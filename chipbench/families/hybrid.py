"""Hymba's layer: attention and a selective-SSM head in parallel on the
same normed input, each normed again and averaged, then a SwiGLU MLP.

The SSM is solved by associative scans over blocks of positions, carried
from block to block, rather than by a loop over time.  Its sizes that
the configuration does not state (the file lists them as assumed) are
the constants below."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench import reference as R

FIELDS = ("ssm_state", "ssm_expand")

DT_RANK_DIV = 16     # dt rank = d_model / 16
CONV_K = 4           # causal depthwise conv width
SSM_BLOCK = 512      # positions per block of the scan


def _sizes(cfg):
    d = cfg["d_model"]
    return d * cfg["ssm_expand"], cfg["ssm_state"], max(1, d // DT_RANK_DIV)


def _ssm_init(key, cfg):
    d = cfg["d_model"]
    ci, n, r = _sizes(cfg)
    k = jax.random.split(key, 7)
    return {
        "in_proj": R.linear_init(k[0], d, 2 * ci),
        "conv_w": 0.1 * jax.random.normal(k[1], (CONV_K, ci)),
        "conv_b": jnp.zeros((ci,), jnp.float32),
        "x_proj": R.linear_init(k[2], ci, r + 2 * n),
        "dt_proj": R.linear_init(k[3], r, ci),
        "dt_bias": jnp.full((ci,), -4.6, jnp.float32),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32),
                                  (ci, 1))),
        "D": jnp.ones((ci,), jnp.float32),
        "out_proj": R.linear_init(k[4], ci, d),
    }


def layer(key, cfg):
    d = cfg["d_model"]
    k = jax.random.split(key, 3)
    return {"ln_in": R.norm_init(d), "attn": R.gqa_init(k[0], cfg),
            "ssm": _ssm_init(k[1], cfg), "ln_attn": R.norm_init(d),
            "ln_ssm": R.norm_init(d), "ln_mlp": R.norm_init(d),
            "mlp": R.mlp_init(k[2], d, cfg["d_ff"])}


def init(key, cfg):
    k = jax.random.split(key, 3)
    p = R.embed_init(k[0], cfg)
    p["layers"] = jax.vmap(lambda kk: layer(kk, cfg))(
        jax.random.split(k[1], cfg["n_layers"]))
    p["lm_head"] = R.linear_init(k[2], cfg["d_model"],
                                 counts.padded_vocab(cfg))
    return p


def _combine(left, right):
    """Compose two steps of h <- a * h + b (left first)."""
    return right[0] * left[0], right[0] * left[1] + right[1]


def selective_ssm(p, x, cfg):
    ci, n, r = _sizes(cfg)
    uz = x @ p["in_proj"]
    u, z = uz[..., :ci], uz[..., ci:]
    pad = jnp.concatenate([jnp.zeros_like(u[:, :CONV_K - 1]), u], 1)
    b, s = u.shape[:2]
    conv = sum(pad[:, i:i + s] * p["conv_w"][i] for i in range(CONV_K))
    u = jax.nn.silu(conv + p["conv_b"])
    proj = u @ p["x_proj"]
    dt = jax.nn.softplus(proj[..., :r] @ p["dt_proj"] + p["dt_bias"])
    bm, cm = proj[..., r:r + n], proj[..., r + n:]
    a = -jnp.exp(p["A_log"])                                  # [Ci, N]

    # h_t = exp(dt_t a) h_{t-1} + dt_t u_t b_t, solved by an associative
    # scan within blocks of positions and carried across them
    @jax.checkpoint
    def scan_block(h, xs):
        dt_k, u_k, b_k, c_k = xs
        decay = jnp.exp(dt_k[..., None] * a)
        inp = (dt_k * u_k)[..., None] * b_k[:, :, None]
        prod, acc = jax.lax.associative_scan(_combine, (decay, inp), axis=1)
        hs = prod * h[:, None] + acc
        return hs[:, -1], jnp.einsum("btcn,btn->btc", hs, c_k)

    t = min(SSM_BLOCK, s)
    split = lambda v: jnp.moveaxis(v.reshape((b, s // t, t) + v.shape[2:]),
                                   1, 0)
    _, ys = jax.lax.scan(scan_block, jnp.zeros((b, ci, n), dt.dtype),
                         (split(dt), split(u), split(bm), split(cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, ci) + u * p["D"]
    return (y * jax.nn.silu(z)) @ p["out_proj"]


def block(lp, x, ang, cfg):
    eps = cfg["norm_eps"]
    h = R.rms(lp["ln_in"], x, eps)
    a = R.attention(lp["attn"], h, ang, cfg, cfg["sliding_window"])
    m = selective_ssm(lp["ssm"], h, cfg)
    x = x + 0.5 * (R.rms(lp["ln_attn"], a, eps) + R.rms(lp["ln_ssm"], m, eps))
    return x + R.swiglu(lp["mlp"], R.rms(lp["ln_mlp"], x, eps))


def layer_flops(cfg, seq: int) -> int:
    d = cfg["d_model"]
    ci, n, r = _sizes(cfg)
    ssm = (2 * d * 2 * ci              # in_proj
           + 2 * CONV_K * ci           # depthwise conv
           + 2 * ci * (r + 2 * n)      # x_proj
           + 2 * r * ci                # dt_proj
           + 6 * ci * n                # recurrence and readout
           + 2 * ci * d)               # out_proj
    return (counts.attention_flops(cfg, seq) + counts.mlp_flops(cfg, seq)
            + ssm * seq)


def layer_params(cfg) -> int:
    d = cfg["d_model"]
    ci, n, r = _sizes(cfg)
    ssm = (d * 2 * ci + CONV_K * ci + ci + ci * (r + 2 * n) + r * ci
           + ci + ci * n + ci + ci * d)
    return (counts.attention_params(cfg) + counts.mlp_params(cfg) + ssm
            + 4 * d)
