"""A test family of two kinds of layer and a loss term of its own: the
reference side of the program's tiny ``deepseek-v2-lite-16b`` twin, as
a configuration would bring it in as files alone.

``params["layers_dense"]`` (``first_k_dense`` layers of MLA attention
and a SwiGLU MLP) come first, then ``params["layers"]`` (MLA attention
and a routed mixture of SwiGLU experts, top-k of a softmax router with
the chosen gates renormalised, plus shared experts).  The stack adds
``router_aux_coef`` times the mean over the expert layers of the
switch-style balance loss.  Experts are dropless here, so where the
program's capacity drops a token the two differ.  Rotary angles span
the ``qk_rope_head_dim`` part of each query and key, not ``head_dim``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench import reference as R

FIELDS = ("n_experts", "n_shared_experts", "top_k", "expert_d_ff",
          "first_k_dense", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "router_aux_coef")


def _mla_init(key, cfg):
    d, h = cfg["d_model"], cfg["n_heads"]
    lora, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    k = jax.random.split(key, 6)
    return {"wq": R.linear_init(k[0], d, h * (nope + rope)),
            "w_dkv": R.linear_init(k[1], d, lora),
            "w_kr": R.linear_init(k[2], d, rope),
            "kv_norm": R.norm_init(lora),
            "w_uk": R.linear_init(k[3], lora, h * nope),
            "w_uv": R.linear_init(k[4], lora, h * v),
            "wo": R.linear_init(k[5], h * v, d)}


def _moe_init(key, cfg):
    d, f, e = cfg["d_model"], cfg["expert_d_ff"], cfg["n_experts"]
    k = jax.random.split(key, 3)
    return {"experts": jax.vmap(lambda kk: R.mlp_init(kk, d, f))(
                jax.random.split(k[0], e)),
            "router": R.linear_init(k[1], d, e),
            "shared": R.mlp_init(k[2], d, f * cfg["n_shared_experts"])}


def _layer(key, cfg, moe: bool):
    d = cfg["d_model"]
    k = jax.random.split(key, 2)
    return {"ln1": R.norm_init(d), "attn": _mla_init(k[0], cfg),
            "ln2": R.norm_init(d),
            "ffn": _moe_init(k[1], cfg) if moe
            else R.mlp_init(k[1], d, cfg["d_ff"])}


def _depths(cfg):
    return cfg["first_k_dense"], cfg["n_layers"] - cfg["first_k_dense"]


def init(key, cfg):
    pre, main = _depths(cfg)
    k = jax.random.split(key, 4)
    p = R.embed_init(k[0], cfg)
    p["lm_head"] = R.linear_init(k[1], cfg["d_model"],
                                 counts.padded_vocab(cfg))
    p["layers"] = jax.vmap(lambda kk: _layer(kk, cfg, True))(
        jax.random.split(k[2], main))
    p["layers_dense"] = jax.vmap(lambda kk: _layer(kk, cfg, False))(
        jax.random.split(k[3], pre))
    return p


def mla(p, x, ang, cfg):
    b, s, _ = x.shape
    h = cfg["n_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    q = (x @ p["wq"]).reshape(b, s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], R._rotate(q[..., nope:], ang)
    ckv = R.rms(p["kv_norm"], x @ p["w_dkv"], cfg["norm_eps"])
    k_rope = R._rotate((x @ p["w_kr"])[:, :, None], ang)[:, :, 0]
    k_nope = (ckv @ p["w_uk"]).reshape(b, s, h, nope)
    val = (ckv @ p["w_uv"]).reshape(b, s, h, v)
    sc = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
          + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope))
    see = jnp.arange(s)[None] <= jnp.arange(s)[:, None]
    sc = jnp.where(see, sc / math.sqrt(nope + rope), -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), val)
    return out.reshape(b, s, h * v) @ p["wo"]


def moe(p, x, cfg):
    """-> (output, balance loss averaged over the rows)."""
    e, k = cfg["n_experts"], cfg["top_k"]
    probs = jax.nn.softmax(x @ p["router"], -1)                # [B, S, E]
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, e)                             # [B, S, k, E]
    weight = jnp.einsum("bsk,bske->bse", gate, chosen)
    every = jax.vmap(lambda ep: R.swiglu(ep, x))(p["experts"])  # [E, B, S, d]
    y = jnp.einsum("bse,ebsd->bsd", weight, every) + R.swiglu(p["shared"], x)
    me = jnp.mean(probs, 1)                                       # [B, E]
    ce = jnp.sum(chosen, 2).mean(1)                               # [B, E]
    return y, jnp.mean(e * jnp.sum(me * ce, -1) / k)


def _block(lp, x, ang, cfg, expert: bool):
    eps = cfg["norm_eps"]
    x = x + mla(lp["attn"], R.rms(lp["ln1"], x, eps), ang, cfg)
    h = R.rms(lp["ln2"], x, eps)
    if not expert:
        return x + R.swiglu(lp["ffn"], h), 0.0
    y, aux = moe(lp["ffn"], h, cfg)
    return x + y, aux


def stack(params, x, cfg):
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * R._inv_freq(cfg["qk_rope_head_dim"], cfg["rope_theta"]))

    def dense(x, lp):
        return jax.checkpoint(lambda x, lp: _block(lp, x, ang, cfg, False)[0]
                              )(x, lp), None

    def expert(carry, lp):
        x, aux = jax.checkpoint(lambda x, lp: _block(lp, x, ang, cfg, True)
                                )(carry[0], lp)
        return (x, carry[1] + aux), None

    x, _ = jax.lax.scan(dense, x, params["layers_dense"])
    (x, aux), _ = jax.lax.scan(expert, (x, jnp.zeros((), x.dtype)),
                               params["layers"])
    return x, cfg["router_aux_coef"] * aux / _depths(cfg)[1]


def _mla_flops(cfg, seq: int) -> int:
    d, h = cfg["d_model"], cfg["n_heads"]
    lora, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    proj = 2 * (d * h * (nope + rope) + d * lora + d * rope
                + lora * h * nope + lora * h * v + h * v * d)
    return proj * seq + 2 * h * (nope + rope + v) * counts.visible_keys(
        seq, 0)


def _mla_params(cfg) -> int:
    d, h = cfg["d_model"], cfg["n_heads"]
    lora, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (d * h * (nope + rope) + d * lora + d * rope + lora
            + lora * h * nope + lora * h * v + h * v * d)


def stack_flops(cfg, seq: int) -> int:
    """Each token through the router, its ``top_k`` experts and the
    shared ones."""
    pre, main = _depths(cfg)
    d, f = cfg["d_model"], cfg["expert_d_ff"]
    expert = (2 * d * cfg["n_experts"]
              + 6 * d * f * (cfg["top_k"] + cfg["n_shared_experts"])) * seq
    return (pre * (_mla_flops(cfg, seq) + counts.mlp_flops(cfg, seq))
            + main * (_mla_flops(cfg, seq) + expert))


def stack_params(cfg) -> int:
    pre, main = _depths(cfg)
    d, f = cfg["d_model"], cfg["expert_d_ff"]
    expert = d * cfg["n_experts"] + 3 * d * f * (cfg["n_experts"]
                                                 + cfg["n_shared_experts"])
    return (pre * (_mla_params(cfg) + counts.mlp_params(cfg))
            + main * (_mla_params(cfg) + expert) + 2 * d * (pre + main))
