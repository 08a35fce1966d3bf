"""The plain decoder: GQA attention with RoPE, SwiGLU MLP, RMSNorm, a
tied or untied head."""
from __future__ import annotations

import jax

from chipbench import counts
from chipbench import reference as R

FIELDS = ()


def layer(key, cfg):
    d = cfg["d_model"]
    k = jax.random.split(key, 2)
    return {"ln1": R.norm_init(d), "attn": R.gqa_init(k[0], cfg),
            "ln2": R.norm_init(d), "ffn": R.mlp_init(k[1], d, cfg["d_ff"])}


def init(key, cfg):
    k = jax.random.split(key, 4)
    p = R.embed_init(k[0], cfg)
    p["layers"] = jax.vmap(lambda kk: layer(kk, cfg))(
        jax.random.split(k[2], cfg["n_layers"]))
    if not cfg["tie_embeddings"]:
        p["lm_head"] = R.linear_init(k[1], cfg["d_model"],
                                     counts.padded_vocab(cfg))
    return p


def block(lp, x, ang, cfg):
    eps = cfg["norm_eps"]
    x = x + R.attention(lp["attn"], R.rms(lp["ln1"], x, eps), ang, cfg,
                        cfg["sliding_window"])
    return x + R.swiglu(lp["ffn"], R.rms(lp["ln2"], x, eps))


def layer_flops(cfg, seq: int) -> int:
    return counts.attention_flops(cfg, seq) + counts.mlp_flops(cfg, seq)


def layer_params(cfg) -> int:
    return (counts.attention_params(cfg) + counts.mlp_params(cfg)
            + 2 * cfg["d_model"])
