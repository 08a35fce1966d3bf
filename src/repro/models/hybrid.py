"""Hymba hybrid block [arXiv:2411.13676]: attention heads and Mamba(SSM)
heads run in PARALLEL on the same normalized input; each branch output is
re-normalized and the two are averaged before the residual add.  Attention
uses a sliding window (the release's few global-attention layers are
approximated by the same window — noted in DESIGN.md).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import mamba as mam
from repro.models.attention import (gqa_attention, gqa_decode, gqa_init,
                                    init_kv_cache, prefill_kv_cache)
from repro.models.common import Params, rmsnorm, rmsnorm_init
from repro.models.mlp import mlp_apply, mlp_init


def hymba_block_init(key, *, d_model: int, n_heads: int, n_kv_heads: int,
                     head_dim: int, d_ff: int, ssm_state: int,
                     ssm_expand: int, act: str, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "ln_in": rmsnorm_init(d_model, dtype),
        "attn": gqa_init(ks[0], d_model, n_heads, n_kv_heads, head_dim, dtype),
        "ssm": mam.mamba_init(ks[1], d_model, d_model * ssm_expand,
                              ssm_state, dtype),
        "ln_attn": rmsnorm_init(d_model, dtype),
        "ln_ssm": rmsnorm_init(d_model, dtype),
        "ln_mlp": rmsnorm_init(d_model, dtype),
        "mlp": mlp_init(ks[2], d_model, d_ff, act, dtype),
    }


def hymba_block_apply(p: Params, x, cos, sin, *, n_heads, n_kv_heads,
                      head_dim, ssm_state, window, eps, act,
                      impl: str = "auto"):
    h = rmsnorm(p["ln_in"], x, eps)
    a = gqa_attention(p["attn"], h, cos, sin, n_heads=n_heads,
                      n_kv_heads=n_kv_heads, head_dim=head_dim,
                      window=window, impl=impl)
    m, _, _ = mam.mamba_apply(p["ssm"], h, state=ssm_state)
    fused = 0.5 * (rmsnorm(p["ln_attn"], a, eps) + rmsnorm(p["ln_ssm"], m, eps))
    x = x + fused
    x = x + mlp_apply(p["mlp"], rmsnorm(p["ln_mlp"], x, eps), act)
    return x


def hymba_block_decode(p: Params, x, state: Dict[str, Any], cos, sin, *,
                       n_heads, n_kv_heads, head_dim, ssm_state, eps, act
                       ) -> Tuple[jax.Array, Dict[str, Any]]:
    h = rmsnorm(p["ln_in"], x, eps)
    a, kv = gqa_decode(p["attn"], h, state["kv"], cos, sin, n_heads=n_heads,
                       n_kv_heads=n_kv_heads, head_dim=head_dim, rolling=True)
    m, ssm = mam.mamba_decode(p["ssm"], h,
                              {"ssm": state["ssm"], "conv": state["conv"]},
                              state=ssm_state)
    fused = 0.5 * (rmsnorm(p["ln_attn"], a, eps) + rmsnorm(p["ln_ssm"], m, eps))
    x = x + fused
    x = x + mlp_apply(p["mlp"], rmsnorm(p["ln_mlp"], x, eps), act)
    return x, {"kv": kv, "ssm": ssm["ssm"], "conv": ssm["conv"]}


def init_hymba_state(batch: int, *, d_model: int, n_kv_heads: int,
                     head_dim: int, ssm_state: int, ssm_expand: int,
                     window: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    kv = init_kv_cache(batch, window, n_kv_heads, head_dim, dtype,
                       rolling=True, window=window)
    ms = mam.init_mamba_state(batch, d_model * ssm_expand, ssm_state)
    return {"kv": kv, "ssm": ms["ssm"], "conv": ms["conv"]}
