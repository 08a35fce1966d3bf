"""Selective SSM (Mamba-style) head used by the Hymba hybrid block.

    h_t = exp(dt_t * A) ⊙ h_{t-1} + dt_t * (B_t ⊗ u_t)
    y_t = C_t · h_t + D ⊙ u_t

with A diagonal (negative), and (dt, B, C) input-dependent ("selective").
Includes the causal depthwise conv1d front (kernel 4) with carried conv
state for decode.  Full-sequence path is a `lax.scan` over time (on TPU the
chunked-kernel pattern demonstrated by kernels/rwkv6_wkv.py applies; the SSM
scan shares its structure).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import Params, dense_init
from repro.telemetry.spans import SSM

CONV_K = 4
DT_RANK_DIV = 16


def mamba_init(key, d_model: int, d_inner: int, state: int,
               dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 7)
    dt_rank = max(1, d_model // DT_RANK_DIV)
    return {
        "in_proj": dense_init(ks[0], d_model, 2 * d_inner, dtype),
        "conv_w": (0.1 * jax.random.normal(ks[1], (CONV_K, d_inner))).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "x_proj": dense_init(ks[2], d_inner, dt_rank + 2 * state, dtype),
        "dt_proj": dense_init(ks[3], dt_rank, d_inner, dtype),
        "dt_bias": jnp.full((d_inner,), -4.6, dtype),  # softplus^-1(0.01)
        "A_log": jnp.log(jnp.tile(jnp.arange(1, state + 1, dtype=jnp.float32),
                                  (d_inner, 1))).astype(dtype),
        "D": jnp.ones((d_inner,), dtype),
        "out_proj": dense_init(ks[4], d_inner, d_model, dtype),
    }


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array,
                 conv_state=None) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. u [B,S,C]; w [K,C].  Returns (y, tail [B,K-1,C])."""
    if conv_state is None:
        pad = jnp.zeros_like(u[:, : CONV_K - 1])
    else:
        pad = conv_state.astype(u.dtype)
    ext = jnp.concatenate([pad, u], axis=1)
    y = sum(ext[:, i:i + u.shape[1]] * w[i] for i in range(CONV_K))
    return jax.nn.silu(y + b), ext[:, -(CONV_K - 1):].astype(jnp.float32)


def _ssm_params(p: Params, u: jax.Array, state: int):
    dt_rank = p["dt_proj"].shape[0]
    proj = u @ p["x_proj"]
    dt = jax.nn.softplus(proj[..., :dt_rank] @ p["dt_proj"]
                         + p["dt_bias"]).astype(jnp.float32)     # [B,S,Ci]
    B = proj[..., dt_rank:dt_rank + state].astype(jnp.float32)   # [B,S,N]
    C = proj[..., dt_rank + state:].astype(jnp.float32)          # [B,S,N]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                 # [Ci,N]
    return dt, B, C, A


@jax.named_scope(SSM)
def mamba_apply(p: Params, x: jax.Array, *, state: int,
                ssm_state=None, conv_state=None, chunk: int = 256):
    """Full-sequence selective scan, time-chunked.

    The naive formulation materializes dA/dBu [B,S,Ci,N] (gigabytes at 4k
    seq).  We scan over sequence CHUNKS with a rematerialized chunk body:
    dA/dBu exist only per chunk ([B,chunk,Ci,N]) and the backward pass
    recomputes them, storing only the [B,Ci,N] states at chunk boundaries.
    """
    b, s, _ = x.shape
    ui = x @ p["in_proj"]
    d_inner = ui.shape[-1] // 2
    u, z = ui[..., :d_inner], ui[..., d_inner:]
    u, conv_tail = _causal_conv(u, p["conv_w"], p["conv_b"], conv_state)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))        # [Ci,N]

    if ssm_state is None:
        h0 = jnp.zeros((b, d_inner, state), jnp.float32)
    else:
        h0 = ssm_state

    def chunk_body(h, u_c):
        """u_c [B, tc, Ci] -> (h_end, y_c [B, tc, Ci])."""
        dt, Bm, Cm, _ = _ssm_params(p, u_c, state)
        dA = jnp.exp(dt[..., None] * A)                 # [B,tc,Ci,N]
        dBu = (dt * u_c.astype(jnp.float32))[..., None] * Bm[:, :, None]

        def step(hh, inp):
            dA_t, dBu_t, C_t = inp
            hh = dA_t * hh + dBu_t
            return hh, jnp.einsum("bcn,bn->bc", hh, C_t)

        hT, ys = jax.lax.scan(step, h,
                              (dA.swapaxes(0, 1), dBu.swapaxes(0, 1),
                               Cm.swapaxes(0, 1)))
        return hT, ys.swapaxes(0, 1)

    tc = min(chunk, s)
    if s % tc == 0 and s > tc:
        nc = s // tc
        uc = jnp.moveaxis(u.reshape(b, nc, tc, d_inner), 1, 0)
        hT, ys = jax.lax.scan(jax.checkpoint(chunk_body), h0, uc)
        y = jnp.moveaxis(ys, 0, 1).reshape(b, s, d_inner)
    else:
        hT, y = chunk_body(h0, u)
    y = y.astype(x.dtype)
    y = y + u * p["D"].astype(u.dtype)
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"], hT, conv_tail


def mamba_decode(p: Params, x: jax.Array, states: Dict[str, jax.Array], *,
                 state: int):
    """One token. x [B,1,d]; states {ssm [B,Ci,N], conv [B,K-1,Ci]}."""
    ui = x @ p["in_proj"]
    d_inner = ui.shape[-1] // 2
    u, z = ui[..., :d_inner], ui[..., d_inner:]
    u, conv_tail = _causal_conv(u, p["conv_w"], p["conv_b"], states["conv"])
    dt, B, C, A = _ssm_params(p, u, state)
    dA = jnp.exp(dt[:, 0, :, None] * A)                 # [B,Ci,N]
    dBu = (dt[:, 0] * u[:, 0].astype(jnp.float32))[..., None] * B[:, 0, None]
    h = dA * states["ssm"] + dBu
    y = jnp.einsum("bcn,bn->bc", h, C[:, 0])[:, None].astype(x.dtype)
    y = y + u * p["D"].astype(u.dtype)
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"], {"ssm": h, "conv": conv_tail}


def init_mamba_state(batch: int, d_inner: int, state: int):
    return {
        "ssm": jnp.zeros((batch, d_inner, state), jnp.float32),
        "conv": jnp.zeros((batch, CONV_K - 1, d_inner), jnp.float32),
    }
