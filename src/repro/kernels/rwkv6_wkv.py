"""RWKV-6 WKV recurrence as a chunked Pallas TPU kernel.

GPU implementations (e.g. the official CUDA wkv6 kernel) give each thread one
channel and loop serially over time in registers.  That shape does not map to
TPU; instead we:

  * keep the per-(batch, head) state matrix S [D, D] resident in VMEM
    scratch for the whole sequence,
  * stream r/k/v/w through VMEM in time-chunks of ``block_t`` via BlockSpec
    index maps (grid = (B*H, time_chunks), time sequential/"arbitrary"),
  * run the recurrence inside the chunk with a fori_loop over VMEM-resident
    rows — each step is rank-1 update + matvec on a [D, D] tile (D = 64 for
    the pool's RWKV config, one (8,128)-aligned VREG tile pair).

The chunk boundary state is also written out so callers can resume (decode).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
                s_scr, *, block_t: int, t_chunks: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _load_state():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)          # [1, D] -> broadcast row
    r = r_ref[0].astype(jnp.float32)          # [block_t, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)

    def step(t, carry):
        y_acc = carry
        r_t = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)   # [1, D]
        k_t = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        v_t = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        w_t = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        S = s_scr[...]                                   # [D, D] (j, i)
        kv = k_t.T * v_t                                 # [D, D] rank-1
        # y[i] = sum_j r[j] (S[j,i] + u[j] kv[j,i])
        y_t = jax.lax.dot_general(
            r_t, S + u.T * kv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [1, D]
        s_scr[...] = w_t.T * S + kv
        y_acc = jax.lax.dynamic_update_slice_in_dim(y_acc, y_t, t, 0)
        return y_acc

    y = jax.lax.fori_loop(0, block_t, step,
                          jnp.zeros((block_t, r.shape[1]), jnp.float32))
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ti == t_chunks - 1)
    def _store_state():
        sT_ref[0] = s_scr[...]


def rwkv6_wkv(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, state: jax.Array, *, block_t: int = 64,
              interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """r/k/v/w [B,S,H,D]; u [H,D]; state [B,H,D,D] -> (y [B,S,H,D], sT)."""
    b, s, h, d = r.shape
    block_t = min(block_t, s)
    assert s % block_t == 0, (s, block_t)
    t_chunks = s // block_t

    def bh(x):  # [B,S,H,D] -> [B*H, S, D]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    rr, kk, vv, ww = bh(r), bh(k), bh(v), bh(w)
    uu = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, 1, d)
    s0 = state.reshape(b * h, d, d)

    seq_map = lambda i, ti: (i, ti, 0)
    fix_map = lambda i, ti: (i, 0, 0)

    kernel = functools.partial(_wkv_kernel, block_t=block_t,
                               t_chunks=t_chunks)
    y, sT = pl.pallas_call(
        kernel,
        name="rwkv6_wkv",
        grid=(b * h, t_chunks),
        in_specs=[
            pl.BlockSpec((1, block_t, d), seq_map),   # r
            pl.BlockSpec((1, block_t, d), seq_map),   # k
            pl.BlockSpec((1, block_t, d), seq_map),   # v
            pl.BlockSpec((1, block_t, d), seq_map),   # w
            pl.BlockSpec((1, 1, d), fix_map),          # u
            pl.BlockSpec((1, d, d), fix_map),          # s0
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, d), seq_map),
            pl.BlockSpec((1, d, d), fix_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rr, kk, vv, ww, uu, s0)

    y = y.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return y, sT.reshape(b, h, d, d)
