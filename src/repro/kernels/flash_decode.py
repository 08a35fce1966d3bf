"""Paged flash-decode attention as a Pallas TPU kernel.

The serving decode hot path: one query token per sequence against a paged
KV cache — fixed-size pages owned by a global pool, gathered per sequence
through a block table (serve/kvcache.py).  Reuses the online-softmax
blocking of kernels/flash_attention.py, adapted to the decode shape:

  * Grid = (batch, kv_heads, pages_per_seq).  The last axis is the
    **split-KV reduction over the cache length**: it is iterated
    sequentially ("arbitrary") and the running (m, l, acc) softmax state
    for the single query position lives in VMEM scratch across page
    steps, exactly like the kv axis of the prefill flash kernel.
  * The block table is a **scalar-prefetch** argument
    (pltpu.PrefetchScalarGridSpec): the K/V BlockSpec index map reads
    ``block_tables[b, i]`` to pick which physical page the next grid step
    streams from HBM — the gather never materializes a dense cache.
  * GQA is expressed in the grid: one program per (batch, kv head)
    handles all ``Hq // Hkv`` query heads of that group at once (they
    share the K/V stream), so K/V pages are read exactly once.
  * Pages past the sequence length short-circuit via ``pl.when``; the
    final partial page and the optional sliding window are masked with
    block-level iota.  Fully-masked sequences (inactive serving slots,
    ``lengths == 0``) output zeros.

Pool layout [Hkv, P, page, D] is head-major so a (page, D) tile streams
contiguously per kv head.  Validated against
kernels/ref.py::flash_decode_ref with interpret=True on CPU
(tests/test_kernels.py), auto-dispatched via kernels/ops.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1.0e30


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int,
                   page: int, pages_per_seq: int):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]                 # tokens incl. the query token
    base = i * page

    # skip pages entirely past the sequence end, and (with a sliding
    # window) pages that have entirely fallen out of the query's window
    # (query position = seq_len - 1)
    run = base < seq_len
    if window:
        run &= base + page - 1 >= seq_len - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)          # [page, D]
        # f32 contractions at full precision, as the model computes: the
        # tiles are a few rows, so the extra MXU passes cost nothing
        # beside the page reads
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) * scale   # [G, page]

        kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_len
        if window:
            mask &= (seq_len - 1 - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # [G, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(i == pages_per_seq - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                 block_tables: jax.Array, lengths: jax.Array, *,
                 window: int = 0, scale: Optional[float] = None,
                 interpret: bool = False) -> jax.Array:
    """q [B, Hq, D]; k_pages/v_pages [Hkv, P, page, D];
    block_tables [B, max_pages] int32 (page-order per sequence, null-page 0
    for unallocated tail entries); lengths [B] int32 incl. the query token.
    Returns [B, Hq, D] in q.dtype.
    """
    b, hq, d = q.shape
    hkv, _, page, _ = k_pages.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    maxp = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5

    qr = q.reshape(b, hkv, g, d)
    tables = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    def q_map(bi, h, i, tbl, ln):
        return (bi, h, 0, 0)

    def kv_map(bi, h, i, tbl, ln):
        return (h, tbl[bi, i], 0, 0)

    kernel = functools.partial(
        _decode_kernel, scale=float(scale), window=window, page=page,
        pages_per_seq=maxp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), q_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="flash_decode",
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lens, qr, k_pages, v_pages)

    return out.reshape(b, hq, d)
