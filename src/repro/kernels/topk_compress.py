"""Fused top-k compression (flatten -> abs -> threshold -> gather) as a
Pallas TPU kernel — the sparse reducer's hot path (comm/sparse.py).

TPU-native design (no global sort): an exact top-k via
  1. a 31-step binary search for the k-th magnitude in the fp32 *bit
     domain* — non-negative IEEE floats compare identically as int32, so
     building the threshold bit-by-bit distinguishes every representable
     magnitude (scale-free: a 1e8 outlier next to 1e-3 values costs no
     precision, unlike value-domain bisection) — pure VPU reductions over
     the row held in VMEM, then
  2. compaction of the selected coordinates in index order.  Two
     compaction engines:

     * ``compaction="scan"`` — a chunk-local prefix count assigns each
       kept element its slot *within the chunk*, a one-hot contraction
       packs the chunk's survivors into a window of ``r + 8`` output rows
       starting at the 8-aligned row that holds the *carried offset* (the
       running count of survivors), and the window is added into the
       output (each slot is written by exactly one chunk).  Work is
       O(n * block_n) per row — independent of k — and indices are exact
       int32 (only the chunk-local offset, < block_n, rides the fp32
       contraction), so rows are not capped at 2^24 elements.
     * ``compaction="onehot"`` (legacy) — the contraction targets the
       whole k-wide output with global slots: O(n * k) MXU work per row
       and an fp32 index round-trip capping rows at 2^24 elements.  Kept
       as the reference engine (kernels/ops.py gates its cap on this path
       only, and its "auto" default dispatches here while k < block_n
       under the cap — the k-wide output is then narrower than scan's
       window).

TPU layout: the row is viewed as ``[n_pad / 128, 128]`` — 128 lanes
wide, index order row-major — and walked in chunks of ``r`` sublane rows
(``block_n`` elements rounded up to whole ``(8, 128)`` tiles).  Grid =
(rows,): one program per learner row, the whole row resident in VMEM
(the per-bucket rows Hier-AVG produces are sized by ``bucket_bytes``; a
4 MiB bucket row takes 8 MiB double-buffered).  Mosaic has no cumsum, so
prefix counts are triangular matmuls of 0/1 tiles, exact in fp32; the
packing contraction is one ``[w, 128] x [128, 128]`` one-hot matmul per
sublane row (rows of the output window x lanes of the output slot), and
every store is a whole-tile store at a sublane offset — no dynamic lane
offsets.  Ties at the k-th magnitude resolve to the lowest indices,
matching kernels/ref.py's oracle.

Caveat: the selection is bit-exact, but subnormal *values* (< ~1.2e-38)
flush to zero through the packing contraction (FTZ on the MXU and in the
XLA dot) — irrelevant for the EF reducer, whose residual re-accumulates
anything dropped.

Validated against ref.topk_compress_ref with interpret=True on CPU
(tests/test_kernels.py), including a heavy-tailed row (1e8 outlier next to
~1.0 values) that defeats value-domain bisection and a >2^24-element row
that defeats the legacy engine's fp32 index compaction; compiled for a
TPU v5e at a bucket row by tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_BISECT_ITERS = 31   # one per magnitude bit of a non-negative fp32
_LANE = 128
_SUBLANE = 8


def _dot(a, b):
    # HIGHEST keeps the MXU passes in full fp32 — default precision
    # would round the packed values and float-encoded indices to bf16
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _topk_kernel(x_ref, vals_ref, idx_ref, *, n: int, k: int, r: int,
                 onehot: bool):
    """One row ``[m, 128]``: exact threshold, tie-exact keep mask, and
    compaction into ``[rows_out, 128]`` outputs, ``r`` sublane rows at a
    time."""
    n_chunks = x_ref.shape[1] // r
    chunk = r * _LANE
    sub = jax.lax.broadcasted_iota(jnp.int32, (r, _LANE), 0)
    local = sub * _LANE + jax.lax.broadcasted_iota(jnp.int32, (r, _LANE), 1)

    def load(c):
        x = x_ref[0, pl.ds(pl.multiple_of(c * r, r), r), :]
        # |x| >= 0 has sign bit 0, so its int32 bit pattern orders
        # identically; padding gets -1, below every candidate threshold
        bits = jnp.where(c * chunk + local < n,
                         jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32),
                         jnp.int32(-1))
        return x, bits

    def count(pred):
        def body(c, acc):
            return acc + jnp.sum(pred(load(c)[1]).astype(jnp.int32))
        return jax.lax.fori_loop(0, n_chunks, body, jnp.int32(0))

    # -- exact k-th magnitude: build the largest threshold t (bit by bit,
    # high to low) such that count(bits >= t) >= k ----------------------- #
    def refine(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda b: b >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, _BISECT_ITERS, refine, jnp.int32(0))
    # everything strictly above the k-th magnitude is kept; the remaining
    # slots go to tied elements in index order — lax.top_k's stable
    # tie-break, so oracle and kernel agree even on tied magnitudes
    fill = k - count(lambda b: b > t)

    # inclusive prefix count of a 0/1 [r, 128] tile in index order: along
    # the lanes (upper-triangular ones), plus the totals of earlier rows
    li = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 1)
    upper = jnp.where(li <= lj, 1.0, 0.0)
    ones = jnp.ones((_LANE, _LANE), jnp.float32)
    earlier = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
                        < jax.lax.broadcasted_iota(jnp.int32, (r, r), 0),
                        1.0, 0.0)

    def prefix(mask):
        f = mask.astype(jnp.float32)
        return (_dot(f, upper) + _dot(earlier, _dot(f, ones))).astype(
            jnp.int32)

    w = vals_ref.shape[1] if onehot else r + _SUBLANE
    wrow = jax.lax.broadcasted_iota(jnp.int32, (w, _LANE), 0)
    wpos = wrow * _LANE + jax.lax.broadcasted_iota(jnp.int32, (w, _LANE), 1)
    vals_ref[...] = jnp.zeros_like(vals_ref)
    idx_ref[...] = jnp.zeros_like(idx_ref)

    def compact(c, carry):
        eq_seen, kept = carry
        x, bits = load(c)
        eq = bits == t
        keep = (bits > t) | (eq & (prefix(eq) + eq_seen <= fill))
        rank = prefix(keep) - 1                  # slot among this chunk's
        if onehot:
            # global slots over the whole output: O(n * k) MXU work, the
            # global index rides the fp32 contraction (rows < 2^24)
            base, pos = 0, kept + rank
            src = (c * chunk + local).astype(jnp.float32)
        else:
            # a window of w rows from the 8-aligned row holding slot
            # `kept`: O(n * r) work whatever k, and only the chunk-local
            # offset (< chunk) rides the fp32 contraction
            base = (kept // (_SUBLANE * _LANE)) * _SUBLANE
            pos = kept - base * _LANE + rank
            src = local.astype(jnp.float32)
        row, lane_t = pos // _LANE, (pos % _LANE).T       # lane_t [128, r]
        acc_v = jnp.zeros((w, _LANE), jnp.float32)
        acc_i = jnp.zeros((w, _LANE), jnp.float32)
        for i in range(r):
            to_row = (row[i:i + 1] == wrow) & keep[i:i + 1]   # [w, 128]
            to_lane = jnp.where(lane_t[:, i:i + 1] == lj, 1.0, 0.0)
            acc_v += _dot(jnp.where(to_row, x[i:i + 1], 0.0), to_lane)
            acc_i += _dot(jnp.where(to_row, src[i:i + 1], 0.0), to_lane)
        n_keep = jnp.sum(keep.astype(jnp.int32))
        if onehot:
            vals_ref[0] += acc_v
            idx_ref[0] += acc_i
        else:
            rows = pl.ds(pl.multiple_of(base, _SUBLANE), w)
            lo = kept - base * _LANE
            mine = (wpos >= lo) & (wpos < lo + n_keep)
            vals_ref[0, rows, :] += acc_v
            idx_ref[0, rows, :] += jnp.where(
                mine, acc_i.astype(jnp.int32) + c * chunk, 0)
        return eq_seen + jnp.sum(eq.astype(jnp.int32)), kept + n_keep

    jax.lax.fori_loop(0, n_chunks, compact, (jnp.int32(0), jnp.int32(0)))


def topk_compress(x: jax.Array, k: int, *, block_n: int = 1024,
                  interpret: bool = False,
                  compaction: str = "scan") -> Tuple[jax.Array, jax.Array]:
    """x [rows, n] -> (values [rows, k] in x.dtype, indices [rows, k] int32,
    ascending per row).  Matches ref.topk_compress_ref exactly (ties at the
    k-th magnitude break to the lowest indices, like lax.top_k).

    ``compaction="scan"`` is the k-independent carried-offset engine;
    ``"onehot"`` is the legacy O(n*k) matmul scatter (rows capped at
    2^24 elements — enforce via kernels/ops.py, whose "auto" default
    picks between them by k/block_n and row length).  ``block_n`` is the
    compaction chunk in elements, rounded up to whole ``(8, 128)`` tiles.
    """
    rows, n = x.shape
    assert 1 <= k <= n, (k, n)
    r = -(-min(block_n, n) // (_SUBLANE * _LANE)) * _SUBLANE
    chunk = r * _LANE
    n_pad = -(-n // chunk) * chunk
    xf = x.astype(jnp.float32)
    if n_pad != n:
        xf = jnp.pad(xf, ((0, 0), (0, n_pad - n)))
    xf = xf.reshape(rows, n_pad // _LANE, _LANE)

    if compaction == "onehot":
        assert n < 2 ** 24, "onehot compaction accumulates indices in fp32"
        out_rows = -(-k // (_SUBLANE * _LANE)) * _SUBLANE
        idx_dtype = jnp.float32
    elif compaction == "scan":
        # the last window starts at the 8-aligned row holding slot k
        out_rows = (k // (_SUBLANE * _LANE)) * _SUBLANE + r + _SUBLANE
        idx_dtype = jnp.int32
    else:
        raise ValueError(
            f"unknown compaction {compaction!r}; use 'scan' or 'onehot'")

    kernel = functools.partial(_topk_kernel, n=n, k=k, r=r,
                               onehot=compaction == "onehot")
    out_spec = pl.BlockSpec((1, out_rows, _LANE), lambda i: (i, 0, 0))
    vals, idx = pl.pallas_call(
        kernel,
        name="topk_compress",
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, n_pad // _LANE, _LANE),
                               lambda i: (i, 0, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, out_rows, _LANE),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((rows, out_rows, _LANE), idx_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xf)
    vals = vals.reshape(rows, -1)[:, :k]
    idx = idx.reshape(rows, -1)[:, :k]
    return vals.astype(x.dtype), idx.astype(jnp.int32)
