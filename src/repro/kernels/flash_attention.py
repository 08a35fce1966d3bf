"""Block-sparse causal flash attention for training, forward and backward,
as Pallas TPU kernels behind one ``jax.custom_vjp``.

What it computes is ``models/attention._gqa_scores_attend`` under a
causal mask, optionally a sliding window (query ``i`` sees key ``j`` iff
``j <= i`` and ``i - j < window``), with the same rounding: each matmul
takes its operands rounded to bfloat16 once and accumulates in float32,
as a default-precision float32 ``einsum`` does on the TPU; softmax
statistics, accumulators and the outputs ``o``, ``dq``, ``dk``, ``dv``
are float32.

* No score or probability tensor reaches HBM.  The running max, sum and
  output of each query block live in VMEM scratch across its key tiles;
  the backward recomputes each tile's probabilities from the forward's
  log-sum-exp.
* Only visible tiles are visited.  The visible (query block, key block)
  pairs are listed at trace time (:func:`tiles`) and prefetched to SMEM;
  the grid walks that list and the index maps read it, so a hidden tile
  is neither computed nor DMA'd.  Tiles wholly inside the mask skip the
  masking arithmetic.
* GQA: one grid step holds the ``g`` query heads of one kv head, so each
  K/V tile is loaded once for all of them.

Three kernels: the forward (rows = query blocks: ``o`` and the
log-sum-exp), ``dq`` (rows = query blocks), and ``dk``/``dv`` (rows =
key blocks, computed transposed so that the per-query statistics
broadcast along lanes).  ``delta = rowsum(do * o)`` is one XLA fusion
between them.  Kernels compile only for multiples of 128 in both block
sizes; interpret mode takes any divisor of the sequence.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(np.finfo(np.float32).max)
LANES = 128
NT = (((1,), (1,)), ((), ()))          # a @ b.T
NN = (((1,), (0,)), ((), ()))          # a @ b
# per-tile flags, prefetched beside the tile list
FIRST, LAST, MASKED = 1, 2, 4
HEAD_DIMS = (64, 128)      # compiled for a v5e in tests/test_chip_compile.py
BLOCKS = (512, 256, 128)   # block sizes, largest first
# scoped VMEM each kernel may take: a v5e core has 128 MiB, the
# compiler's default is 16
VMEM_LIMIT = 32 << 20


def vmem_bytes(g: int, d: int, b: int) -> int:
    """Scoped VMEM the largest of the three kernels takes with ``g``
    query heads a kv head, head dim ``d`` and ``b``-position blocks: its
    double-buffered blocks in and out, its scratch, and the float32
    score tiles the compiler keeps live (one a head in the forward, whose
    head loop is unrolled); a head dim under 128 fills 128 lanes.
    tests/test_chip_compile.py compiles shapes for a v5e with the limit
    set to this estimate, and every GQA shape of the zoo at the block
    :func:`blocks` picks."""
    d = -(-d // LANES) * LANES
    q = g * b * d * 2                  # a bf16 query block of g heads
    kv = b * d * 2                     # a bf16 key or value block
    stat = -(-g // 8) * 8 * b * 4      # g statistics rows, f32
    lanes = g * b * LANES * 4          # a lane-replicated statistic
    acc = g * b * d * 4                # f32 accumulator or output block
    tile = b * b * 4                   # one f32 score tile
    fwd = 2 * (q + 2 * kv) + 2 * (acc + stat) + 2 * lanes + acc \
        + (g + 1) * tile
    dq = 2 * (2 * q + 2 * kv + 2 * stat) + 2 * acc + 2 * lanes + acc \
        + 2 * tile
    dkv = 2 * (2 * q + 2 * kv + 2 * stat) + 6 * (b * d * 4) + 3 * tile
    return max(fwd, dq, dkv)


def blocks(s: int, window: int = 0, g: int = 1,
           d: int = 128) -> Optional[int]:
    """The block size (queries and keys) for a sequence of ``s`` with
    ``g`` query heads a kv head of ``d``: the largest of :data:`BLOCKS`
    that divides ``s``, under a window is at most half of it (or 128),
    and fits :data:`VMEM_LIMIT` by :func:`vmem_bytes`; None when none
    does.

    On a v5e, fwd+bwd of one layer at the benchmark's shapes (4096
    positions) took 2.25 / 2.31 / 3.94 ms at 512 / 256 / 128 under a
    1024-window with 25/5 heads of 64, and 1.67 / 1.88 / 3.18 ms causal
    with 12/2 heads of 128 (PERF.md): larger blocks pay less per grid
    step, smaller ones compute fewer masked positions."""
    for b in BLOCKS:
        if s % b == 0 and (not window or b <= max(window // 2, 128)) \
                and vmem_bytes(g, d, b) <= VMEM_LIMIT:
            return b
    return None


def tiles(s: int, bq: int, bk: int, window: int = 0, by_key: bool = False):
    """The visible (query block, key block) tiles of a causal ``s x s``
    mask (``window`` > 0: sliding window too) and their flags, in row
    order: by query block (``by_key=False``, the forward and dq rows) or
    by key block (the dk/dv rows).  Returns (rows, cols, flags) int32
    arrays, rows the output block of each tile."""
    out = []
    for i in range(s // bq):
        q0, q1 = i * bq, i * bq + bq - 1
        for j in range(s // bk):
            k0, k1 = j * bk, j * bk + bk - 1
            if k0 > q1 or (window and q0 - k1 >= window):
                continue                        # hidden: every key masked
            inside = k1 <= q0 and not (window and q1 - k0 >= window)
            out.append((j, i, inside) if by_key else (i, j, inside))
    out.sort()
    rows = np.array([r for r, _, _ in out], np.int32)
    cols = np.array([c for _, c, _ in out], np.int32)
    flags = np.array([0 if inside else MASKED for _, _, inside in out],
                     np.int32)
    first = np.r_[True, rows[1:] != rows[:-1]]
    last = np.r_[rows[1:] != rows[:-1], True]
    flags |= np.where(first, FIRST, 0) | np.where(last, LAST, 0)
    return rows, cols, flags


def key_tiles(s: int, window: int = 0, g: int = 1, d: int = 128) -> tuple:
    """(tiles computed, tiles of the whole ``s x s`` grid) at the block
    size :func:`blocks` picks, per sequence and kv head."""
    b = blocks(s, window, g, d)
    return len(tiles(s, b, b, window)[0]), (s // b) ** 2


def _visible(q0, k0, shape, window, transposed=False):
    """Mask of a tile whose first query is ``q0`` and first key ``k0``:
    [queries, keys], or [keys, queries] when ``transposed``."""
    qa, ka = (1, 0) if transposed else (0, 1)
    diff = (q0 - k0) + (lax.broadcasted_iota(jnp.int32, shape, qa)
                        - lax.broadcasted_iota(jnp.int32, shape, ka))
    m = diff >= 0
    if window:
        m &= diff < window
    return m


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] statistic widened or cut to n lanes."""
    if n == LANES:
        return x
    if n < LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // LANES))


def _column(row):
    """[1, n] -> [n, 128], each row holding one entry of ``row``."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _branch(flags, body):
    """Run ``body(masked)`` with the masking arithmetic only where the
    tile straddles the mask's edge."""
    masked = (flags & MASKED) != 0
    pl.when(masked)(lambda: body(True))
    pl.when(jnp.logical_not(masked))(lambda: body(False))


# --------------------------------------------------------------------- #
# kernels

def _fwd_kernel(rows, cols, flags, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, window, g, bq, bk):
    t = pl.program_id(2)
    f = flags[t]

    @pl.when((f & FIRST) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        k, v = k_ref[...], v_ref[...]
        d = v.shape[-1]
        if masked:
            vis = _visible(rows[t] * bq, cols[t] * bk, (bq, bk), window)
        for h in range(g):
            s = lax.dot_general(q_ref[h], k, NT,
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(vis, s, NEG_INF)
            m_prev, l_prev = m_scr[h], l_scr[h]
            m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - _lanes(m_next, bk))
            alpha = jnp.exp(m_prev - m_next)
            l_scr[h] = alpha * l_prev + p.sum(axis=-1)[:, None]
            m_scr[h] = m_next
            pv = lax.dot_general(p.astype(v.dtype), v, NN,
                                 preferred_element_type=jnp.float32)
            acc_scr[h] = acc_scr[h] * _lanes(alpha, d) + pv

    _branch(f, body)

    @pl.when((f & LAST) != 0)
    def _finish():
        for h in range(g):
            l = l_scr[h]
            o_ref[h] = acc_scr[h] * _lanes(1.0 / l, o_ref.shape[-1])
            lse = m_scr[h] + jnp.log(l)                 # [bq, 128]
            lse_ref[h:h + 1, :] = lse.T[:1, :]


def _dq_kernel(rows, cols, flags, q_ref, k_ref, v_ref, do_ref, lse_ref,
               di_ref, dq_ref, lse_scr, di_scr, acc_scr, *, scale, window,
               g, bq, bk):
    t = pl.program_id(2)
    f = flags[t]

    @pl.when((f & FIRST) != 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        for h in range(g):
            lse_scr[h] = _column(lse_ref[h:h + 1, :])
            di_scr[h] = _column(di_ref[h:h + 1, :])

    def body(masked):
        k, v = k_ref[...], v_ref[...]
        if masked:
            vis = _visible(rows[t] * bq, cols[t] * bk, (bq, bk), window)
        for h in range(g):
            s = lax.dot_general(q_ref[h], k, NT,
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(vis, s, NEG_INF)
            p = jnp.exp(s - _lanes(lse_scr[h], bk))
            dp = lax.dot_general(do_ref[h], v, NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - _lanes(di_scr[h], bk))
            acc_scr[h] += lax.dot_general(ds.astype(k.dtype), k, NN,
                                          preferred_element_type=jnp.float32)

    _branch(f, body)

    @pl.when((f & LAST) != 0)
    def _finish():
        dq_ref[...] = acc_scr[...] * scale


def _dkv_kernel(rows, cols, flags, q_ref, k_ref, v_ref, do_ref, lse_ref,
                di_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, window,
                g, bq, bk):
    t = pl.program_id(2)
    f = flags[t]

    @pl.when((f & FIRST) != 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        k, v = k_ref[...], v_ref[...]
        if masked:
            vis = _visible(cols[t] * bq, rows[t] * bk, (bk, bq), window,
                           transposed=True)
        for h in range(g):
            q, do = q_ref[h], do_ref[h]
            s = lax.dot_general(k, q, NT,
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(vis, s, NEG_INF)
            p = jnp.exp(s - lse_ref[h:h + 1, :])           # [bk, bq]
            dv_scr[...] += lax.dot_general(
                p.astype(do.dtype), do, NN,
                preferred_element_type=jnp.float32)
            dp = lax.dot_general(v, do, NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - di_ref[h:h + 1, :])
            dk_scr[...] += lax.dot_general(
                ds.astype(q.dtype), q, NN,
                preferred_element_type=jnp.float32)

    _branch(f, body)

    @pl.when((f & LAST) != 0)
    def _finish():
        dk_ref[...] = dk_scr[...] * scale
        dv_ref[...] = dv_scr[...]


# --------------------------------------------------------------------- #
# pallas_calls on the head-major layout: q [B, Hkv, g, S, D],
# k/v [B, Hkv, S, D], per-query statistics [B, Hkv, g, S]

def _call(kernel, name, order, *, inputs, outputs, scratch, window, bq, bk,
          scale, interpret):
    """One kernel over the visible tiles of ``order`` ("q" or "k" rows).
    ``inputs``/``outputs``: (array or ShapeDtypeStruct, kind) with kind
    in q (a query block of g heads), kv (a key block), stat (a query
    block's g statistics rows)."""
    qh = inputs[0][0]
    b, hkv, g, s, d = qh.shape
    rows, cols, flags = tiles(s, bq, bk, window, by_key=(order == "k"))

    def qblk(t, r, c):
        return r[t] if order == "q" else c[t]

    def kblk(t, r, c):
        return c[t] if order == "q" else r[t]

    def spec(kind):
        if kind == "q":
            return pl.BlockSpec((None, None, g, bq, d),
                                lambda i, j, t, r, c, f:
                                (i, j, 0, qblk(t, r, c), 0))
        if kind == "kv":
            return pl.BlockSpec((None, None, bk, d),
                                lambda i, j, t, r, c, f:
                                (i, j, kblk(t, r, c), 0))
        return pl.BlockSpec((None, None, g, bq),
                            lambda i, j, t, r, c, f:
                            (i, j, 0, qblk(t, r, c)))

    kern = functools.partial(kernel, scale=scale, window=window, g=g,
                             bq=bq, bk=bk)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv, len(rows)),
            in_specs=[spec(kind) for _, kind in inputs],
            out_specs=[spec(kind) for _, kind in outputs],
            scratch_shapes=scratch),
        out_shape=[o for o, _ in outputs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=name,
        interpret=interpret,
    )(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(flags),
      *[x for x, _ in inputs])


def _fwd(qh, kh, vh, *, window, bq, bk, scale, interpret):
    b, hkv, g, s, d = qh.shape
    f32 = jnp.float32
    return _call(
        _fwd_kernel, "flash_attention_fwd", "q",
        inputs=[(qh, "q"), (kh, "kv"), (vh, "kv")],
        outputs=[(jax.ShapeDtypeStruct(qh.shape, f32), "q"),
                 (jax.ShapeDtypeStruct((b, hkv, g, s), f32), "stat")],
        scratch=[pltpu.VMEM((g, bq, LANES), f32),
                 pltpu.VMEM((g, bq, LANES), f32),
                 pltpu.VMEM((g, bq, d), f32)],
        window=window, bq=bq, bk=bk, scale=scale, interpret=interpret)


def _bwd(qh, kh, vh, doh, lse, di, *, window, bq, bk, scale, interpret):
    g, d = qh.shape[2], qh.shape[4]
    f32 = jnp.float32
    ins = [(qh, "q"), (kh, "kv"), (vh, "kv"), (doh, "q"), (lse, "stat"),
           (di, "stat")]
    common = dict(window=window, bq=bq, bk=bk, scale=scale,
                  interpret=interpret)
    (dq,) = _call(
        _dq_kernel, "flash_attention_dq", "q", inputs=ins,
        outputs=[(jax.ShapeDtypeStruct(qh.shape, f32), "q")],
        scratch=[pltpu.VMEM((g, bq, LANES), f32),
                 pltpu.VMEM((g, bq, LANES), f32),
                 pltpu.VMEM((g, bq, d), f32)], **common)
    dk, dv = _call(
        _dkv_kernel, "flash_attention_dkv", "k", inputs=ins,
        outputs=[(jax.ShapeDtypeStruct(kh.shape, f32), "kv"),
                 (jax.ShapeDtypeStruct(vh.shape, f32), "kv")],
        scratch=[pltpu.VMEM((bk, d), f32), pltpu.VMEM((bk, d), f32)],
        **common)
    return dq, dk, dv


# --------------------------------------------------------------------- #
# the differentiable op on the model's layout

def _heads(q, k, v):
    """[B,S,Hq,D] q and [B,S,Hkv,D] k/v -> head-major bfloat16."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    bf16 = jnp.bfloat16
    qh = q.reshape(b, s, hkv, hq // hkv, d).transpose(0, 2, 3, 1, 4)
    return (qh.astype(bf16), k.transpose(0, 2, 1, 3).astype(bf16),
            v.transpose(0, 2, 1, 3).astype(bf16))


def _unheads_q(xh):
    b, hkv, g, s, d = xh.shape
    return xh.transpose(0, 3, 1, 2, 4).reshape(b, s, hkv * g, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attend(q, k, v, window, bq, bk, scale, interpret):
    return _attend_fwd(q, k, v, window, bq, bk, scale, interpret)[0]


def _attend_fwd(q, k, v, window, bq, bk, scale, interpret):
    qh, kh, vh = _heads(q, k, v)
    oh, lse = _fwd(qh, kh, vh, window=window, bq=bq, bk=bk, scale=scale,
                   interpret=interpret)
    out = _unheads_q(oh)
    # ``out`` is the array the output projection keeps for its own
    # gradient: saving it rather than ``oh`` stores the output once
    return out, (qh, kh, vh, out, lse)


def _attend_bwd(window, bq, bk, scale, interpret, res, do):
    qh, kh, vh, out, lse = res
    b, hkv, g, s, d = qh.shape
    di = jnp.sum(do * out, axis=-1).reshape(b, s, hkv, g) \
        .transpose(0, 2, 3, 1)
    doh = do.reshape(b, s, hkv, g, d).transpose(0, 2, 3, 1, 4)
    dq, dk, dv = _bwd(qh, kh, vh, doh.astype(jnp.bfloat16), lse, di,
                      window=window, bq=bq, bk=bk, scale=scale,
                      interpret=interpret)
    return (_unheads_q(dq), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int = 0, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Causal (``window`` > 0: sliding-window) attention, differentiable:
    q [B,S,Hq,D], k/v [B,S,Hkv,D] -> [B,S,Hq,D] in q's dtype.

    The blocks default to :func:`blocks`; ``S`` must be a multiple of
    each.
    """
    s, d = q.shape[1], q.shape[-1]
    assert k.shape[1] == s and q.shape[2] % k.shape[2] == 0, \
        (q.shape, k.shape)
    g = q.shape[2] // k.shape[2]
    bq = block_q or blocks(s, window, g, d)
    bk = block_k or blocks(s, window, g, d)
    assert bq and bk and s % bq == 0 and s % bk == 0, (s, bq, bk)
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    f32 = jnp.float32
    out = _attend(q.astype(f32), k.astype(f32), v.astype(f32), int(window),
                  int(bq), int(bk), float(scale), bool(interpret))
    return out.astype(q.dtype)
