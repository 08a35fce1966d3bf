"""Pure-jnp oracles for every Pallas kernel.

These are the semantics of record: kernel tests sweep shapes/dtypes and
assert_allclose against these functions, and the XLA model paths call them
directly (``impl="xla"``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1.0e30


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        scale: Optional[float] = None) -> jax.Array:
    """Reference attention.

    q [B, S, Hq, D]; k/v [B, T, Hkv, D] with Hq % Hkv == 0.
    Returns [B, S, Hq, D] in q.dtype.
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    qg = q.reshape(b, s, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(t)[None, :]
        m = kpos <= qpos
        if window:
            m &= (qpos - kpos) < window
        scores = jnp.where(m[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, hq, d).astype(q.dtype)


def gather_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize a paged pool as a dense per-sequence cache.

    pages [Hkv, P, page, D] (the serving pool layout: head-major so one
    kv head streams contiguously); block_tables [B, max_pages] int32 ->
    dense [B, max_pages * page, Hkv, D].  Entry ``j`` of the dense view is
    global cache position ``j`` because a sequence's block table lists its
    pages in position order.
    """
    hkv, _, page, d = pages.shape
    b, maxp = block_tables.shape
    g = pages[:, block_tables]                     # [Hkv, B, maxp, page, D]
    return g.transpose(1, 2, 3, 0, 4).reshape(b, maxp * page, hkv, d)


def flash_decode_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     block_tables: jax.Array, lengths: jax.Array, *,
                     window: int = 0,
                     scale: Optional[float] = None) -> jax.Array:
    """Reference paged decode attention (XLA gather path).

    One query token per sequence against a paged KV pool:
      q [B, Hq, D]; k_pages/v_pages [Hkv, P, page, D];
      block_tables [B, max_pages] int32; lengths [B] int32 — valid cache
      tokens per sequence INCLUDING the current one (the query sits at
      position lengths-1, already written into its page).

    Key j is visible iff j < lengths[b] and (window == 0 or
    lengths[b]-1 - j < window).  Sequences with lengths == 0 (inactive
    slots) produce zeros instead of NaN.  Returns [B, Hq, D] in q.dtype.
    """
    b, hq, d = q.shape
    hkv = k_pages.shape[0]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    k = gather_pages(k_pages, block_tables)        # [B, T, Hkv, D]
    v = gather_pages(v_pages, block_tables)
    t = k.shape[1]
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg,
                        k.astype(jnp.float32)) * scale
    kpos = jnp.arange(t)[None, :]
    valid = kpos < lengths[:, None]
    if window:
        valid &= (lengths[:, None] - 1 - kpos) < window
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    # all-masked rows (inactive slots): uniform probs would mix garbage,
    # so zero the output instead
    any_valid = valid.any(axis=1)[:, None, None, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v.astype(jnp.float32))
    out = jnp.where(any_valid, out, 0.0)
    return out.reshape(b, hq, d).astype(q.dtype)


def topk_compress_ref(x: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Per-row magnitude top-k selection (the sparse-reducer hot path).

    x [rows, n] -> (values [rows, k] in x.dtype, indices [rows, k] int32).
    Indices are ascending per row (index order, not magnitude order), so the
    Pallas kernel's threshold+compaction pass produces identical output when
    the k-th magnitude is untied.
    """
    _, idx = jax.lax.top_k(jnp.abs(x.astype(jnp.float32)), k)
    idx = jnp.sort(idx, axis=-1)
    vals = jnp.take_along_axis(x, idx, axis=-1)
    return vals, idx.astype(jnp.int32)


def batched_qr_ref(p: jax.Array) -> jax.Array:
    """Batched thin-QR Q factor: ``[..., a, r] -> Q [..., a, r]``.

    XLA lowers this to one Householder QR per batch element (LAPACK on
    CPU).  Column signs follow LAPACK's convention; the Pallas CGS2
    kernel (kernels/batched_qr.py) may flip per-column signs, so parity
    tests compare the projector ``Q Q^T`` — the only quantity PowerSGD's
    reconstruction consumes — rather than the raw factor.
    """
    q, _ = jnp.linalg.qr(p.astype(jnp.float32))
    return q.astype(p.dtype)


_QINT8_SCALE_BYTES = 4


def qint8_pack_ref(x: jax.Array, block: int) -> jax.Array:
    """Fused quantize+pack oracle: ``[..., n] -> int8 [..., nb,
    block + 4]`` (int8 payload + bitcast fp32 scale per block — the wire
    format of kernels/qint8_pack.py).  Scale math is bit-identical to
    comm/quant.py ``quantize_block``; the zero-padded tail of the final
    partial block quantizes to zero.
    """
    lead, n = x.shape[:-1], x.shape[-1]
    nb = -(-n // block)
    xb = x.astype(jnp.float32)
    if nb * block != n:
        xb = jnp.pad(xb, ((0, 0),) * len(lead) + ((0, nb * block - n),))
    xb = xb.reshape(lead + (nb, block))
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    sb = jax.lax.bitcast_convert_type(scale[..., 0], jnp.int8)
    return jnp.concatenate([q, sb], axis=-1)


def qint8_unpack_ref(wire: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`qint8_pack_ref`: ``int8 [..., nb, block + 4]
    -> fp32 [..., n]`` (padding tail sliced off)."""
    lead, (nb, width) = wire.shape[:-2], wire.shape[-2:]
    block = width - _QINT8_SCALE_BYTES
    q = wire[..., :block].astype(jnp.float32)
    scale = jax.lax.bitcast_convert_type(wire[..., block:], jnp.float32)
    return (q * scale[..., None]).reshape(lead + (nb * block,))[..., :n]


def rwkv6_wkv_ref(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                  u: jax.Array, state: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """RWKV-6 WKV recurrence, scanned over time in fp32.

    r/k/v/w: [B, S, H, D]; u: [H, D]; state: [B, H, D, D] (indexed [j, i]).

        y_t[i]  = sum_j r_t[j] * (S[j,i] + u[j] * k_t[j] * v_t[i])
        S'[j,i] = w_t[j] * S[j,i] + k_t[j] * v_t[i]

    Returns (y [B, S, H, D] in r.dtype, final state fp32).
    """
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    uf = u.astype(jnp.float32)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp          # each [B, H, D]
        kv = k_t[..., :, None] * v_t[..., None, :]          # [B,H,D,D]
        y = jnp.einsum("bhj,bhji->bhi", r_t,
                       S + uf[None, :, :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, y

    xs = tuple(x.swapaxes(0, 1) for x in (rf, kf, vf, wf))  # [S,B,H,D]
    final, ys = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return ys.swapaxes(0, 1).astype(r.dtype), final
