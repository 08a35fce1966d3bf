"""Attention variants: GQA (full / sliding-window) and MLA (DeepSeek-V2).

Three entry modes per variant:
  * train:   full-sequence causal self-attention (no cache)
  * prefill: same compute as train, but also returns a populated KV cache
  * decode:  one new token against an existing cache

Caches:
  * full cache   — [B, max_len, Hkv, Dh]; slot i valid iff i < pos
  * rolling cache — [B, window, Hkv, Dh]; write at pos % window (sub-quadratic
    memory for long_500k on full-attention archs)
  * MLA cache    — compressed latents [B, T, kv_lora] + shared rope key
                   [B, T, rope_dim]; decode uses the absorbed formulation
                   (q and out projections folded through the latent space) so
                   per-step compute is O(T * kv_lora), never materializing K/V.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.models.common import (Params, apply_rope, dense_init, rmsnorm,
                                 rmsnorm_init)
from repro.telemetry.spans import ATTENTION

NEG_INF = -1.0e30


# ===================================================================== #
# shared masked attention core (XLA path; Pallas path in kernels/)
# ===================================================================== #

def _gqa_scores_attend(q, k, v, mask, scale):
    """q [B,S,Hq,D], k/v [B,T,Hkv,D], mask [B,1,S,T] bool -> [B,S,Hq,D]."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    q = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, :, None], scores, NEG_INF)  # [B,1,1,S,T] bcast
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, hq, d)


def causal_mask(s: int, t: int, window: int = 0, q_offset: int = 0) -> jax.Array:
    """[s, t] bool mask; query i (global pos q_offset+i) sees key j iff
    j <= pos and (window == 0 or pos - j < window)."""
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window:
        m &= (qpos - kpos) < window
    return m


def _pick_q_chunk(t: int) -> int:
    """Bound the per-chunk score tensor to ~4M elements per (b, head)."""
    return max(64, min(1024, (1 << 22) // max(t, 1)))


def _chunked_causal_attend(q, k, v, *, window: int, scale, q_chunk: int):
    """Query-chunked attention (XLA stand-in for the flash kernel): scores
    are materialized only [.., q_chunk, T] at a time via a sequential
    ``lax.map`` over query blocks."""
    b, s, hq, d = q.shape
    t = k.shape[1]
    nc = s // q_chunk
    qs = jnp.moveaxis(q.reshape(b, nc, q_chunk, hq, d), 1, 0)
    idx = jnp.arange(nc)

    @jax.checkpoint
    def one(args):
        qi, i = args
        m = causal_mask(q_chunk, t, window, q_offset=i * q_chunk)
        m = jnp.broadcast_to(m[None, None], (b, 1, q_chunk, t))
        return _gqa_scores_attend(qi, k, v, m, scale)

    out = jax.lax.map(one, (qs, idx))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hq, d)


class AttentionCall(NamedTuple):
    """One full-sequence attention call, recorded as it is traced (a
    scanned layer stack is traced once for all its layers)."""
    path: str            # "fused" (kernels/flash_attention.py) or "xla"
    why: str             # on "xla": the condition that kept it there
    key_tiles: int       # fused: tiles computed, per sequence and kv head
    all_tiles: int       # fused: tiles of the whole s x s grid


class DispatchRecord:
    """The attention calls traced while it is active (``with``): each
    call's path, and on the fused path its share of key tiles."""

    def __init__(self):
        self.calls: List[AttentionCall] = []

    def __enter__(self) -> "DispatchRecord":
        _RECORDS.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _RECORDS.remove(self)

    def describe(self) -> str:
        """One line: the traced calls on the fused path and their share
        of key tiles, and why the others stayed on XLA."""
        fused = [c for c in self.calls if c.path == "fused"]
        line = (f"attention: fused in {len(fused)} of {len(self.calls)} "
                f"traced calls")
        if fused:
            done = sum(c.key_tiles for c in fused)
            every = sum(c.all_tiles for c in fused)
            line += (f", {done} of {every} key tiles "
                     f"({100 * done / every:.1f}%)")
        whys = sorted({c.why for c in self.calls if c.path == "xla"})
        return line + (f"; XLA: {', '.join(whys)}" if whys else "")


_RECORDS: List[DispatchRecord] = []


def _record(*call) -> None:
    for rec in _RECORDS:
        rec.calls.append(AttentionCall(*call))


def _xla_reason(q, k, *, causal, window, q_offset, extra_mask,
                impl) -> str:
    """Why a call cannot take the fused kernel; "" when it can."""
    s, t, d = q.shape[1], k.shape[1], q.shape[-1]
    if not impl.startswith("pallas"):
        return "not a TPU" if impl == "auto" else f"impl={impl}"
    if not causal:
        return "not causal"
    if extra_mask is not None:
        return "extra mask"
    if not (isinstance(q_offset, int) and q_offset == 0):
        return "query offset"
    if s != t:
        return "queries != keys"
    if d not in fa.HEAD_DIMS:
        return f"head_dim {d}"
    if s % fa.BLOCKS[-1]:
        return f"sequence {s} does not tile"
    g = q.shape[2] // k.shape[2]
    if not fa.blocks(s, window, g, d):
        return f"VMEM: {g} query heads a kv head"
    if jax.sharding.get_abstract_mesh().axis_names:
        return "under a mesh"          # a Mosaic kernel cannot be partitioned
    return ""


def full_attention(q, k, v, *, causal: bool = True, window: int = 0,
                   q_offset: int = 0, extra_mask: Optional[jax.Array] = None,
                   scale: Optional[float] = None, impl: str = "auto"):
    """Attention of q [B,S,Hq,D] over k/v [B,T,Hkv,D].

    ``impl``: "auto" takes the fused block-sparse kernel
    (kernels/flash_attention.py, forward and backward) on a TPU and XLA
    elsewhere; "pallas"/"pallas_interpret" ask for the kernel (compiled /
    interpreted), "xla" for the XLA code.  The kernel serves causal
    (optionally windowed) self-attention over the whole sequence at a
    static zero offset, where the sequence tiles, the head dim is one it
    takes and a block fits VMEM, outside a device mesh; every other call
    runs the XLA code.
    Each call's path is recorded as it is traced (:class:`DispatchRecord`).
    """
    if impl == "auto" and jax.default_backend() == "tpu":
        impl = "pallas"
    why = _xla_reason(q, k, causal=causal, window=window, q_offset=q_offset,
                      extra_mask=extra_mask, impl=impl)
    if not why:
        _record("fused", "", *fa.key_tiles(q.shape[1], window,
                                           q.shape[2] // k.shape[2],
                                           q.shape[-1]))
        return fa.flash_attention(q, k, v, window=window, scale=scale,
                                  interpret=(impl == "pallas_interpret"))
    _record("xla", why, 0, 0)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    static_zero_offset = isinstance(q_offset, int) and q_offset == 0
    b, s, _, _ = q.shape
    t = k.shape[1]
    q_chunk = _pick_q_chunk(t)
    if (causal and extra_mask is None and static_zero_offset
            and s >= 2 * q_chunk and s % q_chunk == 0):
        return _chunked_causal_attend(q, k, v, window=window, scale=scale,
                                      q_chunk=q_chunk)
    if causal:
        m = causal_mask(s, t, window, q_offset)[None, None]
        m = jnp.broadcast_to(m, (b, 1, s, t))
    else:
        m = jnp.ones((b, 1, s, t), bool)
    if extra_mask is not None:
        m = m & extra_mask
    return _gqa_scores_attend(q, k, v, m, scale)


# ===================================================================== #
# GQA
# ===================================================================== #

def gqa_init(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }


def _project_qkv(p: Params, x, n_heads, n_kv_heads, head_dim):
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    k = (x @ p["wk"]).reshape(b, s, n_kv_heads, head_dim)
    v = (x @ p["wv"]).reshape(b, s, n_kv_heads, head_dim)
    return q, k, v


@jax.named_scope(ATTENTION)
def gqa_attention(p: Params, x, cos, sin, *, n_heads: int, n_kv_heads: int,
                  head_dim: int, causal: bool = True, window: int = 0,
                  impl: str = "auto") -> jax.Array:
    """Train/prefill full-sequence path. cos/sin [B,S,head_dim//2]."""
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    if cos is not None:
        q = apply_rope(q, cos[:, :, None], sin[:, :, None])
        k = apply_rope(k, cos[:, :, None], sin[:, :, None])
    out = full_attention(q, k, v, causal=causal, window=window, impl=impl)
    return out.reshape(x.shape[0], x.shape[1], n_heads * head_dim) @ p["wo"]


def cross_attention(p: Params, x, enc_k, enc_v, enc_mask, *, n_heads: int,
                    n_kv_heads: int, head_dim: int) -> jax.Array:
    """Decoder cross-attn; enc_k/enc_v [B,Te,Hkv,D] precomputed."""
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    m = None
    if enc_mask is not None:
        m = enc_mask[:, None, None, :]  # [B,1,1,Te]
        m = jnp.broadcast_to(m, (b, 1, s, enc_k.shape[1]))
    out = full_attention(q, enc_k, enc_v, causal=False, extra_mask=m)
    return out.reshape(b, s, n_heads * head_dim) @ p["wo"]


# --------------------------- caches ---------------------------------- #

def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, rolling: bool = False,
                  window: int = 0) -> Dict[str, Any]:
    length = window if rolling else max_len
    return {
        "k": jnp.zeros((batch, length, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, length, n_kv_heads, head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),       # tokens written so far
    }


def gqa_decode(p: Params, x, cache: Dict[str, Any], cos, sin, *,
               n_heads: int, n_kv_heads: int, head_dim: int,
               rolling: bool = False
               ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One-token decode. x [B,1,d]; cos/sin [B,1,head_dim//2] at current pos.

    ``rolling`` is static: True means the cache is a circular window buffer.
    """
    b = x.shape[0]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    if cos is not None:
        q = apply_rope(q, cos[:, :, None], sin[:, :, None])
        k = apply_rope(k, cos[:, :, None], sin[:, :, None])
    pos = cache["pos"]
    length = cache["k"].shape[1]
    slot = pos % length if rolling else pos
    new_k = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    # validity: slot i holds a real token iff i <= pos (non-rolling) or
    # i < min(pos+1, length) once the rolling buffer may have wrapped
    idx = jnp.arange(length)
    if rolling:
        valid = idx < jnp.minimum(pos + 1, length)
    else:
        valid = idx <= pos
    mask = jnp.broadcast_to(valid[None, None, None, :], (b, 1, 1, length))
    out = full_attention(q, new_k.astype(q.dtype), new_v.astype(q.dtype),
                         causal=False, extra_mask=mask)
    out = out.reshape(b, 1, n_heads * head_dim) @ p["wo"]
    new_cache = dict(cache, k=new_k, v=new_v, pos=pos + 1)
    return out, new_cache


def prefill_kv_cache(p: Params, x, cos, sin, *, n_heads, n_kv_heads, head_dim,
                     max_len: int, dtype=jnp.bfloat16, rolling: bool = False,
                     window: int = 0):
    """Compute roped K/V for the prompt and lay them into a fresh cache."""
    b, s, _ = x.shape
    _, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    if cos is not None:
        k = apply_rope(k, cos[:, :, None], sin[:, :, None])
    cache = init_kv_cache(b, max_len, n_kv_heads, head_dim, dtype,
                          rolling=rolling, window=window)
    if rolling:
        keep = min(s, window)
        k, v = k[:, -keep:], v[:, -keep:]
        s_eff = keep
    else:
        s_eff = s
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(dtype), (0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(dtype), (0, 0, 0, 0))
    cache["pos"] = jnp.asarray(s_eff if rolling else s, jnp.int32)
    return cache


# --------------------------- paged cache ------------------------------ #
#
# The serving engine's paged layout (serve/kvcache.py): K/V live in a
# global pool of fixed-size pages, [Hkv, P, page, D] per layer (head-major
# so the flash-decode kernel streams one (page, D) tile per grid step);
# each sequence owns an ordered block table of page ids.  Page 0 is the
# null page — unallocated table entries point at it and inactive slots'
# writes are directed there.

def init_paged_kv(n_pages: int, page_size: int, n_kv_heads: int,
                  head_dim: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    return {
        "k": jnp.zeros((n_kv_heads, n_pages, page_size, head_dim), dtype),
        "v": jnp.zeros((n_kv_heads, n_pages, page_size, head_dim), dtype),
    }


def paged_slot_coords(block_tables, lengths, active, page_size: int):
    """(page_ids [B], offsets [B]) where each slot's NEXT token is written;
    inactive slots are redirected to the null page 0."""
    idx = lengths // page_size
    page_ids = jnp.take_along_axis(block_tables, idx[:, None], axis=1)[:, 0]
    page_ids = jnp.where(active, page_ids, 0)
    return page_ids, lengths % page_size


def gqa_decode_paged(p: Params, x, pages: Dict[str, Any], block_tables,
                     lengths, active, cos, sin, *, n_heads: int,
                     n_kv_heads: int, head_dim: int, window: int = 0,
                     impl: str = "auto"
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One-token decode against a paged pool (per-slot positions).

    x [B,1,d]; block_tables [B, max_pages] int32; lengths [B] int32 —
    tokens cached so far per slot (the new token is written at position
    ``lengths`` and the attend covers ``lengths + active`` tokens);
    active [B] bool masks serving slots that are mid-sequence.  Unlike
    the dense ``gqa_decode`` (one shared scalar ``pos``), every slot
    advances independently — the property continuous batching needs.
    ``impl`` routes the attend through kernels/ops.py::flash_decode.
    """
    b = x.shape[0]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    if cos is not None:
        q = apply_rope(q, cos[:, :, None], sin[:, :, None])
        k = apply_rope(k, cos[:, :, None], sin[:, :, None])
    page = pages["k"].shape[2]
    page_ids, offs = paged_slot_coords(block_tables, lengths, active, page)
    # [B,1,Hkv,D] -> [Hkv,B,D] scatter rows into (page_id, offset) slots
    new_k = pages["k"].at[:, page_ids, offs].set(
        k[:, 0].transpose(1, 0, 2).astype(pages["k"].dtype))
    new_v = pages["v"].at[:, page_ids, offs].set(
        v[:, 0].transpose(1, 0, 2).astype(pages["v"].dtype))
    from repro.kernels import ops as kops
    att_len = lengths + active.astype(lengths.dtype)
    out = kops.flash_decode(q[:, 0], new_k, new_v, block_tables, att_len,
                            window=window, impl=impl)
    out = out.reshape(b, 1, n_heads * head_dim).astype(x.dtype) @ p["wo"]
    return out, {"k": new_k, "v": new_v}


def gqa_prefill_paged_chunk(p: Params, x, pages: Dict[str, Any],
                            block_tables, base, cos, sin, *, n_heads: int,
                            n_kv_heads: int, head_dim: int, window: int = 0
                            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One prompt chunk of a paged prefill.

    x [B,C,d] — chunk tokens at global positions base..base+C-1 (``base``
    may be traced, so any chunk count compiles once); K/V are written
    into the chunk's pages, then the chunk queries attend every cached
    position (earlier chunks + causal within this one) through the
    gathered pool.  The padded tail of the final chunk writes garbage
    past the true length — masked out of every later attend and
    overwritten by decode, exactly like unreached dense-cache slots.
    """
    b, c, _ = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    if cos is not None:
        q = apply_rope(q, cos[:, :, None], sin[:, :, None])
        k = apply_rope(k, cos[:, :, None], sin[:, :, None])
    page = pages["k"].shape[2]
    pos = base + jnp.arange(c)                        # [C]
    tbl = jnp.broadcast_to(block_tables, (b, block_tables.shape[1]))
    page_ids = jnp.take_along_axis(tbl, pos[None] // page, axis=1)  # [B,C]
    offs = pos % page
    # [B,C,Hkv,D] -> per batch row scatter [Hkv, B, C, D]
    new_k = pages["k"].at[:, page_ids, offs[None]].set(
        k.transpose(2, 0, 1, 3).astype(pages["k"].dtype))
    new_v = pages["v"].at[:, page_ids, offs[None]].set(
        v.transpose(2, 0, 1, 3).astype(pages["v"].dtype))
    from repro.kernels import ref as kref
    kd = kref.gather_pages(new_k, tbl).astype(q.dtype)   # [B,T,Hkv,D]
    vd = kref.gather_pages(new_v, tbl).astype(q.dtype)
    out = full_attention(q, kd, vd, causal=True, window=window,
                         q_offset=base)
    out = out.reshape(b, c, n_heads * head_dim) @ p["wo"]
    return out, {"k": new_k, "v": new_v}


# ===================================================================== #
# MLA (Multi-head Latent Attention, DeepSeek-V2)
# ===================================================================== #

def mla_init(key, d_model: int, n_heads: int, kv_lora: int, qk_nope: int,
             qk_rope: int, v_dim: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * (qk_nope + qk_rope), dtype),
        "w_dkv": dense_init(ks[1], d_model, kv_lora, dtype),
        "w_kr": dense_init(ks[2], d_model, qk_rope, dtype),
        "kv_norm": rmsnorm_init(kv_lora, dtype),
        "w_uk": dense_init(ks[3], kv_lora, n_heads * qk_nope, dtype),
        "w_uv": dense_init(ks[4], kv_lora, n_heads * v_dim, dtype),
        "wo": dense_init(ks[5], n_heads * v_dim, d_model, dtype),
    }


def _mla_q(p, x, n_heads, qk_nope, qk_rope, cos, sin):
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope, cos[:, :, None], sin[:, :, None])
    return q_nope, q_rope


def _mla_latents(p, x, cos, sin, eps):
    ckv = rmsnorm({"scale": p["kv_norm"]["scale"]}, x @ p["w_dkv"], eps)
    kr = apply_rope((x @ p["w_kr"])[:, :, None, :], cos[:, :, None],
                    sin[:, :, None])[:, :, 0]
    return ckv, kr


def mla_attention(p: Params, x, cos, sin, *, n_heads: int, kv_lora: int,
                  qk_nope: int, qk_rope: int, v_dim: int,
                  eps: float = 1e-5) -> jax.Array:
    """Train/prefill: decompress latents into per-head K/V (standard path)."""
    b, s, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, n_heads, qk_nope, qk_rope, cos, sin)
    ckv, kr = _mla_latents(p, x, cos, sin, eps)
    k_nope = (ckv @ p["w_uk"]).reshape(b, s, n_heads, qk_nope)
    v = (ckv @ p["w_uv"]).reshape(b, s, n_heads, v_dim)
    scale = 1.0 / jnp.sqrt(float(qk_nope + qk_rope))

    def attend_block(qn, qr, offset):
        """qn [b, qc, H, nope]; offset: first query position."""
        qc = qn.shape[1]
        mask = causal_mask(qc, s, 0, q_offset=offset)[None, None]
        scores = (jnp.einsum("bshd,bthd->bhst", qn, k_nope)
                  + jnp.einsum("bshd,btd->bhst", qr, kr)
                  ).astype(jnp.float32)
        scores = jnp.where(mask, scores * scale, NEG_INF)
        probs = jax.nn.softmax(scores, -1).astype(v.dtype)
        return jnp.einsum("bhst,bthd->bshd", probs, v)

    q_chunk = _pick_q_chunk(s)
    if s >= 2 * q_chunk and s % q_chunk == 0:
        nc = s // q_chunk
        qns = jnp.moveaxis(q_nope.reshape(b, nc, q_chunk, n_heads, qk_nope),
                           1, 0)
        qrs = jnp.moveaxis(q_rope.reshape(b, nc, q_chunk, n_heads, qk_rope),
                           1, 0)
        out = jax.lax.map(
            jax.checkpoint(
                lambda a: attend_block(a[0], a[1], a[2] * q_chunk)),
            (qns, qrs, jnp.arange(nc)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, n_heads * v_dim)
    else:
        out = attend_block(q_nope, q_rope, 0).reshape(b, s, n_heads * v_dim)
    return out @ p["wo"]


def init_mla_cache(batch: int, max_len: int, kv_lora: int, qk_rope: int,
                   dtype=jnp.bfloat16) -> Dict[str, Any]:
    return {
        "ckv": jnp.zeros((batch, max_len, kv_lora), dtype),
        "k_rope": jnp.zeros((batch, max_len, qk_rope), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def mla_prefill_cache(p: Params, x, cos, sin, *, max_len: int, eps: float,
                      dtype=jnp.bfloat16) -> Dict[str, Any]:
    b, s, _ = x.shape
    ckv, kr = _mla_latents(p, x, cos, sin, eps)
    cache = init_mla_cache(b, max_len, ckv.shape[-1], kr.shape[-1], dtype)
    cache["ckv"] = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv.astype(dtype), (0, 0, 0))
    cache["k_rope"] = jax.lax.dynamic_update_slice(
        cache["k_rope"], kr.astype(dtype), (0, 0, 0))
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return cache


def mla_decode(p: Params, x, cache, cos, sin, *, n_heads: int, kv_lora: int,
               qk_nope: int, qk_rope: int, v_dim: int, eps: float = 1e-5
               ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Absorbed decode: score/value computed in latent space.

    per-step FLOPs ~ O(T * kv_lora * H) with NO K/V materialization — this is
    the production MLA decode and the reason long_500k is feasible with a
    full (non-windowed) cache for deepseek-v2-lite.
    """
    b = x.shape[0]
    q_nope, q_rope = _mla_q(p, x, n_heads, qk_nope, qk_rope, cos, sin)  # [B,1,H,*]
    ckv_new, kr_new = _mla_latents(p, x, cos, sin, eps)                  # [B,1,*]
    pos = cache["pos"]
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), (0, pos, 0))
    krc = jax.lax.dynamic_update_slice(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), (0, pos, 0))
    t = ckv.shape[1]
    # absorb w_uk into q:  q_lat [B,H,lora]
    w_uk = p["w_uk"].reshape(kv_lora, n_heads, qk_nope)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], w_uk)
    scale = 1.0 / jnp.sqrt(float(qk_nope + qk_rope))
    scores = (jnp.einsum("bhl,btl->bht", q_lat, ckv.astype(q_lat.dtype))
              + jnp.einsum("bhd,btd->bht", q_rope[:, 0],
                           krc.astype(q_rope.dtype))).astype(jnp.float32)
    valid = (jnp.arange(t) <= pos)[None, None, :]
    scores = jnp.where(valid, scores * scale, NEG_INF)
    probs = jax.nn.softmax(scores, -1).astype(ckv.dtype)
    ctx_lat = jnp.einsum("bht,btl->bhl", probs, ckv)                # [B,H,lora]
    w_uv = p["w_uv"].reshape(kv_lora, n_heads, v_dim)
    out = jnp.einsum("bhl,lhv->bhv", ctx_lat.astype(x.dtype), w_uv)
    out = out.reshape(b, 1, n_heads * v_dim) @ p["wo"]
    new_cache = dict(cache, ckv=ckv, k_rope=krc, pos=pos + 1)
    return out, new_cache


# --------------------------- paged MLA -------------------------------- #
#
# Latent pages have no head axis — the pool is [P, page, kv_lora] (+ the
# shared rope key [P, page, qk_rope]), so paging the MLA cache is the same
# block-table indirection at ~1/8 the bytes of a GQA pool.  Both the
# decode step and the chunk prefill use the absorbed formulation (scores
# and context in latent space, K/V never materialized).

def init_paged_mla(n_pages: int, page_size: int, kv_lora: int,
                   qk_rope: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    return {
        "ckv": jnp.zeros((n_pages, page_size, kv_lora), dtype),
        "kr": jnp.zeros((n_pages, page_size, qk_rope), dtype),
    }


def _gather_latent(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """pages [P, page, R], tables [B, maxp] -> dense [B, maxp*page, R]."""
    b, maxp = block_tables.shape
    page, r = pages.shape[1], pages.shape[2]
    return pages[block_tables].reshape(b, maxp * page, r)


def _mla_absorbed_attend(p, q_nope, q_rope, ckv_d, kr_d, mask, *,
                         n_heads, kv_lora, qk_nope, qk_rope, v_dim):
    """Absorbed-latent attention for S queries.

    q_nope [B,S,H,nope], q_rope [B,S,H,rope]; ckv_d [B,T,lora],
    kr_d [B,T,rope]; mask [B,S,T] bool.  Rows with no valid key (inactive
    serving slots) output zeros.  Returns [B, S, H*v_dim].
    """
    b, s = q_nope.shape[:2]
    w_uk = p["w_uk"].reshape(kv_lora, n_heads, qk_nope)
    q_lat = jnp.einsum("bshd,lhd->bshl", q_nope, w_uk)
    scale = 1.0 / jnp.sqrt(float(qk_nope + qk_rope))
    scores = (jnp.einsum("bshl,btl->bhst", q_lat,
                         ckv_d.astype(q_lat.dtype))
              + jnp.einsum("bshd,btd->bhst", q_rope,
                           kr_d.astype(q_rope.dtype))).astype(jnp.float32)
    scores = jnp.where(mask[:, None], scores * scale, NEG_INF)
    probs = jax.nn.softmax(scores, -1).astype(ckv_d.dtype)
    ctx = jnp.einsum("bhst,btl->bshl", probs, ckv_d)
    ctx = jnp.where(mask.any(-1)[:, :, None, None], ctx, 0.0)
    w_uv = p["w_uv"].reshape(kv_lora, n_heads, v_dim)
    out = jnp.einsum("bshl,lhv->bshv", ctx.astype(q_nope.dtype), w_uv)
    return out.reshape(b, s, n_heads * v_dim)


def mla_decode_paged(p: Params, x, pages: Dict[str, Any], block_tables,
                     lengths, active, cos, sin, *, n_heads: int,
                     kv_lora: int, qk_nope: int, qk_rope: int, v_dim: int,
                     eps: float = 1e-5
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Absorbed one-token decode against latent pages (per-slot lengths)."""
    b = x.shape[0]
    q_nope, q_rope = _mla_q(p, x, n_heads, qk_nope, qk_rope, cos, sin)
    ckv_new, kr_new = _mla_latents(p, x, cos, sin, eps)        # [B,1,*]
    page = pages["ckv"].shape[1]
    page_ids, offs = paged_slot_coords(block_tables, lengths, active, page)
    ckv = pages["ckv"].at[page_ids, offs].set(
        ckv_new[:, 0].astype(pages["ckv"].dtype))
    kr = pages["kr"].at[page_ids, offs].set(
        kr_new[:, 0].astype(pages["kr"].dtype))
    ckv_d = _gather_latent(ckv, block_tables)
    kr_d = _gather_latent(kr, block_tables)
    att_len = lengths + active.astype(lengths.dtype)
    mask = (jnp.arange(ckv_d.shape[1])[None] < att_len[:, None])[:, None]
    out = _mla_absorbed_attend(p, q_nope, q_rope, ckv_d, kr_d, mask,
                               n_heads=n_heads, kv_lora=kv_lora,
                               qk_nope=qk_nope, qk_rope=qk_rope,
                               v_dim=v_dim)
    return out.astype(x.dtype) @ p["wo"], {"ckv": ckv, "kr": kr}


def mla_prefill_paged_chunk(p: Params, x, pages: Dict[str, Any],
                            block_tables, base, cos, sin, *, n_heads: int,
                            kv_lora: int, qk_nope: int, qk_rope: int,
                            v_dim: int, eps: float = 1e-5
                            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One prompt chunk of a paged MLA prefill (see gqa_prefill_paged_chunk)."""
    b, c, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, n_heads, qk_nope, qk_rope, cos, sin)
    ckv_new, kr_new = _mla_latents(p, x, cos, sin, eps)        # [B,C,*]
    page = pages["ckv"].shape[1]
    pos = base + jnp.arange(c)
    tbl = jnp.broadcast_to(block_tables, (b, block_tables.shape[1]))
    page_ids = jnp.take_along_axis(tbl, pos[None] // page, axis=1)  # [B,C]
    offs = jnp.broadcast_to(pos % page, (b, c))
    ckv = pages["ckv"].at[page_ids, offs].set(
        ckv_new.astype(pages["ckv"].dtype))
    kr = pages["kr"].at[page_ids, offs].set(
        kr_new.astype(pages["kr"].dtype))
    ckv_d = _gather_latent(ckv, tbl)
    kr_d = _gather_latent(kr, tbl)
    kpos = jnp.arange(ckv_d.shape[1])[None, None]              # [1,1,T]
    mask = jnp.broadcast_to(kpos <= pos[None, :, None],
                            (b, c, ckv_d.shape[1]))
    out = _mla_absorbed_attend(p, q_nope, q_rope, ckv_d, kr_d, mask,
                               n_heads=n_heads, kv_lora=kv_lora,
                               qk_nope=qk_nope, qk_rope=qk_rope,
                               v_dim=v_dim)
    return out.astype(x.dtype) @ p["wo"], {"ckv": ckv, "kr": kr}
