"""Public jit'd wrappers for the Pallas kernels with impl dispatch.

``impl``:
  * "xla"              — pure-jnp oracle (kernels/ref.py); default on CPU
  * "pallas"           — compiled Pallas kernel (TPU target)
  * "pallas_interpret" — Pallas kernel body interpreted in Python on CPU
                         (correctness validation without hardware)

Training attention's fused kernel (kernels/flash_attention.py) is chosen
where it is called, in ``models/attention.full_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as kref

# mesh axes a learner row is split over, major to minor (launch/train.py's
# learner mesh); "fsdp" joins when a codec view merges the shards into the
# rows (comm/bucket.py)
_LEARNER_AXES = ("pod", "group", "local")


def _per_row(fn, x, n_trailing: int):
    """``fn(x)`` for a compiled kernel that maps each learner row of
    ``x`` — one index into its leading ``x.ndim - n_trailing`` dims — on
    its own.  The TPU compiler cannot partition a Mosaic kernel, so under
    a learner mesh (``jax.set_mesh``) the kernel runs in a ``shard_map``
    over the rows each device holds; without one it is called as is."""
    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(a for a in _LEARNER_AXES if a in mesh.axis_names)
    if not axes:
        return fn(x)
    lead = x.shape[:x.ndim - n_trailing]
    rows = math.prod(lead)
    learners = math.prod(mesh.shape[a] for a in axes)
    if "fsdp" in mesh.axis_names and rows == learners * mesh.shape["fsdp"]:
        axes += ("fsdp",)
    elif rows != learners:
        raise ValueError(
            f"kernel operand {tuple(x.shape)} has {rows} rows; the mesh "
            f"{dict(mesh.shape)} holds {learners} learners")
    flat = x.reshape((rows,) + x.shape[len(lead):])
    specs = jax.tree.map(lambda _: P(axes), jax.eval_shape(fn, flat))
    out = jax.shard_map(fn, mesh=mesh, in_specs=P(axes), out_specs=specs,
                        check_vma=False)(flat)
    return jax.tree.map(lambda o: o.reshape(lead + o.shape[1:]), out)


def flash_decode(q, k_pages, v_pages, block_tables, lengths, *,
                 window: int = 0, scale: Optional[float] = None,
                 impl: str = "auto") -> jax.Array:
    """Dispatchable paged decode attention (the serving hot path).

    q [B, Hq, D] — one query token per sequence; k_pages/v_pages
    [Hkv, P, page, D] — the paged pool; block_tables [B, max_pages]
    int32; lengths [B] int32 (valid tokens per sequence incl. the query).

    ``impl="auto"`` picks the compiled Pallas kernel
    (kernels/flash_decode.py) on a TPU backend and the XLA gather oracle
    (kernels/ref.py::flash_decode_ref) everywhere else — same fallback
    contract as ``topk_compress``'s ``compaction="auto"``:
    ``"pallas_interpret"`` runs the kernel body in Python on CPU for
    correctness validation without hardware.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return kref.flash_decode_ref(q, k_pages, v_pages, block_tables,
                                     lengths, window=window, scale=scale)
    from repro.kernels.flash_decode import flash_decode as fd
    return fd(q, k_pages, v_pages, block_tables, lengths, window=window,
              scale=scale, interpret=(impl == "pallas_interpret"))


def topk_compress(x, k: int, *, impl: str = "xla", block_n: int = 1024,
                  compaction: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Dispatchable magnitude top-k selection: x [rows, n] ->
    (values [rows, k], indices [rows, k] int32, ascending per row).

    With bucketed reductions (comm/bucket.py) a row is one whole flat
    bucket per learner — one tiled kernel pass instead of a ragged launch
    per leaf.  ``compaction`` picks the Pallas compaction engine
    (kernels/topk_compress.py): ``"scan"`` does O(n * block_n) work per
    row — independent of k — via per-chunk cumsum + carried-offset
    stores, and keeps indices in int32 so rows of any length are exact;
    the legacy ``"onehot"`` engine does O(n * k) matmul scatters and
    round-trips indices through fp32, capping rows at 2**24 elements —
    that cap is enforced here, on the legacy path only.  The default
    ``"auto"`` picks whichever tiles cheaper: ``"onehot"`` while
    ``k < block_n`` and the row is under the legacy cap (its [block_n, k]
    tile beats scan's fixed [block_n, block_n]), ``"scan"`` for large k
    or long rows.
    """
    if impl == "xla":
        return kref.topk_compress_ref(x, k)
    n = x.shape[-1]
    if compaction == "auto":
        compaction = "onehot" if (k < block_n and n < 2 ** 24) else "scan"
    elif compaction == "onehot" and n >= 2 ** 24:
        raise ValueError(
            f"pallas topk_compress compaction='onehot' caps rows at 2**24 "
            f"elements (indices accumulate in fp32), got x shape "
            f"{tuple(x.shape)} (n={n}); use compaction='scan', lower "
            f"HierAvgParams.bucket_bytes, or fall back to impl='xla'")
    from repro.kernels.topk_compress import topk_compress as tk
    fn = functools.partial(tk, k=k, block_n=block_n, compaction=compaction,
                           interpret=(impl == "pallas_interpret"))
    return fn(x) if impl == "pallas_interpret" else _per_row(fn, x, 1)


def batched_qr(p, *, impl: str = "auto") -> jax.Array:
    """Dispatchable batched thin-QR Q factor: ``[..., a, r] -> Q``.

    PowerSGD's orthonormalization hot path (comm/lowrank.py): one CGS2
    program per flattened ``[pods, G, S]`` learner row on TPU
    (kernels/batched_qr.py), the LAPACK/Householder ``jnp.linalg.qr``
    oracle elsewhere.  ``impl="auto"`` follows the ``flash_decode``
    convention: compiled Pallas on a TPU backend, XLA oracle everywhere
    else; ``"pallas_interpret"`` runs the kernel body in Python on CPU.
    Note the CGS2 kernel and the oracle agree on the projector
    ``Q Q^T``, not on per-column signs.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return kref.batched_qr_ref(p)
    from repro.kernels.batched_qr import batched_qr as bqr
    if impl == "pallas_interpret":
        return bqr(p, interpret=True)
    return _per_row(bqr, p, 2)


def qint8_pack(x, block: int, *, impl: str = "auto") -> jax.Array:
    """Dispatchable fused quantize+pack: ``[..., n] -> int8 [..., nb,
    block + 4]`` (leading dims: learner rows) — one contiguous wire
    buffer (payload + bitcast scales) so a qint8 bucket rides the
    collective as ONE message instead of two.  Bit-identical across impls (the scale bytes are a bitcast);
    ``impl="auto"`` = Pallas on TPU, oracle elsewhere.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return kref.qint8_pack_ref(x, block)
    from repro.kernels.qint8_pack import qint8_pack as qp
    if impl == "pallas_interpret":
        return qp(x, block, interpret=True)
    return _per_row(functools.partial(qp, block=block), x, 1)


def qint8_unpack(wire, n: int, *, impl: str = "auto") -> jax.Array:
    """Inverse of :func:`qint8_pack`: ``int8 [..., nb, block + 4] ->
    fp32 [..., n]``."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return kref.qint8_unpack_ref(wire, n)
    from repro.kernels.qint8_pack import qint8_unpack as qu
    if impl == "pallas_interpret":
        return qu(wire, n, interpret=True)
    return _per_row(functools.partial(qu, n=n), wire, 2)


def rwkv6_wkv(r, k, v, w, u, state, *, impl: str = "xla",
              block_t: int = 64) -> Tuple[jax.Array, jax.Array]:
    """Dispatchable WKV6: r/k/v/w [B,S,H,D], u [H,D], state [B,H,D,D].
    ``"auto"`` is the XLA scan: the kernel has no backward."""
    if impl in ("xla", "auto"):
        return kref.rwkv6_wkv_ref(r, k, v, w, u, state)
    from repro.kernels.rwkv6_wkv import rwkv6_wkv as wkv
    return wkv(r, k, v, w, u, state, block_t=block_t,
               interpret=(impl == "pallas_interpret"))
