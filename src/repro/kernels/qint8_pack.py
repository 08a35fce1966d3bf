"""Fused absmax + quantize + pack for the qint8 codec — one pass over
the bucket, ONE contiguous wire buffer per bucket.

The legacy path (comm/quant.py ``quantize_block``/``dequantize_block``)
is two-pass and two-message: an absmax reduction materializes a
``[rows, nb]`` fp32 scale array, a second pass quantizes, and the int8
payload and the fp32 scales ride the collective as SEPARATE arrays —
doubling the per-bucket message count that latency-dominated tiers pay
for (see ``LevelCost.messages``).

This kernel fuses the scan and packs both into a single int8 buffer:

    wire[..., nb, block + 4]   (leading dims: the learner rows)
      wire[..., :block]  int8 quantized values (one block per row)
      wire[..., block:]  the block's fp32 scale, bitcast to 4 int8 bytes

Quantization math is IDENTICAL to the legacy path — ``scale =
max|x| / 127`` clamped at 1e-12, ``q = clip(round(x / scale), ±127)`` —
and the scale bytes are a bitcast (not a cast), so pack→unpack is
bit-identical to quantize→dequantize; tests assert exact equality
against both the pure-jnp oracle (kernels/ref.py) and the legacy
two-pass functions.

Layout notes: the grid runs over (learner row, tile of ``_TILE`` blocks),
so VMEM holds one ``[_TILE, block]`` slab at a time whatever the bucket
size; the wrapper zero-pads the trailing dim to a whole number of blocks
and the block count to a whole number of tiles (zero padding quantizes
to zero and is sliced off after unpack — the scale of an all-zero block
is the 1e-12 clamp, never a divide-by-zero).  The ``block + 4`` minor
dim is the whole array dim, so it need not be lane-aligned: it is the
wire format.  The TPU compiler does not bitcast between widths, so the
scale is bitcast to int32 (same width) and split into its four
little-endian bytes with shifts — the byte order XLA's
``bitcast_convert_type(f32 -> int8)`` produces, which the oracle uses.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_SCALE_BYTES = 4       # one fp32 scale per block, as 4 int8 bytes
_SCALE_FLOOR = 1e-12   # matches comm/quant.py quantize_block
_TILE = 512            # blocks per grid step (multiple of int8's 32 rows)


def _byte_shifts(rows: int):
    return 8 * jax.lax.broadcasted_iota(jnp.int32, (rows, _SCALE_BYTES), 1)


def _pack_kernel(x_ref, out_ref, *, block: int):
    xb = x_ref[0].astype(jnp.float32)                     # [tile, block]
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, _SCALE_FLOOR)              # [tile, 1]
    q = jnp.clip(jnp.round(xb / scale), -127, 127)
    bits = jax.lax.bitcast_convert_type(scale, jnp.int32)
    b = (bits >> _byte_shifts(xb.shape[0])) & 0xFF        # [tile, 4]
    b = jnp.where(b > 127, b - 256, b)                    # as signed bytes
    out_ref[0, :, :block] = q.astype(jnp.int32).astype(jnp.int8)
    out_ref[0, :, block:] = b.astype(jnp.int8)


def _unpack_kernel(w_ref, out_ref, *, block: int):
    w = w_ref[0].astype(jnp.int32)                        # [tile, block+4]
    q = w[:, :block].astype(jnp.float32)
    b = w[:, block:] & 0xFF                               # [tile, 4]
    bits = jnp.sum(b << _byte_shifts(w.shape[0]), axis=1, keepdims=True)
    scale = jax.lax.bitcast_convert_type(bits, jnp.float32)
    out_ref[0] = q * scale


def _tiling(nb: int):
    """(tile, padded block count): whole tiles of ``_TILE`` blocks, or
    one tile of all blocks rounded up to int8's 32-row tile."""
    if nb >= _TILE:
        return _TILE, -(-nb // _TILE) * _TILE
    t = -(-nb // 32) * 32
    return t, t


def qint8_pack(x: jax.Array, block: int, *,
               interpret: bool = False) -> jax.Array:
    """``[..., n] -> int8 [..., nb, block + 4]`` fused wire buffer
    (``nb = ceil(n / block)``; the final partial block is zero-padded).
    Each index into the leading dims is a learner row of its own."""
    lead, n = x.shape[:-1], x.shape[-1]
    nb = -(-n // block)
    tile, nb_pad = _tiling(nb)
    # Rows are sliced out and blocked one at a time.  Reshaping the whole
    # array instead would merge the learner axes into rows: when the
    # second-minor one is small (the 2 learners of a group) that is a
    # relayout between (2, 128) and (8, 128) tilings, which the TPU
    # compiler emits as code proportional to the row length — minutes
    # of compile for a 4-learner hymba round stacked on one chip.
    xb = jnp.stack([
        jnp.pad(x[i].astype(jnp.float32),
                (0, nb_pad * block - n)).reshape(nb_pad, block)
        for i in np.ndindex(lead)])
    rows = xb.shape[0]
    width = block + _SCALE_BYTES
    wire = pl.pallas_call(
        functools.partial(_pack_kernel, block=block),
        name="qint8_pack",
        grid=(rows, nb_pad // tile),
        in_specs=[pl.BlockSpec((1, tile, block), lambda r, t: (r, t, 0))],
        out_specs=pl.BlockSpec((1, tile, width), lambda r, t: (r, t, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, nb_pad, width), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xb)
    return wire[:, :nb].reshape(lead + (nb, width))


def qint8_unpack(wire: jax.Array, n: int, *,
                 interpret: bool = False) -> jax.Array:
    """``int8 [..., nb, block + 4] -> fp32 [..., n]`` dequantize —
    inverse of :func:`qint8_pack` (padding tail sliced off)."""
    lead, (nb, width) = wire.shape[:-2], wire.shape[-2:]
    rows = math.prod(lead)
    block = width - _SCALE_BYTES
    tile, nb_pad = _tiling(nb)
    wire = jnp.pad(wire.reshape(rows, nb, width),
                   ((0, 0), (0, nb_pad - nb), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, block=block),
        name="qint8_unpack",
        grid=(rows, nb_pad // tile),
        in_specs=[pl.BlockSpec((1, tile, width), lambda r, t: (r, t, 0))],
        out_specs=pl.BlockSpec((1, tile, block), lambda r, t: (r, t, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, nb_pad, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(wire)
    # rows are joined by concatenation, not a reshape (see qint8_pack)
    flat = jnp.concatenate([r.reshape(nb_pad * block)[:n] for r in out])
    return flat.reshape(lead + (n,))
