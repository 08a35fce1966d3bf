"""Batched thin-QR as a Pallas TPU kernel — PowerSGD's orthonormalization
hot path (comm/lowrank.py ``_orthonormalize``).

The low-rank reducer needs the Q factor of a *tall-skinny* panel
``P = M Q_prev`` per learner: shape ``[rows, a, r]`` with ``rows`` the
flattened ``[pods, G, S]`` learner batch, ``a`` up to a bucket side
(hundreds..thousands) and ``r`` the PowerSGD rank (2..8).  XLA lowers
``jnp.linalg.qr`` to a per-matrix LAPACK/Householder custom call that
neither batches over learners nor fuses with the surrounding einsums —
on the per-leaf path it is the straggler that cannot bucket or pipeline.

TPU-native design: classical Gram-Schmidt with reorthogonalization
(CGS2), one program per batch row, the whole panel held in VMEM
*transposed* — ``[r, a]``, the panel's columns as sublane rows and its
long side on the 128 lanes — so a panel of ``a`` rows costs ``8 * a``
words of VMEM instead of ``a * 128`` (a 32k-row embedding panel fits):

  * the q accumulator starts at zero and lives in registers, so
    projecting against the whole q tile subtracts only the filled rows
    ``< j`` — no masking — and each column is written by a select on the
    row index, never a single-row store;
  * each column does two projection passes (CGS2: a second pass restores
    orthogonality to fp32 working precision, where plain CGS loses it
    for ill-conditioned panels) — all VPU reductions, no MXU;
  * a rank-deficient column (zero norm after projection) emits a ZERO
    column instead of dividing by ~0: for PowerSGD that contributes
    nothing to the approximation and the error-feedback residual
    re-accumulates the mass, whereas LAPACK would emit an arbitrary
    orthonormal completion direction.

Sign/convention caveat: CGS fixes each column's sign by the input
panel's, LAPACK by R's positive diagonal, so Q may differ from
``jnp.linalg.qr`` by per-column signs.  The *projector* ``Q Q^T`` — the
only thing PowerSGD's ``P^ Q'^T`` reconstruction consumes — is
convention-free; kernel tests compare projectors and orthonormality,
not raw factors (kernels/ref.py ``batched_qr_ref`` is the oracle).

Grid = (batch,): transposed panels are padded to the fp32 sublane
multiple (8) in ``r`` and to the lane multiple (128) in ``a``;
zero-padding is exact (zero entries contribute nothing to inner
products, zero rows stay zero) and is sliced off by the wrapper.

Validated against ``jnp.linalg.qr`` with interpret=True on CPU
(tests/test_kernels.py), including non-pow2 rows, tall/near-square
panels and GQA-style odd dims.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_SUBLANE = 8      # fp32 second-minor tile multiple
_LANE = 128       # minor (lane) tile multiple
_EPS = 1e-30      # rank-deficiency floor on the squared column norm


def _qr_kernel(x_ref, q_ref, *, r: int):
    """One batch row: CGS2 over the ``r`` live rows of the transposed
    panel ``[r_pad, a_pad]``."""
    x = x_ref[0].astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    q = jnp.zeros_like(x)
    for j in range(r):                                  # r is small: 2..8
        v = jnp.sum(jnp.where(row == j, x, 0.0), axis=0, keepdims=True)
        for _ in range(2):                              # CGS2 passes
            # coefficients against every filled row (rows >= j are
            # still zero, so they subtract nothing)
            c = jnp.sum(q * v, axis=1, keepdims=True)   # [r_pad, 1]
            v = v - jnp.sum(q * c, axis=0, keepdims=True)
        nrm2 = jnp.sum(v * v)
        inv = jnp.where(nrm2 > _EPS, jax.lax.rsqrt(nrm2), 0.0)
        q = jnp.where(row == j, v * inv, q)
    q_ref[0] = q


def batched_qr(p: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Thin-QR Q factor over arbitrary leading batch dims:
    ``[..., a, r] -> Q [..., a, r]`` with ``a >= r`` (columns of a
    rank-deficient panel come back zero — see module docstring)."""
    *lead, a, r = p.shape
    if a < r:
        raise ValueError(
            f"batched_qr needs a tall panel (a >= r), got {tuple(p.shape)}")
    batch = math.prod(lead) if lead else 1
    x = jnp.swapaxes(p.reshape(batch, a, r).astype(jnp.float32), 1, 2)
    r_pad = -(-r // _SUBLANE) * _SUBLANE
    a_pad = -(-a // _LANE) * _LANE
    if (r_pad, a_pad) != (r, a):
        x = jnp.pad(x, ((0, 0), (0, r_pad - r), (0, a_pad - a)))

    q = pl.pallas_call(
        functools.partial(_qr_kernel, r=r),
        name="batched_qr",
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, r_pad, a_pad), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, r_pad, a_pad), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, r_pad, a_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    q = jnp.swapaxes(q[:, :r, :a], 1, 2)
    return q.reshape(p.shape).astype(p.dtype)
