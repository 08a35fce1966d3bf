"""Per-block int8 scale quantization reducer.

Each learner quantizes its parameters blockwise (absmax scale per block of
``block`` consecutive elements, int8 mantissa) — 1 byte/element + 4
bytes/block on the wire vs 4 bytes/element dense.  Stateless: the
round-trip error is bounded by ``absmax(block) / 254`` per element, which
test_comm.py asserts, so no error feedback is carried.

Two wire layouts:

  * **fused** (default): one pass through ``kernels/ops.py::qint8_pack``
    emits a single contiguous int8 buffer per leaf/bucket — payload and
    bitcast fp32 scales interleaved per block — so each reduction ships
    ONE message instead of two (``n_messages``).  The final partial
    block is zero-padded on the wire, which ``payload_bytes`` bills
    honestly (``nb * (block + 4)`` bytes).
  * **twopass** (``qint8:<block>:twopass``): the legacy
    :func:`quantize_block`/:func:`dequantize_block` pair — int8 payload
    and fp32 scale arrays ride the collective as SEPARATE messages
    (2 per leaf/bucket), the baseline the fused-pack A/B measures
    against.

Both quantize with identical math (the fused scale bytes are a bitcast,
not a cast), so the dequantized values are bit-identical under jit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comm.reducer import N_LEARNER_AXES, Reducer, per_learner_size
from repro.kernels import ops


def _blocked(x2d, block: int):
    """[rows, n] -> ([rows, nb, block], n) zero-padded to a block multiple."""
    rows, n = x2d.shape
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        x2d = jnp.pad(x2d, ((0, 0), (0, pad)))
    return x2d.reshape(rows, nb, block)


def quantize_block(x2d, block: int):
    """[rows, n] fp -> (q int8 [rows, nb, block], scale fp32 [rows, nb, 1])."""
    xb = _blocked(x2d.astype(jnp.float32), block)
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_block(q, scale, n: int):
    """Inverse of quantize_block: -> [rows, n] fp32 (padding stripped)."""
    rows = q.shape[0]
    x = q.astype(jnp.float32) * scale
    return x.reshape(rows, -1)[:, :n]


class QInt8Reducer(Reducer):
    """int8 payload with per-block fp32 scales; averaging in fp32."""

    name = "qint8"
    bucket_by_default = True
    has_codec = True

    def __init__(self, block: int = 256, fused: bool = True,
                 impl: str = "auto"):
        if block < 1:
            raise ValueError(f"qint8 block must be >= 1, got {block}")
        self.block = int(block)
        self.fused = bool(fused)
        # pack/unpack kernel dispatch (kernels/ops.py): "auto" | "xla"
        # | "pallas" | "pallas_interpret"
        self.impl = impl

    def _flat(self, leaf):
        rows = 1
        for d in leaf.shape[:N_LEARNER_AXES]:
            rows *= d
        return leaf.reshape(rows, per_learner_size(leaf))

    def compress(self, tree, state):
        if self.fused:
            # the learner axes stay leading: the kernels take their rows
            # by index, not by a reshape (kernels/qint8_pack.py)
            payload = [ops.qint8_pack(leaf.reshape(
                leaf.shape[:N_LEARNER_AXES] + (per_learner_size(leaf),)),
                self.block, impl=self.impl)
                for leaf in jax.tree.leaves(tree)]
        else:
            payload = [quantize_block(self._flat(leaf), self.block)
                       for leaf in jax.tree.leaves(tree)]
        return payload, state

    def decompress(self, payload, like, state):
        leaves, treedef = jax.tree.flatten(like)
        if self.fused:
            out = [ops.qint8_unpack(w, per_learner_size(leaf),
                                    impl=self.impl).reshape(leaf.shape)
                   for w, leaf in zip(payload, leaves)]
        else:
            out = [dequantize_block(q, s, per_learner_size(leaf)
                                    ).reshape(leaf.shape)
                   for (q, s), leaf in zip(payload, leaves)]
        return treedef.unflatten(out)

    def finalize(self, avg_tree, orig_tree, state):
        out = jax.tree.map(lambda a, o: a.astype(o.dtype),
                           avg_tree, orig_tree)
        return out, state

    def n_messages(self, tree) -> int:
        """Fused: one packed buffer per leaf/bucket.  Two-pass: the int8
        payload AND the fp32 scale array each ride as their own
        collective — the honest baseline bill the fused A/B beats."""
        per = 1 if self.fused else 2
        return per * len(jax.tree.leaves(tree))

    def payload_bytes(self, tree) -> int:
        total = 0
        for leaf in jax.tree.leaves(tree):
            n = leaf.size
            nb = -(-n // self.block)
            if self.fused:
                # the packed wire buffer ships whole blocks: the final
                # partial block's zero tail is transmitted
                total += nb * (self.block + 4)
            else:
                total += n + nb * 4
        return int(total)

    def _describe(self) -> str:
        return f"qint8:{self.block}" + ("" if self.fused else ":twopass")
