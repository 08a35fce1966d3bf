"""Roofline share of the qint8 codec kernels, from their device time in
the trace and the bytes the configuration's parameters need.

The Pallas kernels carry no name of their own into the trace: each is a
``custom-call`` to ``tpu_custom_call``, known by its signature.  The
pack turns float32 blocks ``[..., block]`` into int8 rows
``[..., block + 4]`` (payload and the scale's four bytes); the unpack
does the reverse."""
from __future__ import annotations

from typing import List, Optional

from chipbench import counts
from chipbench.trace import opcode, result_arrays


def qint8_blocks(plan: str) -> List[int]:
    out = []
    for part in plan.split("/"):
        codec = part.split("@", 1)[1].split(":")[1:]
        if codec[:1] == ["qint8"]:
            out.append(int(codec[1]))
    return out


def is_kernel(text: str, which: str, blocks: List[int]) -> bool:
    """Whether an operation is a launch of the ``which`` kernel."""
    if opcode(text) != "custom-call" or "tpu_custom_call" not in text:
        return False
    res = result_arrays(text)
    if len(res) != 1:
        return False
    dtype, dims = res[0]
    if which == "pack":
        return dtype == "s8" and dims[-1] - 4 in blocks
    return dtype == "f32" and dims[-1] in blocks and \
        any(f",{b + 4}]" in text.split("custom-call(", 1)[1][:200]
            for b in blocks)


def least_bytes_per_round(cell, which: str, chips: int) -> int:
    """HBM bytes one chip's kernels of this kind need in a round."""
    n = counts.param_count(cell.config) * cell.traffic["learners"] // chips
    fires = counts.level_fires_per_round(cell.traffic["plan"])
    per = (counts.qint8_pack_bytes if which == "pack"
           else counts.qint8_unpack_bytes)
    total = 0
    for part in cell.traffic["plan"].split("/"):
        name, rest = part.split("@", 1)
        codec = rest.split(":")[1:]
        if codec[:1] == ["qint8"]:
            total += fires[name] * per(n, int(codec[1]))
    return total


def roofline(ctx, which: str) -> Optional[float]:
    t = ctx.trace
    blocks = qint8_blocks(ctx.cell.traffic["plan"])
    seconds = t.mean_op_seconds(lambda text: is_kernel(text, which, blocks))
    need = least_bytes_per_round(ctx.cell, which, ctx.chips)
    if seconds == 0.0 or need == 0:
        return None
    least = need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / len(t.rounds))
