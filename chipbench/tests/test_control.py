"""The control and the planted faults of ``control.py`` at a size a CPU
test holds: the program reads within every limit of its cell, and the
bfloat16 control and each fault each fail at least one."""
from __future__ import annotations

import jax
import pytest

from chipbench import check, control, bench


def failed(numbers, limits):
    ok, _ = check.verdict(numbers, limits)
    return not ok


@pytest.mark.parametrize("cell", ["tiny-qwen2-vl-2b.train.p1",
                                  "tiny-hymba-1.5b.train.p1",
                                  "tiny-qwen2-vl-2b.hier.p4-qint8"])
def test_control_and_faults_fail_the_limits(tiny_root, cell):
    c = bench.find_cell(cell, tiny_root)
    out = control.readings(c, 2**31 + 3, jax.devices()[:1])
    assert not failed(out["program"], c.limits), out["program"]
    assert failed(out["control"], c.limits), out["control"]
    assert failed(out["half_batch"], c.limits), out["half_batch"]
    if c.traffic["learners"] > 1:
        assert failed(out["no_exchange"], c.limits), out["no_exchange"]
