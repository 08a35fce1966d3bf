"""The trace reduction: by hand on a made-up trace, and on a small trace
recorded on a TPU v5e (``fixtures/tiny4.xplane.pb``): the two window
rounds of the program's smoke-test qwen2-vl-2b widths, four learners
stacked on one chip under ``local@1:qint8:128/global@8``, 256 positions
a step."""
from __future__ import annotations

from pathlib import Path
from typing import List, NamedTuple, Tuple

import pytest

from chipbench import codec, trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"


class Ev(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    stats: List[Tuple[str, object]] = []


class Line(NamedTuple):
    name: str
    events: List[Ev]


class Plane(NamedTuple):
    name: str
    lines: List[Line]


AR = ("%all-reduce.1 = f32[1,1,1,1000]{3,2,1,0:T(8,128)} "
      "all-reduce(f32[1,1,1,1000]{3,2,1,0} %p), to_apply=%add")
F1 = "%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(f32[8] %a), kind=kLoop"
F2 = "%fusion.2 = (f32[8]{0}, s32[]) fusion(f32[8] %b), kind=kLoop"
W = "%while.3 = (s32[], f32[8]{0:T(128)}) while((s32[], f32[8]) %t)"


def made_up():
    ms = 1e6
    host = Plane("/host:CPU", [Line("python3", [
        Ev("round[3]", 0, 100 * ms), Ev("data", 0, 10 * ms),
        Ev("device", 10 * ms, 80 * ms), Ev("host_sync", 90 * ms, 10 * ms),
        Ev("round[4]", 100 * ms, 100 * ms), Ev("data", 100 * ms, 20 * ms),
        Ev("device", 120 * ms, 75 * ms), Ev("host_sync", 195 * ms, 5 * ms),
    ])])
    dev = Plane("/device:TPU:0", [Line("XLA Modules", []), Line("XLA Ops", [
        Ev(F1, 10 * ms, 30 * ms), Ev(F2, 40 * ms, 30 * ms),
        Ev(AR, 70 * ms, 10 * ms),
        Ev(W, 120 * ms, 70 * ms),               # holds the next two
        Ev(F1, 125 * ms, 60 * ms), Ev(AR, 185 * ms, 5 * ms),
        Ev(F1, 250 * ms, 10 * ms),              # after the window
    ])])
    return [host, dev]


def test_made_up_trace_by_hand():
    red = trace.reduce_planes(made_up(), chips=1)
    assert [r.index for r in red.rounds] == [3, 4]
    assert red.window_s == pytest.approx(0.2)
    # busy: [10, 80] and [120, 190] ms
    assert red.busy_s == pytest.approx(0.070 + 0.070)
    assert red.op_runs[0]["%all-reduce.1"] == 2
    assert red.op_seconds[0]["%while.3"] == pytest.approx(0.005)
    assert red.mean_op_seconds(trace.is_collective) == pytest.approx(0.015)
    # idle [0, 10], [80, 120], [190, 200] ms, cut at the host spans
    assert red.gaps == [
        ("data", pytest.approx(0.010)), ("device", pytest.approx(0.010)),
        ("host_sync", pytest.approx(0.010)), ("data", pytest.approx(0.020)),
        ("device", pytest.approx(0.005)), ("host_sync", pytest.approx(0.005))]
    b = red.breakdown()
    assert b["device_ops"][0] == ["%fusion.1 fusion f32[8,128]",
                                  pytest.approx(0.090)]
    assert b["idle_gaps"][0] == ["data (2 gaps, longest 0.020000s)",
                                 pytest.approx(0.030)]
    assert trace.result_bytes(red.op_text["%all-reduce.1"]) == 4000
    assert trace.opcode(W) == "while"
    assert trace.result_arrays(F2) == [("f32", (8,)), ("s32", ())]


def test_a_trace_without_rounds_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_planes(made_up()[1:], chips=1)


def test_recorded_trace():
    red = trace.reduce(FIXTURES, chips=1)
    assert [r.index for r in red.rounds] == [3, 4]
    assert all(set(r.spans) == {"data", "device", "host_sync"}
               for r in red.rounds)
    assert 0 < red.busy_s < red.window_s
    # the tiny rows' eager jax.random calls keep the chip waiting: most of
    # each round is the loader's
    assert red.busy_s / red.window_s < 0.1
    assert red.gaps and max(red.gaps, key=lambda g: g[1])[0] == "data"
    pack = red.mean_op_seconds(lambda t: codec.is_kernel(t, "pack", [128]))
    unpack = red.mean_op_seconds(
        lambda t: codec.is_kernel(t, "unpack", [128]))
    assert pack > 0 and unpack > 0
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
