"""Device time per scope: the token match, the billing by hand on a
made-up trace, and the wire-format reader on the trace recorded on a
TPU v5e (``fixtures/tiny4.xplane.pb``, from before the program named
its scopes, so all of it is unscoped)."""
from __future__ import annotations

import os
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import bench, scopes, trace
from chipbench.scopes import Event, Line, Plane

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny4.xplane.pb"
BENCH = FIXTURE.parents[2]


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(round_fn)/while/body/closed_call/attention/dot_general:",
     "attention"),
    ("jit(f)/transpose(jvp(vmap(vmap(vmap(head_loss)))))/dot_general",
     "head_loss"),
    ("jit(f)/jvp(mlp)/mul", "mlp"),
    ("jit(f)/while/body/ssm/while/body/closed_call/bcn,bn->bc/dot_general",
     "ssm"),
    ("jit(f)/while/body/reduce.local/reduce_sum", "reduce.local"),
    ("jit(f)/reduce.global/reduce_sum", "reduce.global"),
    ("jit(f)/reduce.pod-2/mul", "reduce.pod-2"),
    # the innermost of two
    ("jit(f)/attention/while/body/mlp/add", "mlp"),
    # not scopes: parts of other names, an unnamed level, no path
    ("jit(f)/attention_mask/mlp_ratio/xssm/mul", "unscoped"),
    ("jit(f)/reduce/reduce_sum", "unscoped"),
    ("jit(f)/while/body/reduce_sum", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def _made_up():
    ms = 1e6
    host = Plane("/host:CPU", [Line("python3", [
        Event("round[3]", 0, 100 * ms, ""),
        Event("round[4]", 100 * ms, 100 * ms, ""),
    ])])
    loop = "jit(round_fn)/while"
    dev = Plane("/device:TPU:0", [Line("XLA Modules"), Line(trace.OPS_LINE, [
        Event("%embed", 5 * ms, 5 * ms, "jit(round_fn)/gather:"),
        # a loop holding an attention body and an MLP body
        Event("%while.1", 10 * ms, 60 * ms, loop),
        Event("%fusion.1", 12 * ms, 20 * ms,
              loop + "/body/jvp(attention)/dot_general"),
        Event("%fusion.2", 40 * ms, 25 * ms,
              loop + "/body/transpose(jvp(mlp))/dot_general"),
        Event("%head", 120 * ms, 30 * ms,
              "jit(round_fn)/jvp(vmap(head_loss))/reduce_max"),
        # runs past the last round: clipped to it
        Event("%ar", 190 * ms, 20 * ms,
              "jit(round_fn)/reduce.global/reduce_sum"),
        Event("%late", 250 * ms, 10 * ms, loop),
    ])])
    return [host, dev]


def test_billing_by_hand():
    per = scopes.ms_per_round(_made_up(), chips=1)
    # two rounds: each scope's self ms over 2
    assert per == {"unscoped": pytest.approx((5 + 60 - 20 - 25) / 2),
                   "attention": pytest.approx(10.0),
                   "mlp": pytest.approx(12.5),
                   "head_loss": pytest.approx(15.0),
                   "reduce.global": pytest.approx(5.0)}
    red = trace.reduce_planes(_made_up(), chips=1)
    assert sum(per.values()) == pytest.approx(
        1e3 * red.busy_s / len(red.rounds))


def test_an_overlap_without_nesting_shows_as_a_shortfall_of_busy_time():
    """A copy that runs on past the end of the loop it started in no
    longer shows as a shortfall: each instant goes to the latest-started
    operation running then, so the copy keeps its 10 ms, the loop loses
    the 4 ms they share, and the scopes add up to the busy time (billed
    as the loop's child, the copy took all 10 ms from the loop and left
    the 6 ms past the loop's end out)."""
    ms = 1e6
    planes = _made_up()
    ops = planes[1].lines[1].events
    # runs 6 ms past the end of %while.1 (10..70 ms)
    ops.insert(4, Event("%copy", 66 * ms, 10 * ms,
                        "jit(round_fn)/while/body/jvp(ssm)/copy"))
    per = scopes.ms_per_round(planes, chips=1)
    red = trace.reduce_planes(planes, chips=1)
    assert per["ssm"] == pytest.approx(5.0)
    assert per["unscoped"] == pytest.approx((5 + 60 - 20 - 25 - 4) / 2)
    assert sum(per.values()) == pytest.approx(
        1e3 * sum(red.op_seconds[0].values()) / len(red.rounds))
    assert sum(per.values()) == pytest.approx(
        1e3 * red.busy_s / len(red.rounds))


def test_a_loop_that_starts_under_an_async_copy_keeps_its_time():
    """On the chip a copy's span can cover the start of the next loop
    (``while`` under ``copy-done`` in the hymba-1.5b traces).  The loop
    is not the copy's child: billed as one, the copy's scope lost the
    whole loop and read below zero, and its metric went missing."""
    ms = 1e6
    host = Plane("/host:CPU", [Line("python3", [
        Event("round[3]", 0, 100 * ms, "")])])
    dev = Plane("/device:TPU:0", [Line(trace.OPS_LINE, [
        Event("%copy-done", 5 * ms, 7 * ms, "jit(round_fn)/ssm/copy"),
        Event("%while", 10 * ms, 80 * ms, "jit(round_fn)/while"),
        Event("%fusion", 20 * ms, 60 * ms,
              "jit(round_fn)/while/body/mlp/dot_general")])])
    per = scopes.ms_per_round([host, dev], chips=1)
    assert per == {"ssm": pytest.approx(5.0), "unscoped": pytest.approx(20.0),
                   "mlp": pytest.approx(60.0)}


def test_wire_reader_agrees_with_the_protobuf_classes():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    space.ParseFromString(FIXTURE.read_bytes())
    ours = {p.name: p for p in scopes.read_planes(FIXTURE, chips=1)}
    assert set(ours) == {p.name for p in space.planes
                         if p.name.startswith("/host")
                         or p.name == "/device:TPU:0"}
    for plane in space.planes:
        if plane.name not in ours:
            continue
        stat = {k: m.name for k, m in plane.stat_metadata.items()}
        mine = {ln.name: ln for ln in ours[plane.name].lines}
        assert list(mine) == [ln.name for ln in plane.lines]
        for line in plane.lines:
            if plane.name.startswith("/device") and \
                    line.name != trace.OPS_LINE:
                assert mine[line.name].events == []
                continue
            want = []
            for ev in line.events:
                meta = plane.event_metadata[ev.metadata_id]
                tf_op = next((s.str_value for s in meta.stats
                              if stat[s.metadata_id] == "tf_op"), "")
                want.append((meta.name,
                             float(line.timestamp_ns + ev.offset_ps // 1000),
                             float(ev.duration_ps // 1000), tf_op))
            got = [(e.name, e.start_ns, e.duration_ns, e.tf_op)
                   for e in mine[line.name].events]
            assert got == want
    assert any(e.tf_op for ln in ours["/device:TPU:0"].lines
               for e in ln.events)


def test_recorded_trace_is_all_unscoped_and_sums_to_busy_time():
    per = scopes.ms_per_round(scopes.read_planes(FIXTURE, chips=1), chips=1)
    red = trace.reduce(FIXTURE.parent, chips=1)
    assert set(per) == {"unscoped"}
    # trace.py's self times of the operations, which add up to the busy
    # time (0.09% of it overlaps without nesting)
    assert per["unscoped"] == pytest.approx(
        1e3 * sum(red.op_seconds[0].values()) / len(red.rounds), rel=1e-9)
    assert per["unscoped"] == pytest.approx(
        1e3 * red.busy_s / len(red.rounds), rel=1e-9)


def test_readers_find_the_newest_window_trace_of_their_cell(tmp_path,
                                                            monkeypatch):
    cell = "tiny-qwen2-vl-2b.train.p1"
    new = tmp_path / f"{cell}.-9" / "window" / "plugins" / "y.xplane.pb"
    for p in (new, tmp_path / f"{cell}.7" / "program" / "z.xplane.pb",
              tmp_path / f"{cell}-other.3" / "window" / "w.xplane.pb"):
        p.parent.mkdir(parents=True)
        shutil.copy(FIXTURE, p)
    # an older run's trace, not a trace at all: read, it would raise
    old = tmp_path / f"{cell}.7" / "window" / "x.xplane.pb"
    old.parent.mkdir()
    old.write_bytes(b"")
    os.utime(old, (1, 1))
    per = scopes.for_cell(cell, 1, traces=tmp_path)
    assert set(per) == {"unscoped"}
    assert scopes.for_cell("no-such-cell", 1, traces=tmp_path) == {}

    monkeypatch.setattr(scopes, "TRACES", tmp_path)
    ctx = SimpleNamespace(cell=SimpleNamespace(name=cell, home=BENCH),
                          chips=1)
    for metric, want in (("unscoped_ms_per_round.train", per["unscoped"]),
                         ("attention_ms_per_round.train", None),
                         ("mlp_ms_per_round.train", None),
                         ("ssm_ms_per_round.train", None),
                         ("head_loss_ms_per_round.train", None)):
        assert bench.reader(metric)(ctx) == want, metric
