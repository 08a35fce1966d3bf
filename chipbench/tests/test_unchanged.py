"""The numbers both configurations read stay those recorded before a
family could own its stack, loss term and counts
(``fixtures/parent_readings.json``): the reference's losses and leaf
norms at tiny widths on the CPU, sound and with the half-batch fault;
the operation, parameter and qint8 byte counts at real and tiny widths.
The billing of the recorded trace is the one reading that moves: its
operations that overlap without nesting now keep their time."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import pytest

from chipbench import check, codec, control, counts, bench, scopes, trace
from conftest import BENCH, TINY_SEQ, tiny_config

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PARENT = json.loads((FIXTURES / "parent_readings.json").read_text())


def job(traffic: str, seq=None) -> dict:
    t = json.loads((BENCH / "workloads" / f"{traffic}.json").read_text())
    return t if seq is None else dict(t, seq=seq)


@pytest.mark.parametrize("name", ["qwen2-vl-2b", "hymba-1.5b"])
@pytest.mark.parametrize("kind,kw", [
    ("base", {}), ("half_batch", {"loss_fn": control.half_batch_loss})])
def test_reference_readings(name, kind, kw):
    got = check.reference_readings(
        tiny_config(name), job("train.p1", TINY_SEQ), PARENT["seed"],
        devices=jax.devices()[:1], trainer_kw=kw)
    assert got == PARENT["reference"][f"{name}.train.p1"][kind]


@pytest.mark.parametrize("name", ["qwen2-vl-2b", "hymba-1.5b"])
@pytest.mark.parametrize("size", ["real", "tiny"])
def test_counts(name, size):
    cfg = (json.loads((BENCH / "configs" / f"{name}.json").read_text())
           if size == "real" else tiny_config(name))
    want = PARENT["counts"][f"{name}.{size}"]
    assert {s: counts.forward_flops(cfg, int(s))
            for s in want["forward_flops"]} == want["forward_flops"]
    assert counts.param_count(cfg) == want["param_count"]
    for traffic in ("train.p1", "hier.p4-qint8"):
        assert counts.train_flops_per_round(cfg, job(traffic)) == \
            want[f"train_flops_per_round.{traffic}"]


def test_qint8_bytes():
    cell = bench.find_cell("qwen2-vl-2b.train.p1")
    cell.traffic = job("hier.p4-qint8")
    assert {w: codec.least_bytes_per_round(cell, w, 4)
            for w in ("pack", "unpack")} == \
        PARENT["counts"]["qwen2-vl-2b.hier.p4-qint8.least_bytes"]


def test_recorded_trace_billing():
    """The parent's billing fell short of the busy time by the 0.09% that
    overlaps without nesting; now it is the busy time."""
    planes = scopes.read_planes(FIXTURES / "tiny4.xplane.pb", chips=1)
    per = scopes.ms_per_round(planes, chips=1)
    red = trace.reduce_planes(planes, chips=1)
    busy = 1e3 * red.busy_s / len(red.rounds)
    assert set(per) == set(PARENT["tiny4_ms_per_round"]) == {"unscoped"}
    assert per["unscoped"] == pytest.approx(busy, rel=1e-9)
    assert PARENT["tiny4_ms_per_round"]["unscoped"] == pytest.approx(
        busy - 0.006861, abs=1e-6)
