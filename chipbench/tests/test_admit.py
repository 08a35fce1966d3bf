"""A configuration the harness has no code for, admitted by adding files
and entries alone: a family with a leading layer of another kind and a
loss term of its own (``fixtures/admit/families/``), a program argument
beyond the depth, a scope and a program counter, each read by a new
metric file (``fixtures/admit/metrics/``).  The program side is its
tiny ``deepseek-v2-lite-16b`` twin."""
from __future__ import annotations

import json
import math
import shutil
from pathlib import Path

import jax
import pytest

from chipbench import bench, run, scopes, trace, window
from chipbench.scopes import Event, Line, Plane
from conftest import BENCH, REPO, make_root, run_tiny

ADMIT = Path(__file__).resolve().parent / "fixtures" / "admit"
ARCH = "deepseek-v2-lite-16b"
NAME = f"tiny-{ARCH}"
CELL = f"{NAME}.train.p1"
FIELDS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
          "vocab_size", "tie_embeddings", "rope_theta", "sliding_window",
          "norm_eps", "act", "n_experts", "n_shared_experts", "top_k",
          "expert_d_ff", "first_k_dense", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "router_aux_coef")


def moe_config(program_args):
    """The configuration file of the program's twin at its reduced
    widths, registered as ``conftest.tiny_config`` registers its own."""
    from repro.configs import get_config
    from repro.configs.base import register
    pc = get_config(ARCH).reduced()
    register(NAME)(lambda: pc)
    cfg = {k: getattr(pc, k) for k in FIELDS}
    cfg.update(name=NAME, source="https://arxiv.org/abs/2405.04434",
               arch=NAME, reference_family="mla_moe_fixture",
               program_args=program_args)
    return cfg


def add_files(root: Path, program_args=("--telemetry",)) -> None:
    """The new configuration's files and ``BENCHMARK.json`` entries; no
    file of the checkout is changed but ``BENCHMARK.json``."""
    home = root / "chipbench"
    new = [p for p in ADMIT.rglob("*.py")]
    for src in new:
        dst = home / src.relative_to(ADMIT)
        assert not dst.exists(), dst
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst)
    (home / "configs" / f"{NAME}.json").write_text(
        json.dumps(moe_config(list(program_args))))
    shutil.copy(BENCH / "limits" / "qwen2-vl-2b.train.p1.json",
                home / "limits" / f"{CELL}.json")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": NAME, "source": "tests only",
                         "file": f"chipbench/configs/{NAME}.json",
                         "reduced": [], "why": "tests only"})
    b["workloads"].append({"name": CELL, "config": NAME,
                           "traffic": "train.p1", "chips": 1,
                           "why": "tests only"})
    for p in new:
        if p.parent.name == "metrics":
            b["per_layer"].append({
                "name": p.stem, "unit": "ms", "better": "lower",
                "source": "device_trace", "layer": "tests only",
                "moves": "train_tokens_per_s", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))


def snapshot():
    files = [p for p in BENCH.rglob("*") if p.is_file()
             and "__pycache__" not in p.parts]
    return {p: p.read_bytes() for p in files + [REPO / "BENCHMARK.json"]}


@pytest.fixture
def moe_root(tmp_path):
    root = make_root(tmp_path)
    add_files(root)
    return root


def test_a_new_family_runs_to_a_result(tmp_path):
    before = snapshot()
    root = make_root(tmp_path)
    add_files(root)
    result = run_tiny(root, CELL)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert all(math.isfinite(v["value"]) for v in result["checks"].values())
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    assert snapshot() == before


def test_its_counts_are_the_familys_own(moe_root):
    from chipbench import counts, families
    cfg = bench.find_cell(CELL, moe_root).config
    fam = families.of(cfg)
    assert fam.__name__ == "chipbench.families.mla_moe_fixture"
    assert counts.stack_flops(cfg, 64) == fam.stack_flops(cfg, 64)
    d, vp = cfg["d_model"], counts.padded_vocab(cfg)
    assert counts.param_count(cfg) == fam.stack_params(cfg) + 2 * vp * d + d


def test_program_args_reach_main_and_a_mismatch_is_caught(tmp_path):
    root = make_root(tmp_path)
    add_files(root, program_args=["--telemetry", "--layers", "3"])
    cell = bench.find_cell(CELL, root)
    argv = bench.program_argv(cell, seed=1, rounds=2)
    assert argv[-3:] == ["--telemetry", "--layers", "3"]
    assert bench.config_mismatches(cell, run.program_config(cell)) == [
        "n_layers: program 3, file 2"]
    with pytest.raises(SystemExit, match="n_layers"):
        run_tiny(root, CELL)


def test_a_counter_reaches_a_new_metric_file(moe_root, tmp_path):
    """A traced window keeps each traced round's scalar metrics; the
    program's own ``aux_loss`` and, from ``--telemetry``, its
    ``telemetry/*`` entries."""
    from repro.launch import train
    cell = bench.find_cell(CELL, moe_root)
    argv = bench.program_argv(cell, 2**31 + 5, 10,
                              str(tmp_path / "program"))
    w = window.drive(train.main, argv, jax.devices()[:1], check_rounds=2,
                     seconds=0.0, compile_log=window.CompileLog(),
                     trace_dir=str(tmp_path / "trace"), trace_rounds=2)
    jax.profiler.stop_trace()
    assert sorted(w.counters) == [2, 3]
    assert all(any(k.startswith("telemetry/") for k in c)
               for c in w.counters.values())
    ctx = trace.Context(cell=cell, peaks={}, trace=None, chips=1,
                        counters=w.counters)
    value = bench.reader("aux_loss_fixture.train", cell.home)(ctx)
    assert value == pytest.approx(
        (w.counters[2]["aux_loss"] + w.counters[3]["aux_loss"]) / 2)
    assert bench.reader("aux_loss_fixture.train", cell.home)(
        trace.Context(cell=cell, peaks={}, trace=None, chips=1)) is None


def test_a_scope_a_new_metric_file_declares_is_billed(moe_root):
    ms = 1e6
    planes = [Plane("/host:CPU", [Line("python3", [
        Event("round[3]", 0, 100 * ms, "")])]),
        Plane("/device:TPU:0", [Line(trace.OPS_LINE, [
            Event("%a", 10 * ms, 20 * ms,
                  "jit(round_fn)/while/body/moe_fixture/dot_general"),
            Event("%b", 40 * ms, 10 * ms,
                  "jit(round_fn)/transpose(jvp(moe_fixture))/mlp/mul")])])]
    names = scopes.declared(moe_root / "chipbench" / "metrics")
    assert set(names) == set(scopes.declared()) | {"moe_fixture"}
    assert scopes.ms_per_round(planes, 1, names) == {
        "moe_fixture": pytest.approx(20.0), "mlp": pytest.approx(10.0)}
    assert scopes.ms_per_round(planes, 1) == {
        "unscoped": pytest.approx(20.0), "mlp": pytest.approx(10.0)}
