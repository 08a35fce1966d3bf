"""Cells are data: a new traffic file and a new BENCHMARK.json entry make
a new cell, with no other file changed."""
from __future__ import annotations

import json

import pytest

from chipbench import bench
from conftest import BENCH, REPO, make_root


def test_every_cell_resolves_and_builds_its_argv():
    for w in bench.load_benchmark()["workloads"]:
        cell = bench.find_cell(w["name"])
        argv = bench.program_argv(cell, seed=5, rounds=10)
        assert argv[argv.index("--arch") + 1] == cell.config["arch"]
        assert argv[argv.index("--plan") + 1] == cell.traffic["plan"]
        assert cell.per_layer, w["name"]


def test_a_new_workload_file_is_found(tmp_path):
    before = {p: p.read_bytes() for p in BENCH.rglob("*.json")}
    root = make_root(tmp_path)
    job = {"learners": 2, "s": 2, "plan": "local@2/global@4", "batch": 2,
           "seq": 512, "lr": 0.01}
    (root / "chipbench" / "workloads" / "train.p2.json").write_text(
        json.dumps(job))
    (root / "chipbench" / "limits" / "tiny-hymba-1.5b.train.p2.json"
     ).write_text(json.dumps({"loss_gap": 1, "update_gap": 1,
                              "change_gap": 1}))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-hymba-1.5b.train.p2",
                           "config": "tiny-hymba-1.5b", "traffic": "train.p2",
                           "chips": 2, "why": "a new cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = bench.find_cell("tiny-hymba-1.5b.train.p2", root)
    argv = bench.program_argv(cell, seed=11, rounds=3)
    assert argv[argv.index("--learners") + 1] == "2"
    assert argv[argv.index("--plan") + 1] == "local@2/global@4"
    assert argv[argv.index("--batch") + 1] == "2"
    assert argv[argv.index("--arch") + 1] == "tiny-hymba-1.5b"
    assert "--no-overlap" in argv
    assert cell.tokens_per_round == 4 * 2 * 2 * 512
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in b["per_layer"] if "workloads" not in m}
    assert {p: p.read_bytes() for p in BENCH.rglob("*.json")} == before


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        bench.find_cell("no-such.cell", REPO)


def test_every_per_layer_metric_has_a_reader():
    for m in bench.load_benchmark()["per_layer"]:
        assert callable(bench.reader(m["name"]))


def test_the_program_runs_what_each_configuration_file_states():
    from chipbench import run
    for w in bench.load_benchmark()["workloads"]:
        cell = bench.find_cell(w["name"])
        assert bench.config_mismatches(cell, run.program_config(cell)) == []


def test_a_configuration_the_program_does_not_run_is_caught():
    from chipbench import run
    cell = bench.find_cell("hymba-1.5b.train.p1")
    cell.config = dict(cell.config, d_ff=5505, ssm_state=8)
    out = bench.config_mismatches(cell, run.program_config(cell))
    assert len(out) == 2 and out[0].startswith("d_ff")


@pytest.mark.parametrize("extra", [[], ["--layers", "1"], ["--reduced"],
                                   ["--reduced", "--layers", "3"]])
def test_the_config_from_argv_is_the_one_main_trains(monkeypatch, extra):
    """``run.arch_config`` against the configuration a whole ``train.main``
    run hands to ``build`` and the weights it trains."""
    import repro.launch.train as train
    from chipbench import run
    from conftest import tiny_config
    argv = ["--arch", tiny_config("qwen2-vl-2b")["arch"]] + extra
    want = run.arch_config(argv)
    built = []
    real = train.build

    def build(cfg):
        built.append(cfg)
        return real(cfg)
    monkeypatch.setattr(train, "build", build)
    out = train.main(argv + ["--rounds", "1", "--learners", "1", "--s", "1",
                             "--batch", "1", "--seq", "16", "--k1", "1",
                             "--k2", "1"])
    assert built == [want]
    assert out.state.params["layers"]["ln1"]["scale"].shape[-2] == \
        want.n_layers
