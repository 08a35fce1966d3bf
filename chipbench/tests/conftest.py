"""Tiny cells for running the harness on the CPU.

Each tiny cell is a real cell with the program's smoke-test widths and
64-position sequences; it keeps the real cell's plan and correctness
limits.  Its configuration names a tiny architecture, registered here
with the program's registry as the real one's ``reduced()`` widths, so
the program and the harness run it as they run any other."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

TINY_SEQ = 64
# The four-learner qint8 cell, not in BENCHMARK.json until it
# is measured on four chips: its traffic file and readers are kept, and
# its tiny twin (four learners stacked on one CPU device) runs under the
# p1 cell's limits.
HIER = {"name": "qwen2-vl-2b.hier.p4-qint8", "config": "qwen2-vl-2b",
        "traffic": "hier.p4-qint8", "chips": 1, "why": "tests only",
        "limits": "qwen2-vl-2b.train.p1"}
# notes on the real size, which a tiny twin does not share
NOT_COPIED = ("reduced", "compiler_bytes")


def tiny_config(name: str) -> dict:
    """The configuration file's keys, each the program also has at the
    program's reduced value, under a tiny architecture registered for
    the purpose; the file's other keys (``reference_family``,
    ``program_args``, notes) as they are."""
    from repro.configs import get_config
    from repro.configs.base import register
    real = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    pc = get_config(real["arch"]).reduced()
    register(f"tiny-{real['arch']}")(lambda: pc)
    out = {k: v for k, v in real.items() if k not in NOT_COPIED}
    for k in out:
        if hasattr(pc, k):
            v = getattr(pc, k)
            out[k] = list(v) if isinstance(v, tuple) else v
    out.update(name=f"tiny-{name}", source=real["source"],
               arch=f"tiny-{real['arch']}", head_dim=pc.resolved_head_dim)
    return out


def make_root(tmp: Path) -> Path:
    """A checkout holding the real benchmark's tiny twin of every cell."""
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp / "chipbench" / "configs").mkdir(parents=True)
    (tmp / "chipbench" / "limits").mkdir()
    shutil.copytree(BENCH / "workloads", tmp / "chipbench" / "workloads")
    shutil.copytree(BENCH / "metrics", tmp / "chipbench" / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for t in (tmp / "chipbench" / "workloads").glob("*.json"):
        job = json.loads(t.read_text())
        job["seq"] = TINY_SEQ
        t.write_text(json.dumps(job))
    bench = dict(real, configs=[], workloads=[])
    for c in real["configs"]:
        cfg = tiny_config(c["name"])
        f = f"chipbench/configs/{cfg['name']}.json"
        (tmp / f).write_text(json.dumps(cfg))
        bench["configs"].append(dict(c, name=cfg["name"], file=f))
    for w in real["workloads"] + [HIER]:
        name = "tiny-" + w["name"]
        shutil.copy(BENCH / "limits" / f"{w.get('limits', w['name'])}.json",
                    tmp / "chipbench" / "limits" / f"{name}.json")
        bench["workloads"].append(dict(w, name=name,
                                       config="tiny-" + w["config"]))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


def run_tiny(root: Path, cell: str, seed: int = 2**31 + 7,
             seconds: float = 0.5) -> dict:
    """One harness run of a tiny cell on the CPU, past the chip check."""
    import jax
    from chipbench import run
    return run.run(["--workload", cell, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", "0"],
                   root=root, chips=jax.devices()[:1],
                   peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
