"""The catalog: cells, configurations, jobs and per-layer readers, found
by the names ``BENCHMARK.json`` gives them.

A cell names a configuration (``configs`` entry, whose ``file`` holds
the sizes) and a traffic mix (``workloads/<traffic>.json``, the training
job: learners, plan, batch, sequence length).  Its correctness limits
are ``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<metric>.py``; the configuration's reference family is
``families/<name>.py``, named by its ``reference_family`` or else its
``family`` (``families/__init__.py`` says what a family gives: one kind
of layer, or a stack of its own with a loss term and its own counts).
Adding a cell, a configuration, a family or a metric adds files and
entries; no code changes:

* program settings: a configuration file's ``program_args``, a list of
  further ``train.main`` arguments, go after the job's own; the run
  checks the configuration ``main`` builds from the whole argument list
  (``run.program_config``) against the file;
* scopes: a metric file's ``SCOPE = "<name>"`` makes ``scopes.py`` bill
  the device time of operations under the program's ``<name>`` scope
  to it (beside the ``reduce.<level>`` scopes, always billed);
* counters: a traced run hands a reader ``ctx.counters``, each traced
  round's scalar entries of the metrics dict ``main`` prints its round
  line from (``trace.Context``).
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

from chipbench import families

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
# JAX's persistent compilation cache: a fixed directory inside the
# checkout (the path is part of the cache's key), apart from the
# program's own default so that nothing else fills or trims it
CACHE = REPO / ".chipbench_cache"

# ArchConfig fields of the program that must equal the configuration
# file's numbers (the reference computes from the file); each family
# adds its own.
PROGRAM_FIELDS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads",
                  "d_ff", "vocab_size", "tie_embeddings", "rope_theta",
                  "sliding_window", "norm_eps", "act")


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    per_layer: List[Dict[str, Any]] = field(default_factory=list)
    home: Path = HERE          # the benchmark's directory in the checkout

    @property
    def tokens_per_round(self) -> int:
        """Positions every learner trains on in one round."""
        from chipbench.counts import steps_per_round
        t = self.traffic
        return (steps_per_round(t["plan"]) * t["learners"] * t["batch"]
                * t["seq"])


def use_cache() -> None:
    """Before JAX is imported: keep every program this process compiles,
    down to the loader's small ones, in :data:`CACHE`."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def load_benchmark(root: Path = REPO) -> Dict[str, Any]:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: Dict[str, Any], cell: str, e2e: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e


def find_cell(name: str, root: Path = REPO,
              bench: Optional[Dict[str, Any]] = None) -> Cell:
    root = Path(root)
    bench = bench or load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    home = root / bench["paths"][0]
    traffic = json.loads(
        (home / "workloads" / f"{w['traffic']}.json").read_text())
    limits = json.loads((home / "limits" / f"{name}.json").read_text())
    e2e = [m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, e2e)]
    families.search(home / "families")
    return Cell(name, int(w["chips"]), config, traffic, limits, per_layer,
                home)


def program_argv(cell: Cell, seed: int, rounds: int,
                 profile_dir: Optional[str] = None) -> List[str]:
    """Arguments of ``repro.launch.train.main`` for this cell's job, then
    the configuration file's ``program_args``.  The serial bucket engine
    (``--no-overlap``): the pipelined one pads every bucket to the
    largest leaf and does not fit."""
    c, t = cell.config, cell.traffic
    argv = ["--arch", c["arch"], "--layers", str(c["n_layers"]),
            "--learners", str(t["learners"]), "--s", str(t["s"]),
            "--plan", t["plan"], "--batch", str(t["batch"]),
            "--seq", str(t["seq"]), "--lr", repr(float(t["lr"])),
            "--seed", str(seed), "--rounds", str(rounds), "--no-overlap"]
    argv += [str(a) for a in c.get("program_args", [])]
    if profile_dir is not None:
        argv += ["--profile-dir", profile_dir]
    return argv


def config_mismatches(cell: Cell, program_cfg) -> List[str]:
    """Where the program's own configuration differs from the file's: the
    shared fields and the family's own (``FIELDS``)."""
    c = cell.config
    fields = PROGRAM_FIELDS + families.of(c).FIELDS
    have = dict({k: getattr(program_cfg, k) for k in fields},
                head_dim=program_cfg.resolved_head_dim)
    out = []
    for key, got in have.items():
        want = c.get(key)
        if isinstance(want, list):
            got = list(got)
        if key in c and got != want:
            out.append(f"{key}: program {got!r}, file {want!r}")
    return out


def metric_module(path: Path) -> ModuleType:
    """A metric file, loaded (``metrics/<metric>.py``)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, home: Path = HERE) -> Callable:
    """``read(ctx) -> float | None`` of ``metrics/<metric>.py``."""
    return metric_module(Path(home) / "metrics" / f"{metric}.py").read
