#!/usr/bin/env python3
"""One run of one benchmark cell on the chips it asks for.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up runs from process start to the start of the window: the program
makes its weights and rows on the device from the seed, compiles its
round or loads it from the persistent cache (``.chipbench_cache`` at
the root of the checkout), and trains the rounds the reference follows.  The
window then drives further rounds of the same ``train.main`` call for
``--seconds`` and reports ``train_tokens_per_s`` (every position every
learner trained on in the window's whole rounds, over the window's
seconds, per chip) and ``setup_s``.  ``--trace 1`` traces a few window
rounds instead and reports the cell's per-layer metrics.

After the window the plain reference (``reference.py``) trains the same
first rounds from the same seed, and ``correct`` says whether the
program's rounds agree with it within ``limits/<cell>.json``.  The last
line of standard output is the result as JSON.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import bench, check, device, window  # noqa: E402

ROUNDS = 1_000_000           # main's fixed round count; the window ends it
TRACE_ROUNDS = 2             # window rounds a traced run records
TRACES = REPO / ".chipbench_traces"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class _Built(Exception):
    """Raised in place of building the model, with the configuration."""


def arch_config(argv):
    """The ``ArchConfig`` that ``repro.launch.train.main`` trains for
    ``argv``: ``main`` itself parses the arguments and makes the
    configuration (``get_config``, ``--reduced``, ``--layers`` and any
    later setting), and stops where it hands it to ``build``; nothing is
    built."""
    from repro.launch import train

    def build(cfg):
        raise _Built(cfg)

    real, train.build = train.build, build
    try:
        train.main(argv)
    except _Built as e:
        return e.args[0]
    finally:
        train.build = real
    raise RuntimeError("train.main returned without building its model")


def program_config(cell):
    """The configuration the program trains for this cell's argv."""
    return arch_config(bench.program_argv(cell, seed=0, rounds=1))


def run(argv=None, *, root=REPO, chips=None, peaks=None) -> dict:
    """One run; returns the result.  ``chips`` and ``peaks`` given skip
    the look for a TPU (tests on the CPU)."""
    args = parse(argv)
    cell = bench.find_cell(args.workload, root)
    import jax
    from repro.launch import train
    marks = [("imports", time.perf_counter())]
    if chips is None:
        chips = device.require_chips(cell.chips)
        peaks = device.peaks_for(chips[0].device_kind)
    marks.append(("chips", time.perf_counter()))
    dev = device.describe(chips)
    print(f"device: {dev['count']} x {dev['platform']} {dev['kind']}",
          flush=True)
    mismatch = bench.config_mismatches(cell, program_config(cell))
    if mismatch:
        raise SystemExit("the program's configuration is not the file's: "
                         + "; ".join(mismatch))

    seed = args.seed
    trace_dir = None
    if args.trace:
        trace_dir = TRACES / f"{cell.name}.{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    log = window.CompileLog()
    w = window.drive(
        train.main,
        bench.program_argv(cell, seed, ROUNDS,
                           None if trace_dir is None
                           else str(trace_dir / "program")),
        chips, check_rounds=check.CHECK_ROUNDS, seconds=args.seconds,
        compile_log=log,
        trace_dir=None if trace_dir is None else str(trace_dir / "window"),
        trace_rounds=TRACE_ROUNDS)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    setup_s = w.start_t - START
    peak = device.peak_bytes_in_use(chips)
    phases = marks + w.marks + [("window", w.start_t)]
    print("set-up phases, s from process start: " + ", ".join(
        f"{k} {t - START:.3f}" for k, t in phases), flush=True)
    print(f"set-up {setup_s:.3f}s; window {w.rounds} rounds in "
          f"{w.seconds:.3f}s, longest {w.longest_round[0]:.3f}s (round "
          f"{w.longest_round[1]}), garbage collection {w.gc_s:.3f}s; "
          f"compiles: {w.compiles_before} before the window, "
          f"{w.compiles_in_window} in it; persistent-cache hits "
          f"{log.cache_hits}; peak_bytes_in_use {peak}", flush=True)
    if w.tokens_printed != cell.tokens_per_round:
        raise SystemExit(f"the program trained {w.tokens_printed} "
                         f"positions a round, the job {cell.tokens_per_round}")

    result = {"correct": False, "attempted": w.rounds,
              "failed": sum(not math.isfinite(v) for v in w.losses.values()),
              "metrics": {}, "device": dict(dev)}
    if trace_dir is None:
        rate = w.rounds * cell.tokens_per_round / w.seconds / len(chips)
        result["metrics"] = {
            "train_tokens_per_s": {"value": rate, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        from chipbench import trace
        red = trace.reduce(trace_dir / "window", len(chips))
        ctx = trace.Context(cell=cell, peaks=peaks, trace=red,
                            chips=len(chips), counters=w.counters)
        for m in cell.per_layer:
            value = bench.reader(m["name"], cell.home)(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the program's state went with main's frame: free it, read the
    # compiler's plan of the timed round (from the persistent cache), and
    # only then let the reference take the chips
    prog = check.program_readings(w)
    round_fn, round_args = w.round_fn, w.round_args
    del w
    gc.collect()
    compiles = log.compiles
    plan = device.plan_bytes(round_fn, round_args)
    del round_fn, round_args
    print(f"compiler's plan of the round: {plan} bytes a chip "
          f"({log.compiles - compiles} compiles to read it)", flush=True)
    # the runtime's counter leaves out the round's scratch: the plan
    # is the peak the round fills
    result["device"].update(memory_peak_bytes=max(peak, plan or 0),
                            peak_bytes_in_use=peak, round_plan_bytes=plan)
    jax.clear_caches()
    t = time.perf_counter()
    ref = check.reference_readings(cell.config, cell.traffic, seed,
                                   devices=chips)
    ref_s = time.perf_counter() - t
    numbers = check.compare(prog, ref)
    ok, shown = check.verdict(numbers, cell.limits)
    print(f"reference: {ref_s:.1f}s; losses program {prog['losses']} "
          f"reference {ref['losses']}; worst leaves: update "
          f"{numbers['update_gap']['at']}, change "
          f"{numbers['change_gap']['at']}; left out "
          f"{numbers['left_out']}", flush=True)
    result["correct"] = bool(ok and result["failed"] == 0)
    result["checks"] = shown
    return result


def main(argv=None) -> int:
    bench.use_cache()
    try:
        result = run(argv)
    except device.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    for name, v in result["checks"].items():
        print(f"{name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
