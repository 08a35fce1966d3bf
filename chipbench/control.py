#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the
cell's own size, for several seeds in one process.

    python3 chipbench/control.py --workload <cell> --seeds 11 12 13 [--out F]

For each seed: the program's first rounds (its own ``train.main``, the
window's hook keeping learner 0's weights) against the reference, which
is the lower reading; and, in the program's place, the control (the
reference held in bfloat16, the precision below the configuration's
float32) and the planted faults against the reference, which give the
upper readings:

* ``half_batch``: the cross-entropy taken over half of each sequence's
  positions;
* ``no_exchange`` (cells with more than one learner): the groups' means
  left out.

A step that leaves the state unchanged reads 1 on ``update_gap`` by
construction and needs no run.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import bench, check, device, window  # noqa: E402


def half_batch_loss(params, rows, cfg):
    """The fault: the cross-entropy's mean over the first half of the
    positions alone; the family's loss term stays as it is."""
    from chipbench import reference as R
    h, extra = R.hidden(params, rows, cfg)
    n = h.shape[1] // 2
    return R.mean_nll(params, h[:, :n], rows["labels"][:, :n]) + extra


def program_side(cell, seed, chips):
    from repro.launch import train
    w = window.drive(train.main,
                     bench.program_argv(cell, seed, check.CHECK_ROUNDS + 1),
                     chips, check_rounds=check.CHECK_ROUNDS, seconds=0.0,
                     compile_log=window.CompileLog())
    return check.program_readings(w)


def readings(cell, seed, chips):
    import gc
    import jax
    import jax.numpy as jnp
    prog = program_side(cell, seed, chips)
    gc.collect()
    jax.clear_caches()

    def ref(**kw):
        return check.reference_readings(cell.config, cell.traffic, seed,
                                        devices=chips, trainer_kw=kw)

    base = ref()
    out = {"seed": seed, "program": check.compare(prog, base),
           "control": check.compare(ref(dtype=jnp.bfloat16), base),
           "half_batch": check.compare(ref(loss_fn=half_batch_loss), base)}
    if cell.traffic["learners"] > 1:
        out["no_exchange"] = check.compare(ref(exchange=False), base)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench.use_cache()
    cell = bench.find_cell(args.workload)
    chips = device.require_chips(cell.chips)
    for seed in args.seeds:
        line = json.dumps(readings(cell, seed, chips))
        print("readings", line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
