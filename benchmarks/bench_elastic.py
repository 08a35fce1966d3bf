"""Elastic-membership benchmark: dropout convergence, masked overhead,
reshape round-trip, cross-process fault determinism.

Four sections, machine-readable records in ``RECORDS`` (benchmarks/
run.py writes them to BENCH_elastic.json / .smoke.json):

1. **Dropout convergence** (the PR's headline): the 3-level fleet with
   20% pod-level dropout (``flaky:pod:0.2``) vs the fault-free run on
   the same seed/data.  The ``elastic/dropout20`` record carries the
   final-loss gap and the Theorem 3.2 bound bar priced at the dropout
   run's *effective* participant count
   (``theory.effective_participants``) — ``within_bars`` is CI-gated.

2. **Masked overhead**: a fault schedule that never fires
   (``flaky:0.0``) against the dense round program — the all-ones mask
   must be bit-identical in losses AND add only a small wall-clock
   overhead (the mask is one fused multiply + renormalize per grouped
   mean).  ``overhead_frac`` is CI-gated at a lenient 2-core-container
   bound; the point is catching an accidental second reduction, not
   hardware-grade timing.

3. **Reshape round-trip**: checkpoint a 4-learner fleet mid-run (topk
   error feedback carried), ``elastic_restore`` onto 6 learners, then
   back onto 4 — survivors bit-preserved, joiners donor-cloned with
   zeroed EF residual, round-trip exact (all CI-gated).

4. **Fault determinism**: the mask stream of a mixed
   crash/flaky/straggler schedule, hashed in-process and in a FRESH
   subprocess — must agree (the schedule is a pure function of
   (seed, unit, round); the A/B legs above rely on it).

``run(smoke=True)`` (CI) shortens the convergence legs.

Standalone: PYTHONPATH=src python -m benchmarks.bench_elastic [--smoke]
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile
from typing import Dict, List

import jax
import numpy as np

from benchmarks.common import Row, cls_setup, timed_run
from repro.configs.base import HierAvgParams
from repro.core import HierTopology, Simulator, init_state
from repro.core.plan import resolve_plan
from repro.core.theory import (effective_participants, thm32_bound,
                               thm32_condition)
from repro.elastic import (FaultSchedule, elastic_restore,
                           save_elastic_checkpoint)
from repro.optim import sgd
from repro.runtime import refuse_on_tpu

RECORDS: List[Dict] = []

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

TOPO = HierTopology(2, 2, 2)
PLAN = "local@2/pod@4/global@8"
DROP = "flaky:pod:0.2"
# Thm 3.2 constants, matching tests/test_hier_avg.py's 3-level sweep
F1, L, M, GAMMA, B = 2.0, 1.0, 1.0, 0.05, 16
# loose ceiling for the masked-program overhead on a noisy shared-CPU
# container; the regression this catches is structural (an extra
# reduction or a broken jit cache), not a few-percent drift
OVERHEAD_CEILING = 0.35

DET_SPEC = "crash:0.1/flaky:pod:0.3:2/straggler:0.5:1.0"
DET_DEADLINES = {"local": 0.5, "pod": 1.0, "global": 2.0}


def _sim(setup, faults=None, seed: int = 3) -> Simulator:
    return Simulator(setup["loss_fn"], setup["init_fn"], setup["sample"],
                     topo=TOPO, hier=HierAvgParams(plan=PLAN),
                     optimizer=sgd(GAMMA), seed=seed, per_learner_batch=B,
                     eval_batch=setup["eval_batch"], faults=faults)


def _dropout_rows(setup, rounds: int, smoke: bool) -> List[Row]:
    rows: List[Row] = []
    res, us = {}, {}
    for name, faults in (("faultfree", None), ("dropout20", DROP)):
        res[name], us[name] = timed_run(_sim(setup, faults), rounds)
    ff, dp = res["faultfree"], res["dropout20"]
    gap = abs(float(dp.eval_losses[-1]) - float(ff.eval_losses[-1]))
    n_eff = effective_participants(TOPO.n_learners, 0.2)
    bar = thm32_bound(F1, L, M, GAMMA, K1=2, K2=8, S=2, P=n_eff, B=B,
                      N=rounds)
    fracs = dp.active_fracs.mean(axis=0)
    RECORDS.append({
        "name": "elastic/faultfree", "us": us["faultfree"],
        "rounds": rounds, "plan": PLAN, "topo": list(TOPO.shape),
        "final_train_loss": float(ff.losses[-1]),
        "final_eval_loss": float(ff.eval_losses[-1]),
        "final_eval_acc": float(ff.eval_accs[-1]), "smoke": smoke,
    })
    RECORDS.append({
        "name": "elastic/dropout20", "us": us["dropout20"],
        "rounds": rounds, "plan": PLAN, "faults": DROP,
        "final_train_loss": float(dp.losses[-1]),
        "final_eval_loss": float(dp.eval_losses[-1]),
        "final_eval_acc": float(dp.eval_accs[-1]),
        "loss_gap": gap, "thm32_bar": float(bar),
        "thm32_condition": bool(thm32_condition(L, GAMMA, K2=8)),
        "within_bars": bool(gap <= bar), "n_eff": float(n_eff),
        "mean_active_frac": {n: float(f) for n, f in
                             zip(("local", "pod", "global"), fracs)},
        "mean_round_wall_s": float(dp.round_wall_s.mean()),
        "smoke": smoke,
    })
    rows.append(("elastic/faultfree", us["faultfree"],
                 f"eval_loss={ff.eval_losses[-1]:.4f}"))
    rows.append(("elastic/dropout20", us["dropout20"],
                 f"eval_loss={dp.eval_losses[-1]:.4f} gap={gap:.4f} "
                 f"bar={bar:.3f} within={gap <= bar} "
                 f"frac={fracs.mean():.3f}"))
    return rows


def _overhead_row(setup, rounds: int, smoke: bool) -> Row:
    import time
    # warm both jit caches first (the elastic program is a different —
    # and bigger — trace than the dense one; compile time is not the
    # claim), then INTERLEAVE the timed reps and take each leg's min:
    # this box's scheduler noise is bimodal and sequential A/B legs
    # would bill one leg's bad luck as the other's overhead
    reps = 2 if smoke else 4
    sims, best, res = {}, {}, {}
    for name, faults in (("dense", None), ("masked", "flaky:0.0")):
        sims[name] = _sim(setup, faults)
        sims[name].run(1)
        best[name] = None
    for _ in range(reps):
        for name, sim in sims.items():
            t0 = time.time()
            res[name] = sim.run(rounds)
            u = (time.time() - t0) / rounds * 1e6
            best[name] = u if best[name] is None else min(best[name], u)
    dense_us, dense_res = best["dense"], res["dense"]
    masked_us, masked_res = best["masked"], res["masked"]
    overhead = (masked_us - dense_us) / dense_us
    identical = bool(np.array_equal(dense_res.losses, masked_res.losses))
    RECORDS.append({
        "name": "elastic/masked_overhead", "us": masked_us,
        "dense_us": dense_us, "overhead_frac": float(overhead),
        "overhead_ceiling": OVERHEAD_CEILING,
        "bit_identical_losses": identical, "rounds": rounds,
        "smoke": smoke,
    })
    return ("elastic/masked_overhead", masked_us,
            f"dense_us={dense_us:.0f} overhead={overhead:+.1%} "
            f"bit_identical={identical}")


def _reshape_row(setup, smoke: bool) -> Row:
    import time
    old_topo, new_topo = HierTopology(1, 2, 2), HierTopology(1, 3, 2)
    hier = HierAvgParams(plan="global@2:topk:0.25")
    sim = Simulator(setup["loss_fn"], setup["init_fn"], setup["sample"],
                    topo=old_topo, hier=hier, optimizer=sgd(GAMMA),
                    seed=13, per_learner_batch=8)
    state = sim.run(2).state
    plan = resolve_plan(hier)

    def rows_of(tree, topo):
        return [np.asarray(x).reshape((-1,) + x.shape[3:])
                for x in jax.tree.leaves(tree)
                if hasattr(x, "ndim") and x.ndim >= 3
                and tuple(x.shape[:3]) == topo.shape]

    with tempfile.TemporaryDirectory() as d:
        d4, d6 = os.path.join(d, "f4"), os.path.join(d, "f6")
        save_elastic_checkpoint(d4, state, old_topo, step=2, plan=sim.plan)
        t0 = time.time()
        like6 = init_state(new_topo, setup["init_fn"], sgd(GAMMA),
                           jax.random.PRNGKey(99), plan=plan)
        got6 = elastic_restore(d4, like6, new_topo=new_topo)
        grow_s = time.time() - t0
        survivors_ok = all(
            np.array_equal(n[:4], o) for o, n in
            zip(rows_of(state.params, old_topo),
                rows_of(got6.params, new_topo)))
        ef_ok = all(
            np.array_equal(n[:4], o) for o, n in
            zip(rows_of(state.comm_state, old_topo),
                rows_of(got6.comm_state, new_topo)))
        err_zeroed = all(
            np.all(n[4:] == 0) for n in
            rows_of(got6.comm_state["global"].err, new_topo))
        save_elastic_checkpoint(d6, got6, new_topo, step=2, plan=sim.plan)
        like4 = init_state(old_topo, setup["init_fn"], sgd(GAMMA),
                           jax.random.PRNGKey(98), plan=plan)
        back = elastic_restore(d6, like4, new_topo=old_topo)
        roundtrip = all(
            np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
            zip(jax.tree.leaves(state.params) +
                jax.tree.leaves(state.comm_state),
                jax.tree.leaves(back.params) +
                jax.tree.leaves(back.comm_state)))
    RECORDS.append({
        "name": "elastic/reshape_roundtrip", "us": grow_s * 1e6,
        "old_learners": old_topo.n_learners,
        "new_learners": new_topo.n_learners,
        "survivors_bit_preserved": bool(survivors_ok),
        "ef_remapped": bool(ef_ok),
        "joiner_err_zeroed": bool(err_zeroed),
        "roundtrip_exact": bool(roundtrip), "smoke": smoke,
    })
    return ("elastic/reshape_roundtrip", grow_s * 1e6,
            f"survivors={survivors_ok} ef={ef_ok} "
            f"err_zeroed={err_zeroed} roundtrip={roundtrip}")


def _determinism_row(smoke: bool) -> Row:
    refuse_on_tpu("benchmarks/bench_elastic.py")
    fs = FaultSchedule(DET_SPEC, TOPO, ("local", "pod", "global"),
                       seed=11, deadlines=DET_DEADLINES)
    here = hashlib.sha256(
        b"".join(fs.active(r).tobytes() for r in range(8))).hexdigest()
    child = (
        "import hashlib, json\n"
        "from repro.core import HierTopology\n"
        "from repro.elastic import FaultSchedule\n"
        "fs = FaultSchedule(%r, HierTopology(2, 2, 2),\n"
        "                   ('local', 'pod', 'global'), seed=11,\n"
        "                   deadlines=%r)\n"
        "h = hashlib.sha256(\n"
        "    b''.join(fs.active(r).tobytes() for r in range(8)))\n"
        "print(json.dumps({'sha': h.hexdigest()}))\n"
        % (DET_SPEC, DET_DEADLINES))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(_REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=300)
    sha = (json.loads(r.stdout.strip().splitlines()[-1])["sha"]
           if r.returncode == 0 else None)
    match = bool(sha == here)
    RECORDS.append({
        "name": "elastic/fault_determinism", "us": 0.0,
        "spec": DET_SPEC, "seed": 11, "rounds_hashed": 8,
        "inprocess_sha": here, "subprocess_sha": sha,
        "match": match, "smoke": smoke,
    })
    return ("elastic/fault_determinism", 0.0,
            f"match={match} sha={here[:12]}")


def run(smoke: bool = False) -> List[Row]:
    RECORDS.clear()
    setup = cls_setup(in_dim=16, n_classes=4, hidden=(32,), noise=0.5,
                      seed=11)
    rounds = 4 if smoke else 12
    rows = _dropout_rows(setup, rounds, smoke)
    rows.append(_overhead_row(setup, 3 if smoke else 6, smoke))
    rows.append(_reshape_row(setup, smoke))
    rows.append(_determinism_row(smoke))
    return rows


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    print("name,us_per_call,derived")
    for n, us, derived in run(smoke=smoke):
        print(f"{n},{us:.0f},{derived}")
    with open(os.path.join(
            _REPO, "BENCH_elastic.smoke.json" if smoke
            else "BENCH_elastic.json"), "w") as f:
        json.dump(RECORDS, f, indent=2)
