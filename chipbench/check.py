"""Whether a run's timed path trained correctly: the program's first
rounds against the plain reference's, at the timed sizes.

Three numbers, each against its limit from ``limits/<cell>.json``:

* ``loss_gap``: the largest relative gap between the program's mean loss
  of a round and the reference's, over the rounds the reference follows;
* ``update_gap``: of learner 0's change over round 0, the worst leaf's
  gap between the program's norm and the reference's, measured against
  the reference's norm of that leaf or of the median leaf, whichever is
  larger;
* ``change_gap``: the same for the change over all the rounds followed.

Leaves whose first gradient in the reference is under a thousandth of
the median leaf's are left out of the two gaps of norms: round-off
alone moves them.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

NUMBERS = ("loss_gap", "update_gap", "change_gap")
GRAD_FLOOR = 1e-3     # of the median leaf's first-gradient norm
# Rounds the reference follows; every limit was read at this number.
CHECK_ROUNDS = 3


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: List[str]) -> Tuple[float, str]:
    """Worst leaf's gap of norms and the leaf."""
    floor = float(np.median([ref[k] for k in keep]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in keep}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def kept_leaves(first_grad: Dict[str, float]) -> List[str]:
    med = float(np.median(list(first_grad.values())))
    return sorted(k for k, v in first_grad.items() if v >= GRAD_FLOOR * med)


def reference_readings(cfg, traffic, seed: int, *, devices,
                       trainer_kw=None) -> Dict:
    """Run the reference's first :data:`CHECK_ROUNDS` rounds from
    ``seed``: mean
    losses, learner 0's change norms after round 0 and after the last,
    and learner 0's first-gradient norms."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from chipbench import reference as R
    job = R.job_from(traffic)
    sharding = None
    if len(devices) > 1:
        mesh = Mesh(np.array(devices[:job.learners]), ("learner",))
        sharding = NamedSharding(mesh, P("learner"))
    with jax.default_matmul_precision("highest"), \
            jax.default_device(devices[0]):
        p0 = jax.jit(lambda: R.init_params(cfg, seed))()
        trainer = R.Trainer(cfg, job, sharding=sharding,
                            **(trainer_kw or {}))
        params = trainer.start(p0)
        p0 = jax.tree.map(lambda x: x.astype(trainer.dtype), p0)
        change = jax.jit(lambda p, q: R.leaf_norms(jax.tree.map(
            lambda a, b: a[0].astype("float32") - b.astype("float32"),
            p, q)))
        losses, changes, first = [], {}, None
        for r in range(CHECK_ROUNDS):
            rows = R.round_rows(cfg, job, seed, r)
            params, loss, grads = trainer.run_round(params, rows)
            losses.append(float(loss))
            if first is None:
                first = {k: float(v) for k, v in grads.items()}
            if r in (0, CHECK_ROUNDS - 1):
                changes[r] = {k: float(v)
                              for k, v in change(params, p0).items()}
    return {"losses": losses, "update": changes[0],
            "change": changes[CHECK_ROUNDS - 1], "first_grad": first}


def compare(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """The compared numbers, each with the leaf or round it comes from."""
    rounds = len(ref["losses"])
    loss_gaps = [abs(prog["losses"][r] - ref["losses"][r])
                 / abs(ref["losses"][r]) for r in range(rounds)]
    keep = kept_leaves(ref["first_grad"])
    upd, upd_leaf = norm_gap(prog["update"], ref["update"], keep)
    chg, chg_leaf = norm_gap(prog["change"], ref["change"], keep)
    return {"loss_gap": {"value": max(loss_gaps),
                         "at": f"round {int(np.argmax(loss_gaps))}"},
            "update_gap": {"value": upd, "at": upd_leaf},
            "change_gap": {"value": chg, "at": chg_leaf},
            "left_out": sorted(set(ref["first_grad"]) - set(keep))}


def program_readings(window) -> Dict:
    """The program's side, from what the window's hook read."""
    return {"losses": [window.losses[r] for r in range(CHECK_ROUNDS)],
            "update": window.update, "change": window.change}


def verdict(numbers: Dict[str, Dict], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict]]:
    """Each number beside its limit, and whether all are within."""
    shown = {k: {"value": numbers[k]["value"], "limit": limits[k]}
             for k in NUMBERS}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in shown.values())
    return ok, shown
