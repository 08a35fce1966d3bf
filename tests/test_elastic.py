"""Elastic membership (repro/elastic), end to end.

The acceptance surface of participation-masked reductions: the masked
grouped mean must be bit-identical to the dense one at full
participation (serial, pipelined, and — in a forced-device subprocess —
fsdp=2 sharded engines), degenerate masks must degrade gracefully
(single survivor = that survivor's params, all-absent = identity, never
NaN), an absent learner's EF carry must survive a missed fire
bit-exactly, fault schedules must be pure functions of (seed, unit,
round) across processes, and a checkpointed fleet reshape must
bit-preserve survivors while remapping (or loudly dropping) reducer
state.  The n_eff expected-cost billing must collapse to the dense bill
at drop_prob=0.
"""
import hashlib
import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HierAvgParams
from repro.core import (HierTopology, Simulator, init_state,
                        make_hier_round, make_sgd_step, where_active)
from repro.core.plan import resolve_plan
from repro.core.theory import (CommModel, effective_participants,
                               param_template, plan_comm_per_round)
from repro.core.topology import (GLOBAL_ARRAY_AXES, POD_ARRAY_AXES,
                                 average_over)
from repro.elastic import (CommStateDropWarning, FaultSchedule,
                           checkpoint_topology, elastic_restore,
                           learner_index_map, parse_faults,
                           reshape_comm_state, save_elastic_checkpoint)
from repro.optim import sgd

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _assert_trees_equal(a, b, what=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=what)


def _stacked_leaves(tree, topo):
    """Leaves carrying the full [pods, G, S] stacked lead (skips PRNG
    keys and scalars)."""
    return [x for x in jax.tree.leaves(tree)
            if x.ndim >= 3 and tuple(x.shape[:3]) == topo.shape]


# --------------------------------------------------------------------- #
# masked grouped mean
# --------------------------------------------------------------------- #

def test_masked_mean_full_participation_bit_identical():
    """mask=all-ones must be bit-for-bit the dense mean at every level."""
    topo = HierTopology(2, 2, 2)
    key = jax.random.PRNGKey(0)
    tree = {"w": jax.random.normal(key, topo.shape + (5, 3)),
            "b": jax.random.normal(jax.random.split(key)[0],
                                   topo.shape + (7,))}
    ones = jnp.ones(topo.shape, bool)
    for axes in ((2,), POD_ARRAY_AXES, GLOBAL_ARRAY_AXES):
        _assert_trees_equal(average_over(tree, axes, mask=ones),
                            average_over(tree, axes), what=str(axes))


def test_masked_mean_renormalizes_over_survivors():
    """Absent learners get weight 0; the mean renormalizes over the
    survivor count — matches the numpy oracle exactly."""
    topo = HierTopology(1, 2, 2)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                     topo.shape + (6,)))
    m = np.ones(topo.shape, bool)
    m[0, 0, 0] = False
    got = average_over({"x": jnp.asarray(x)}, GLOBAL_ARRAY_AXES,
                       mask=jnp.asarray(m))["x"]
    w = m.astype(x.dtype).reshape(topo.shape + (1,))
    want = np.broadcast_to((x * w).sum((0, 1, 2), keepdims=True) / w.sum(),
                           x.shape)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_masked_mean_single_survivor_group():
    """A group reduced to one survivor averages to exactly that
    survivor's values (no drift from the renormalization)."""
    topo = HierTopology(2, 2, 2)
    x = jax.random.normal(jax.random.PRNGKey(2), topo.shape + (4,))
    m = np.zeros(topo.shape, bool)
    m[0, 1, 0] = True          # pod 0: single survivor
    m[1] = True                # pod 1: fully active
    got = average_over({"x": x}, POD_ARRAY_AXES, mask=jnp.asarray(m))["x"]
    want0 = np.broadcast_to(np.asarray(x)[0, 1, 0], (2, 2, 4))
    np.testing.assert_array_equal(np.asarray(got)[0], want0)
    want1 = np.broadcast_to(np.asarray(x)[1].mean((0, 1)), (2, 2, 4))
    np.testing.assert_allclose(np.asarray(got)[1], want1, rtol=1e-6)


def test_masked_mean_all_absent_is_finite_and_where_active_keeps_old():
    """All-absent group: the masked mean degrades to zeros (max(count,1)
    guard — never NaN) and the where_active select keeps the old tree
    bit-exactly, so the reduction is an identity."""
    topo = HierTopology(1, 2, 2)
    old = {"x": jax.random.normal(jax.random.PRNGKey(3), topo.shape + (4,))}
    zeros = jnp.zeros(topo.shape, bool)
    avg = average_over(old, GLOBAL_ARRAY_AXES, mask=zeros)
    assert np.all(np.isfinite(np.asarray(avg["x"])))
    assert np.all(np.asarray(avg["x"]) == 0.0)
    _assert_trees_equal(where_active(zeros, avg, old), old)


def test_where_active_codec_view_and_global_leaves():
    """Leaf alignment: [pods, G, S*F] codec-view leaves repeat each
    learner's bit over its F shard rows; non-stacked leaves (PRNG keys)
    always take new."""
    topo = HierTopology(1, 2, 2)
    m = np.ones(topo.shape, bool)
    m[0, 0, 1] = False
    new = {"ef": jnp.arange(24, dtype=jnp.float32).reshape(1, 2, 4, 3),
           "key": jnp.array([1, 2], jnp.uint32)}
    old = {"ef": jnp.zeros((1, 2, 4, 3)), "key": jnp.array([9, 9],
                                                          jnp.uint32)}
    out = where_active(jnp.asarray(m), new, old)
    got = np.asarray(out["ef"])
    # learner (0,0,1) owns shard rows 2:4 of group 0 — restored to old
    np.testing.assert_array_equal(got[0, 0, 2:4], 0.0)
    np.testing.assert_array_equal(got[0, 0, 0:2],
                                  np.asarray(new["ef"])[0, 0, 0:2])
    np.testing.assert_array_equal(got[0, 1], np.asarray(new["ef"])[0, 1])
    np.testing.assert_array_equal(np.asarray(out["key"]),
                                  np.asarray(new["key"]))


# --------------------------------------------------------------------- #
# elastic round program
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("overlap", [False, True],
                         ids=["serial", "pipelined"])
def test_elastic_full_participation_bit_identical(cls_task, overlap):
    """A fault schedule that never fires (flaky p=0) must train
    bit-identically to the dense round program — losses AND final params
    — on both the serial and the pipelined bucket engines (small
    bucket_bytes forces a real multi-bucket schedule).  The pipelined
    engine runs two learners: a two-term mean has one association, so
    the masked and dense sums agree bit for bit, where over four XLA may
    associate the two scanned programs' sums differently."""
    topo = HierTopology(1, 1, 2) if overlap else HierTopology(1, 2, 2)
    hier = HierAvgParams(plan="local@2/global@4:topk:0.25",
                         bucket_bytes=2048, overlap=overlap)
    runs = {}
    for name, faults in [("dense", None), ("masked", "flaky:0.0")]:
        sim = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                        cls_task["sample"], topo=topo, hier=hier,
                        optimizer=sgd(0.05), seed=7,
                        per_learner_batch=8, faults=faults)
        runs[name] = sim.run(3)
    np.testing.assert_array_equal(runs["dense"].losses,
                                  runs["masked"].losses)
    _assert_trees_equal(runs["dense"].state.params,
                        runs["masked"].state.params)
    _assert_trees_equal(runs["dense"].state.comm_state,
                        runs["masked"].state.comm_state)
    assert np.all(runs["masked"].active_fracs == 1.0)
    assert runs["masked"].round_wall_s is not None
    assert runs["dense"].active_fracs is None


def test_all_absent_round_is_pure_local_sgd(cls_task):
    """An all-false mask turns the round into per-learner SGD: identical
    to scanning make_sgd_step with no reduction at all, and the metrics
    report active_frac 0."""
    topo = HierTopology(1, 2, 2)
    hier = HierAvgParams(plan="global@2:mean")
    opt = sgd(0.05)
    key = jax.random.PRNGKey(4)
    rnd = jax.jit(make_hier_round(cls_task["loss_fn"], opt, hier,
                                  elastic=True))
    batch = cls_task["sample"](jax.random.PRNGKey(5),
                               2 * topo.n_learners * 8)
    shaped = jax.tree.map(
        lambda x: x.reshape((2,) + topo.shape + (8,) + x.shape[1:]), batch)
    state = init_state(topo, cls_task["init_fn"], opt, key,
                       plan=resolve_plan(hier))
    none_active = jnp.zeros((1,) + topo.shape, bool)
    out, metrics = rnd(state, shaped, none_active)
    assert float(metrics["active_frac/global"]) == 0.0

    step = jax.jit(make_sgd_step(cls_task["loss_fn"], opt))
    ref = init_state(topo, cls_task["init_fn"], opt, key,
                     plan=resolve_plan(hier))
    for t in range(2):
        ref, _ = step(ref, jax.tree.map(lambda x: x[t], shaped))
    _assert_trees_equal(out.params, ref.params, "all-absent != pure SGD")
    for leaf in jax.tree.leaves(out.params):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_ef_bit_preserved_across_missed_fire(cls_task):
    """An absent learner's error-feedback carry must come out of the
    round bit-identical to how it went in (it neither contributed to nor
    observed the reduction), while present learners' EF advances."""
    topo = HierTopology(1, 2, 2)
    hier = HierAvgParams(plan="global@2:topk:0.25")
    opt = sgd(0.05)
    rnd = jax.jit(make_hier_round(cls_task["loss_fn"], opt, hier,
                                  elastic=True))
    state = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(6), plan=resolve_plan(hier))
    batch = cls_task["sample"](jax.random.PRNGKey(7),
                               2 * topo.n_learners * 8)
    shaped = jax.tree.map(
        lambda x: x.reshape((2,) + topo.shape + (8,) + x.shape[1:]), batch)
    active = np.ones((1,) + topo.shape, bool)
    active[0, 0, 0, 0] = False
    before = _stacked_leaves(state.comm_state, topo)
    assert before, "topk plan should carry stacked EF state"
    before = [np.asarray(x) for x in before]
    out, _ = rnd(state, shaped, jnp.asarray(active))
    after = _stacked_leaves(out.comm_state, topo)
    changed = False
    for b, a in zip(before, after):
        a = np.asarray(a)
        np.testing.assert_array_equal(
            a[0, 0, 0], b[0, 0, 0],
            err_msg="absent learner's EF touched across a missed fire")
        changed = changed or not np.array_equal(a[0, 0, 1], b[0, 0, 1])
    assert changed, "present learners' EF should advance"
    # the absent learner's params kept its own local-SGD trajectory:
    # distinct from the survivors' averaged params
    p = np.asarray(jax.tree.leaves(out.params)[0])
    assert not np.array_equal(p[0, 0, 0], p[0, 0, 1])
    np.testing.assert_array_equal(p[0, 0, 1], p[0, 1, 1])


# --------------------------------------------------------------------- #
# fault schedules
# --------------------------------------------------------------------- #

def test_fault_schedule_deterministic_and_order_free():
    topo = HierTopology(2, 2, 2)
    levels = ("local", "pod", "global")
    spec = "crash:0.1/flaky:pod:0.3:2/straggler:0.5:1.0"
    dl = {"local": 0.5, "pod": 1.0, "global": 2.0}
    a = FaultSchedule(spec, topo, levels, seed=3, deadlines=dl)
    b = FaultSchedule(spec, topo, levels, seed=3, deadlines=dl)
    for r in (5, 0, 3, 5, 1):           # out of order, repeated
        np.testing.assert_array_equal(a.active(r), b.active(r))
    assert a.describe() == b.describe()
    assert parse_faults(a.describe()) == a.clauses
    # a different seed moves the pattern
    c = FaultSchedule(spec, topo, levels, seed=4, deadlines=dl)
    assert any(not np.array_equal(a.active(r), c.active(r))
               for r in range(8))


def test_fault_schedule_crash_is_permanent():
    topo = HierTopology(1, 2, 2)
    fs = FaultSchedule("crash:0.3", topo, ("global",), seed=5)
    masks = np.stack([fs.active(r)[0].reshape(-1) for r in range(20)])
    for j in range(topo.n_learners):
        down = np.where(~masks[:, j])[0]
        if down.size:
            assert not masks[down[0]:, j].any(), "crashed learner rejoined"
    assert not masks[-1].all(), "p=0.3 over 20 rounds should crash someone"


def test_fault_schedule_flaky_granularity_and_down_window():
    topo = HierTopology(2, 2, 2)
    pod = FaultSchedule("flaky:pod:0.5", topo, ("global",), seed=1)
    hit = False
    for r in range(8):
        m = pod.active(r)[0]
        # whole pods flap together
        assert all(len(set(m[p].reshape(-1).tolist())) == 1
                   for p in range(2))
        hit = hit or not m.all()
    assert hit
    # a longer outage window only removes participation, on the same
    # underlying hit stream
    short = FaultSchedule("flaky:0.4:1", topo, ("global",), seed=2)
    long = FaultSchedule("flaky:0.4:3", topo, ("global",), seed=2)
    s = np.stack([short.active(r) for r in range(10)])
    l = np.stack([long.active(r) for r in range(10)])
    assert np.all(l <= s)
    assert l.sum() < s.sum()


def test_fault_schedule_level_restriction_and_straggler_deadlines():
    topo = HierTopology(1, 2, 2)
    levels = ("local", "global")
    fs = FaultSchedule("flaky:1.0@global", topo, levels, seed=0)
    m = fs.active(0)
    assert m[0].all() and not m[1].any()
    with pytest.raises(ValueError, match="names level"):
        FaultSchedule("crash:0.1@nosuch", topo, levels, seed=0)
    # stragglers miss every level whose deadline their delay exceeds:
    # the cheap level's survivor set nests inside the expensive level's
    fs = FaultSchedule("straggler:1.0:1.0", topo, levels, seed=9,
                       deadlines={"local": 0.05, "global": 50.0})
    masks = np.stack([fs.active(r) for r in range(6)])
    assert np.all(masks[:, 0] <= masks[:, 1])
    assert masks[:, 0].sum() < masks[:, 1].sum()
    # p=0 never masks anyone
    calm = FaultSchedule("straggler:0.0", topo, levels, seed=9)
    assert calm.active(0).all()


def test_fault_spec_grammar_errors():
    for bad in ("bogus:0.5", "crash:1.5", "crash:-0.1", "crash",
                "flaky:0.2:0", "flaky:tower:0.2", "", "straggler"):
        with pytest.raises(ValueError):
            parse_faults(bad)


def test_fault_schedule_deterministic_across_processes():
    """Satellite (f): the mask stream is reconstructable from
    (spec, seed, round) alone — a fresh process produces the identical
    masks (the bench A/B subprocess legs rely on this)."""
    spec = "crash:0.1/flaky:pod:0.3:2/straggler:0.5:1.0"
    dl = {"local": 0.5, "global": 2.0}
    topo = HierTopology(2, 2, 2)
    fs = FaultSchedule(spec, topo, ("local", "global"), seed=11,
                       deadlines=dl)
    here = hashlib.sha256(
        b"".join(fs.active(r).tobytes() for r in range(6))).hexdigest()
    child = (
        "import hashlib, json, sys\n"
        "from repro.core import HierTopology\n"
        "from repro.elastic import FaultSchedule\n"
        "fs = FaultSchedule(%r, HierTopology(2, 2, 2),\n"
        "                   ('local', 'global'), seed=11, deadlines=%r)\n"
        "h = hashlib.sha256(\n"
        "    b''.join(fs.active(r).tobytes() for r in range(6)))\n"
        "print(json.dumps({'sha': h.hexdigest()}))\n" % (spec, dl))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(_REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout.strip().splitlines()[-1])["sha"] == here


# --------------------------------------------------------------------- #
# fleet reshape
# --------------------------------------------------------------------- #

def test_learner_index_map():
    old, new = HierTopology(1, 2, 2), HierTopology(1, 3, 2)
    src, joiner = learner_index_map(old, new)
    np.testing.assert_array_equal(src, [0, 1, 2, 3, 0, 0])
    np.testing.assert_array_equal(joiner, [False] * 4 + [True] * 2)
    src, joiner = learner_index_map(new, old)       # shrink
    np.testing.assert_array_equal(src, [0, 1, 2, 3])
    assert not joiner.any()
    src, _ = learner_index_map(old, new, survivors=[3, 1], donor=3)
    np.testing.assert_array_equal(src, [3, 1, 3, 3, 3, 3])
    for bad in ({"survivors": [0, 0]}, {"survivors": [7]},
                {"survivors": list(range(5))}, {"survivors": []}):
        with pytest.raises(ValueError):
            learner_index_map(old, HierTopology(1, 2, 2), **bad)


def test_checkpointed_reshape_roundtrip_bit_preserves(cls_task, tmp_path):
    """Grow 4 -> 6 learners, then shrink back: survivors' params and
    bucket-space EF are bit-preserved both ways, joiners clone the donor
    with a ZEROED error residual, and the round-trip is exact."""
    old_topo, new_topo = HierTopology(1, 2, 2), HierTopology(1, 3, 2)
    hier = HierAvgParams(plan="global@2:topk:0.25")
    sim = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                    cls_task["sample"], topo=old_topo, hier=hier,
                    optimizer=sgd(0.05), seed=13, per_learner_batch=8)
    state = sim.run(2).state
    d4 = str(tmp_path / "fleet4")
    save_elastic_checkpoint(d4, state, old_topo, step=2, plan=sim.plan)
    assert checkpoint_topology(d4) == old_topo

    like6 = init_state(new_topo, cls_task["init_fn"], sgd(0.05),
                       jax.random.PRNGKey(99), plan=resolve_plan(hier))
    got6 = elastic_restore(d4, like6, new_topo=new_topo)
    for old_leaf, new_leaf in zip(_stacked_leaves(state.params, old_topo),
                                  _stacked_leaves(got6.params, new_topo)):
        o = np.asarray(old_leaf).reshape((-1,) + old_leaf.shape[3:])
        n = np.asarray(new_leaf).reshape((-1,) + new_leaf.shape[3:])
        np.testing.assert_array_equal(n[:4], o, "survivors not preserved")
        np.testing.assert_array_equal(n[4], o[0], "joiner != donor clone")
    # joiners' EF residual is zeroed (a cloned residual would double-count
    # the donor's untransmitted mass); survivors' EF is bit-preserved
    err6 = _stacked_leaves(got6.comm_state["global"].err, new_topo)
    err4 = _stacked_leaves(state.comm_state["global"].err, old_topo)
    for e6, e4 in zip(err6, err4):
        e6 = np.asarray(e6).reshape((-1,) + e6.shape[3:])
        np.testing.assert_array_equal(
            e6[:4], np.asarray(e4).reshape((-1,) + e4.shape[3:]))
        np.testing.assert_array_equal(e6[4:], 0.0)

    d6 = str(tmp_path / "fleet6")
    save_elastic_checkpoint(d6, got6, new_topo, step=2, plan=sim.plan)
    like4 = init_state(old_topo, cls_task["init_fn"], sgd(0.05),
                       jax.random.PRNGKey(98), plan=resolve_plan(hier))
    back = elastic_restore(d6, like4, new_topo=old_topo)
    _assert_trees_equal(back.params, state.params, "round-trip params")
    _assert_trees_equal(back.comm_state, state.comm_state,
                        "round-trip comm_state")


def test_reshape_drops_codec_view_state_with_warning():
    """Shard-space (codec-view) reducer state is not lead-invariant —
    the reshape must refuse to guess, warn loudly, and drop it."""
    from repro.comm.sparse import EFState
    old_topo, new_topo = HierTopology(1, 2, 2), HierTopology(1, 3, 2)
    cs = {"global": EFState(
        ref=[jnp.ones((1, 2, 4, 7))],        # S*F = 4 != S = 2: codec view
        err=[jnp.zeros((1, 2, 4, 7))],
        key=jax.random.PRNGKey(0))}
    src, joiner = learner_index_map(old_topo, new_topo)
    with pytest.warns(CommStateDropWarning, match="global"):
        out = reshape_comm_state(cs, old_topo, new_topo, src, joiner)
    assert out["global"] == ()


def test_restore_learner_count_mismatch_diagnostic(cls_task, tmp_path):
    """Satellite (a): plain restore_checkpoint onto a different fleet
    size must fail with a diagnostic naming the learner grids and both
    counts and pointing at elastic_restore."""
    from repro.checkpoint import restore_checkpoint
    topo = HierTopology(1, 2, 2)
    state = init_state(topo, cls_task["init_fn"], sgd(0.05),
                       jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    save_elastic_checkpoint(d, state, topo)
    like = init_state(HierTopology(1, 3, 2), cls_task["init_fn"],
                      sgd(0.05), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="learner-count mismatch") as ei:
        restore_checkpoint(d, like)
    msg = str(ei.value)
    assert "(1, 2, 2)" in msg and "(1, 3, 2)" in msg
    assert "4 learners" in msg and "6" in msg
    assert "elastic_restore" in msg


# --------------------------------------------------------------------- #
# expected-cost billing (n_eff)
# --------------------------------------------------------------------- #

def test_effective_participants():
    assert effective_participants(8, 0.0) == 8.0
    assert effective_participants(8, 1.0) == 1.0
    assert effective_participants(1, 0.7) == 1.0
    vals = [effective_participants(8, p) for p in (0.0, 0.2, 0.5, 1.0)]
    assert vals == sorted(vals, reverse=True)
    assert effective_participants(8, -0.5) == 8.0   # clamped
    assert effective_participants(8, 2.0) == 1.0


def test_plan_comm_drop_prob_billing():
    from repro.core.plan import ReductionPlan
    plan = ReductionPlan.parse("local@2/global@8")
    topo = HierTopology(2, 2, 2)
    template = param_template(1 << 16, n_leaves=4)
    cm = CommModel()
    dense = plan_comm_per_round(plan, topo, template, cm)
    same = plan_comm_per_round(plan, topo, template, cm, drop_prob=0.0)
    for a, b in zip(dense, same):       # p=0 bills identically to dense
        assert a.seconds_per_round == b.seconds_per_round
        assert a.overlap_s == b.overlap_s
        assert b.n_eff == b.participants
    lossy = plan_comm_per_round(plan, topo, template, cm, drop_prob=0.3)
    for a, b in zip(dense, lossy):
        assert b.drop_prob == 0.3
        assert 1.0 < b.n_eff < b.participants
        assert b.seconds_per_round < a.seconds_per_round
    # per-level dict: only the named tier is billed under dropout
    mixed = plan_comm_per_round(plan, topo, template, cm,
                                drop_prob={"global": 0.5})
    assert mixed[0].drop_prob == 0.0
    assert mixed[0].seconds_per_round == dense[0].seconds_per_round
    assert mixed[1].drop_prob == 0.5
    assert mixed[1].seconds_per_round < dense[1].seconds_per_round
    # p=1: only the (expected) lone survivor remains -> zero comm wire
    alone = plan_comm_per_round(plan, topo, template, cm, drop_prob=1.0)
    assert all(c.seconds_per_round == 0.0 for c in alone)


def test_search_and_controller_take_drop_prob():
    from repro.autotune.controller import CostAwarePlan
    from repro.autotune.search import search_plans
    topo = HierTopology(2, 2, 2)
    template = param_template(1 << 16, n_leaves=4)
    dense = search_plans(topo, template=template)
    lossy = search_plans(topo, template=template, drop_prob=0.5)
    assert {s.spec for s in dense} == {s.spec for s in lossy}
    by_spec = {s.spec: s for s in dense}
    assert all(s.comm_s_per_step <= by_spec[s.spec].comm_s_per_step
               for s in lossy)
    assert any(s.comm_s_per_step < by_spec[s.spec].comm_s_per_step
               for s in lossy)
    ctl_d = CostAwarePlan("local@2/pod@4/global@8", topo,
                          template=template)
    ctl_l = CostAwarePlan("local@2/pod@4/global@8", topo,
                          template=template, drop_prob={"global": 0.5})
    assert ctl_l.level_costs[:2] == ctl_d.level_costs[:2]
    assert ctl_l.level_costs[2] < ctl_d.level_costs[2]
    assert ctl_l.periods_for(10.0)      # still produces a valid lattice


# --------------------------------------------------------------------- #
# the headline: dropout convergence within the theory bars
# --------------------------------------------------------------------- #

@pytest.mark.slow
def test_pod_dropout_within_thm32_bars(cls_task):
    """The PR's headline claim: a 3-level fleet with 20% pod-level
    dropout converges within the Thm 3.2 bound bar of the fault-free
    run (bar priced at the dropout run's effective participant count)."""
    from repro.core.theory import thm32_bound, thm32_condition
    topo = HierTopology(2, 2, 2)
    res = {}
    for name, faults in [("faultfree", None), ("dropout20",
                                               "flaky:pod:0.2")]:
        sim = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                        cls_task["sample"], topo=topo,
                        hier=HierAvgParams(k1=2, k2=8,
                                           plan="local@2/pod@4/global@8"),
                        optimizer=sgd(0.05), seed=3, per_learner_batch=16,
                        eval_batch=cls_task["eval_batch"], faults=faults)
        res[name] = sim.run(4)
    dp = res["dropout20"]
    assert dp.active_fracs is not None and dp.active_fracs.shape == (4, 3)
    assert 0.0 < dp.active_fracs.mean() < 1.0, "20% dropout never fired"
    assert dp.round_wall_s is not None and np.all(dp.round_wall_s > 0)
    F1, L, M, gamma, P, B, N = 2.0, 1.0, 1.0, 0.05, 8, 16, 4
    assert thm32_condition(L, gamma, K2=8)
    bar = thm32_bound(F1, L, M, gamma, K1=2, K2=8, S=2,
                      P=effective_participants(P, 0.2), B=B, N=N)
    for name in res:
        losses = res[name].eval_losses
        assert losses[-1] < 0.65 * losses[0], (name, losses)
    gap = abs(dp.eval_losses[-1] - res["faultfree"].eval_losses[-1])
    assert gap <= bar, (gap, bar)
    assert gap <= 0.05, f"empirical dropout gap blew up: {gap}"


# --------------------------------------------------------------------- #
# fsdp=2 sharded engine (forced-device subprocess, as tests/test_sharded)
# --------------------------------------------------------------------- #

_SHARDED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.comm import reduce_with
from repro.core.topology import GLOBAL_ARRAY_AXES, average_over
from repro.testing import (AB_SMALL_CAP, build_sharded_ab_reduction,
                           count_collective_ops)

b = build_sharded_ab_reduction("serial", AB_SMALL_CAP, spec="mean")
p = jax.device_put(b["params"], b["shardings"][0])
s = jax.device_put(b["state"], b["shardings"][1])
topo_shape = (1, 2, 2)
out = {}

def masked_fn(mask):
    return jax.jit(lambda pp, ss: reduce_with(
        b["reducer"],
        lambda t, cf=None, specs=None: average_over(
            t, GLOBAL_ARRAY_AXES, cf, specs, mask),
        pp, ss), in_shardings=b["shardings"])

# full participation: bit-identical to the dense sharded reduction, and
# the masked lowering stays pure reduce-scatter/all-gather
fn_full = masked_fn(jnp.ones(topo_shape, bool))
got_full, _ = fn_full(p, s)
got_dense, _ = b["fn"](p, s)
out["full_maxdiff"] = max(
    float(jnp.max(jnp.abs(a - c))) for a, c in
    zip(jax.tree.leaves(got_full), jax.tree.leaves(got_dense)))
out["collectives"] = count_collective_ops(
    fn_full.lower(p, s).compile().as_text())

# partial participation matches the replicated masked-mean oracle
m = np.ones(topo_shape, bool); m[0, 0, 0] = False
got_part, _ = masked_fn(jnp.asarray(m))(p, s)
w = m.astype(np.float32).reshape(topo_shape + (1, 1))
md = 0.0
for a, x in zip(jax.tree.leaves(got_part), jax.tree.leaves(b["params"])):
    x = np.asarray(x)
    want = (x * w).sum(axis=(0, 1, 2), keepdims=True) / w.sum()
    md = max(md, float(np.max(np.abs(
        np.asarray(a) - np.broadcast_to(want, x.shape)))))
out["partial_maxdiff"] = md
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_masked_reduction_subprocess():
    """fsdp=2: the participation mask is applied in wire space, so the
    shard-aware bucket path keeps its reduce-scatter/all-gather lowering
    and its numerics — full-mask bit-identical to dense, partial mask
    equal to the replicated oracle."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(_REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _SHARDED_CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["full_maxdiff"] == 0.0
    assert out["partial_maxdiff"] == 0.0
    assert out["collectives"]["all_reduce"] == 0
    assert out["collectives"]["reduce_scatter"] > 0
    assert out["collectives"]["all_gather"] > 0
