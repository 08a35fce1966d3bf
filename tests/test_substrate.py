"""Substrate tests: optimizers, schedules, data pipeline, checkpointing,
partition rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs.base import HierAvgParams
from repro.core import HierTopology
from repro.data.loader import HierDataLoader
from repro.data.synthetic import (make_classification_task, make_markov_task,
                                  markov_lm_batch)
from repro.optim import (adamw, clip_by_global_norm, constant_lr, cosine_lr,
                         global_norm, sgd, step_decay_lr)
from repro.parallel.sharding import PartitionRules, safe_pspec
from jax.sharding import AbstractMesh, PartitionSpec as P


# ------------------------------ optim -------------------------------- #

def test_sgd_plain_matches_manual():
    opt = sgd(0.1)
    params = {"w": jnp.array([1.0, 2.0])}
    grads = {"w": jnp.array([0.5, -1.0])}
    st = opt.init(params)
    new, _ = opt.update(grads, params, st, jnp.zeros((), jnp.int32))
    np.testing.assert_allclose(np.asarray(new["w"]), [0.95, 2.1], rtol=1e-6)


def test_sgd_momentum():
    opt = sgd(0.1, momentum=0.9)
    params = {"w": jnp.zeros(2)}
    grads = {"w": jnp.ones(2)}
    st = opt.init(params)
    p1, st = opt.update(grads, params, st, jnp.zeros((), jnp.int32))
    p2, st = opt.update(grads, p1, st, jnp.ones((), jnp.int32))
    # v1 = 1, p1 = -0.1 ; v2 = 1.9, p2 = -0.1 - 0.19
    np.testing.assert_allclose(np.asarray(p2["w"]), [-0.29, -0.29],
                               rtol=1e-6)


def test_adamw_converges_quadratic():
    opt = adamw(0.1)
    params = {"w": jnp.array([5.0])}
    st = opt.init(params)
    step = jnp.zeros((), jnp.int32)
    for i in range(200):
        g = {"w": 2 * params["w"]}
        params, st = opt.update(g, params, st, step + i)
    assert abs(float(params["w"][0])) < 0.1


def test_schedules():
    f = step_decay_lr(0.1, [150], [0.1])   # the paper's recipe
    assert float(f(0)) == pytest.approx(0.1)
    assert float(f(151)) == pytest.approx(0.01)
    c = cosine_lr(1.0, 100)
    assert float(c(0)) == pytest.approx(1.0)
    assert float(c(100)) == pytest.approx(0.1, abs=1e-6)


def test_clip_by_global_norm():
    tree = {"a": jnp.ones(4) * 3.0}
    clipped, n = clip_by_global_norm(tree, 1.0)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)
    assert float(n) == pytest.approx(6.0)


# ------------------------------ data --------------------------------- #

def test_markov_task_entropy_floor():
    logits, floor = make_markov_task(16, temperature=1.0)
    assert 0.0 < floor < np.log(16)
    b = markov_lm_batch(jax.random.PRNGKey(0), 8, 32, logits)
    assert b["tokens"].shape == (8, 32)
    assert b["labels"].shape == (8, 32)
    assert int(b["tokens"].max()) < 16


def test_loader_shapes_and_independence():
    topo = HierTopology(1, 2, 2)
    hier = HierAvgParams(k1=2, k2=4)
    sample = make_classification_task(8, 3)
    ld = HierDataLoader(sample, topo=topo, hier=hier, per_learner_batch=4,
                        seed=0)
    rb = ld.next_round()
    assert rb["x"].shape == (2, 2, 1, 2, 2, 4, 8)
    # learners see different data within the same step
    step0 = rb["x"][0, 0, 0]
    assert not np.allclose(np.asarray(step0[0, 0]), np.asarray(step0[0, 1]))
    # deterministic across loaders with the same seed
    ld2 = HierDataLoader(sample, topo=topo, hier=hier, per_learner_batch=4,
                         seed=0)
    np.testing.assert_allclose(np.asarray(rb["x"]),
                               np.asarray(ld2.next_round()["x"]))


def test_round_batch_shardings_any_plan_depth():
    """Schedule-aware shard assignment (data/loader.py) is generic in
    the plan depth: the leading step-axis prefix tracks len(batch_dims)
    for 1-, 2-, and 3-level plans — and for deeper hypothetical
    schedules — instead of a baked <=3-entry prefix."""
    from jax.sharding import PartitionSpec as P
    from repro.data.loader import (round_batch_pspec,
                                   round_batch_shardings)
    mesh = jax.make_mesh((1, 1, 1, 1), ("pod", "group", "local", "fsdp"))
    plans = {"local@4": 1, "local@2/global@4": 2,
             "local@2/pod@4/global@8": 3}
    for spec, depth in plans.items():
        hier = HierAvgParams(plan=spec)
        assert len(hier.batch_dims) == depth
        leaf_ndim = depth + 3 + 1 + 1        # steps + learners + B + feat
        ps = round_batch_pspec(hier.batch_dims, leaf_ndim, mesh)
        assert tuple(ps) == ((None,) * depth
                             + ("pod", "group", "local", "fsdp", None))
    # deeper than any named plan today: the prefix still tracks the dims
    deep_dims = (2, 2, 2, 2, 2)
    ps = round_batch_pspec(deep_dims, len(deep_dims) + 4, mesh)
    assert tuple(ps) == ((None,) * 5 + ("pod", "group", "local", "fsdp"))
    # meshes without an fsdp axis just drop the example-dim shard
    mesh3 = jax.make_mesh((1, 1, 1), ("pod", "group", "local"))
    ps3 = round_batch_pspec((2, 2), 7, mesh3)
    assert tuple(ps3) == (None, None, "pod", "group", "local", None, None)
    # non-divisible dims are dropped by the safety net, not crashed on
    ps_safe = round_batch_pspec((2,), 5, mesh3, leaf_shape=(2, 1, 1, 1, 7))
    assert isinstance(ps_safe, P)
    # a leaf too short for the step+learner prefix is refused loudly,
    # never silently mis-sharded with truncated learner axes
    with pytest.raises(ValueError):
        round_batch_pspec((2, 2), 4, mesh3)
    # end-to-end: a loader given only the mesh derives the shardings and
    # places a 3-level round batch
    topo = HierTopology(1, 1, 1)
    hier = HierAvgParams(plan="local@1/pod@2/global@4")
    ld = HierDataLoader(make_classification_task(8, 3), topo=topo,
                        hier=hier, per_learner_batch=4, seed=0, mesh=mesh)
    rb = ld.next_round()
    assert rb["x"].shape == (2, 2, 1, 1, 1, 1, 4, 8)
    assert ld.shardings is not None
    assert tuple(ld.shardings["x"].spec)[:3] == (None, None, None)
    shards = round_batch_shardings(mesh, hier, rb)
    assert shards["x"].mesh.shape == mesh.shape


# --------------------------- checkpoint ------------------------------ #

def test_checkpoint_roundtrip(tmp_path):
    tree = {"layers": {"w": jnp.arange(6.0).reshape(2, 3),
                       "b": jnp.ones(3)},
            "head": jnp.full((4,), 2.5)}
    save_checkpoint(str(tmp_path / "ck"), tree, step=7,
                    metadata={"arch": "test"})
    like = jax.tree.map(jnp.zeros_like, tree)
    restored = restore_checkpoint(str(tmp_path / "ck"), like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path / "ck"), {"w": jnp.ones(3)})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path / "ck"), {"w": jnp.ones(4)})


# ------------------------- partition rules --------------------------- #

def test_partition_rules_paths():
    r = PartitionRules()
    assert r.inner_spec("layers/attn/wq", 2) == ("fsdp", "model")
    assert r.inner_spec("layers/attn/wo", 2) == ("model", "fsdp")
    assert r.inner_spec("layers/ffn/experts/w_gate", 3) == \
        ("model", "fsdp", None)
    assert r.inner_spec("layers/cm/wv", 2) == ("model", "fsdp")
    assert r.inner_spec("layers/tm/wk", 2) == ("fsdp", "model")
    assert r.inner_spec("embed", 2) == ("model", None)


def test_spec_leading_axes_stacked():
    r = PartitionRules()
    # stacked learners + layer-stack dim + 2-D weight
    s = r.spec_for("layers/attn/wq", (1, 2, 2, 24, 64, 64),
                   stacked_learners=True)
    assert tuple(s) == ("pod", "group", "local", None, "fsdp", "model")
    s = r.spec_for("layers/attn/wq", (24, 64, 64), stacked_learners=False)
    assert tuple(s) == (None, "fsdp", "model")


def test_safe_pspec_drops_nondivisible():
    mesh = AbstractMesh((1, 1), ("data", "model"))
    # size-1 axes divide everything
    s = safe_pspec(P("data", "model"), (25, 7), mesh)
    assert tuple(s) == ("data", "model")
    mesh4 = AbstractMesh((2, 2), ("data", "model"))
    s = safe_pspec(P("data", "model"), (25, 8), mesh4)
    assert tuple(s) == (None, "model")
    # tuple axes multiply
    s = safe_pspec(P(("data", "model")), (8,), mesh4)
    assert tuple(s) == (("data", "model"),)
    s = safe_pspec(P(("data", "model")), (6,), mesh4)
    assert tuple(s) == (None,)
