"""Sharded (fsdp>1) reduction stack, end to end.

The acceptance surface of the shard-aware bucket layout: the compiled
SPMD HLO of a sharded bucket reduction must lower to reduce-scatter +
all-gather (never a full all-reduce for the buckets, and no stray
all-to-all / collective-permute from a non-shard-local reshape), the
result must be bit-identical to the per-leaf *replicated* oracle for the
lossless payloads (mean, cast), and EF state — carried in shard space
(codec view: shards merged into the local-learner axis) — must
round-trip through checkpoint save/restore back onto the mesh.

Device count must be forced before jax initializes, so everything that
needs the 8-device (4 learners x 2 shards) mesh runs in a subprocess
(same pattern as tests/test_pipeline.py).  Layout/metadata tests
(replica groups, safe_pspec non-dividing drops) run in-process.
"""
import json
import os
import subprocess
import sys
import types
import warnings

import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.parallel.sharding import (PSpecDropWarning, ShardPlan,
                                     replica_groups, resolve_pspec,
                                     safe_pspec)
from repro.testing import count_collective_ops

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import json, sys
import jax, jax.numpy as jnp
from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.comm import get_reducer, reduce_with
from repro.core.topology import global_average
# the SAME builder benchmarks/bench_bucketing.py measures for the
# sharded A/B rows — verified structure and benchmarked program agree
from repro.testing import AB_SMALL_CAP, build_sharded_ab_reduction

d = sys.argv[1]
out = {}

# compiled HLO of the sharded bucket reduction, both schedules
for sched in ("serial", "pipelined"):
    b = build_sharded_ab_reduction(sched, AB_SMALL_CAP)
    p = jax.device_put(b["params"], b["shardings"][0])
    s = jax.device_put(b["state"], b["shardings"][1])
    open(os.path.join(d, sched + ".hlo"), "w").write(
        b["fn"].lower(p, s).compile().as_text())
    out[sched + "_buckets"] = b["n_buckets"]

# bit-identity vs the per-leaf REPLICATED oracle (same reducer, no
# bucketing, no mesh) for the lossless payloads
for spec in ("mean", "cast:bfloat16"):
    b = build_sharded_ab_reduction("serial", AB_SMALL_CAP, spec=spec)
    p = jax.device_put(b["params"], b["shardings"][0])
    s = jax.device_put(b["state"], b["shardings"][1])
    got, _ = b["fn"](p, s)
    leaf_red = get_reducer(spec)
    leaf_state = leaf_red.init_state(
        jax.tree.map(jnp.zeros_like, b["params"]))
    want, _ = reduce_with(leaf_red, global_average, b["params"],
                          leaf_state)
    out["maxdiff_" + spec.split(":")[0]] = max(
        float(jnp.max(jnp.abs(g.astype(jnp.float32)
                              - w.astype(jnp.float32))))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))

# fused qint8 through the sharded RS/AG path: shard-run packing shifts
# the quantizer's block boundaries vs the per-leaf layout, so parity is
# the per-block error bound vs the dense mean, not bit-identity; the
# fused pack must still ship ONE message per bucket
b = build_sharded_ab_reduction("serial", AB_SMALL_CAP, spec="qint8:128")
p = jax.device_put(b["params"], b["shardings"][0])
s = jax.device_put(b["state"], b["shardings"][1])
got, _ = b["fn"](p, s)
dense, _ = reduce_with(get_reducer("mean"), global_average,
                       b["params"], ())
out["maxdiff_qint8"] = max(
    float(jnp.max(jnp.abs(g - w)))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(dense)))
out["absmax_qint8"] = max(
    float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(b["params"]))
out["qint8_messages"] = int(b["reducer"].n_messages(b["tree1"]))
out["qint8_buckets"] = int(b["n_buckets"])

# EF / reducer state round-trips through checkpoint in shard space
for tag, spec in (("topk", "topk:0.05"), ("qint8", "qint8")):
    b = build_sharded_ab_reduction("serial", AB_SMALL_CAP, spec=spec)
    p = jax.device_put(b["params"], b["shardings"][0])
    s = jax.device_put(b["state"], b["shardings"][1])
    _, s1 = b["fn"](p, s)
    ck = os.path.join(d, "ck_" + tag)
    save_checkpoint(ck, s1, step=1)
    like = jax.device_put(jax.tree.map(jnp.zeros_like, s1),
                          b["shardings"][1])
    s2 = restore_checkpoint(ck, like)
    out[tag + "_equal"] = all(
        bool(jnp.array_equal(a, r)) for a, r in
        zip(jax.tree.leaves(s1), jax.tree.leaves(s2)))
    out[tag + "_mesh_backed"] = all(
        getattr(x.sharding, "mesh", None) is not None
        for x in jax.tree.leaves(s2))
    out[tag + "_state_shapes"] = sorted(
        {str(tuple(x.shape)) for x in jax.tree.leaves(s1)})
    out[tag + "_nonzero"] = any(
        float(jnp.max(jnp.abs(x))) > 0 for x in jax.tree.leaves(s1)
        if jnp.issubdtype(x.dtype, jnp.floating))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_run(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("sharded"))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _CHILD, d], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    meta = json.loads(r.stdout.strip().splitlines()[-1])
    with open(os.path.join(d, "serial.hlo")) as f:
        serial = f.read()
    with open(os.path.join(d, "pipelined.hlo")) as f:
        pipelined = f.read()
    return serial, pipelined, meta


def test_sharded_buckets_lower_to_reduce_scatter_all_gather(sharded_run):
    """The acceptance criterion verbatim: with fsdp=2 the compiled SPMD
    program reduces every bucket with reduce-scatter + all-gather — zero
    full all-reduce — and the shard-local pack/unpack reshapes introduce
    no all-to-all or collective-permute."""
    serial, pipelined, meta = sharded_run
    n = meta["serial_buckets"]
    assert n >= 8                     # really multi-bucket
    for txt in (serial, pipelined):
        c = count_collective_ops(txt)
        assert c["all_reduce"] == 0, c
        assert c["reduce_scatter"] > 0 and c["all_gather"] > 0, c
        assert c["all_to_all"] == 0 and c["collective_permute"] == 0, c
    # serial unrolls one RS/AG pair per active mesh axis per bucket (the
    # default (1,2,2) topo has two active learner axes at the global
    # level); the pipeline's scan keeps the count O(1) in buckets
    cs = count_collective_ops(serial)
    assert cs["reduce_scatter"] == 2 * n
    # at least the scatter-mean's forward gathers; GSPMD may add more
    # around the sparse codec
    assert cs["all_gather"] >= 2 * n
    cp = count_collective_ops(pipelined)
    assert cp["reduce_scatter"] + cp["all_gather"] <= 16


def test_sharded_mean_and_cast_match_replicated_oracle(sharded_run):
    """Sharded bucketed mean/cast are bit-identical to the per-leaf
    replicated reduction (the RS chain walks the same per-axis tree as
    the replicated grouped mean, so not even the summation order
    differs)."""
    _, _, meta = sharded_run
    assert meta["maxdiff_mean"] == 0.0
    assert meta["maxdiff_cast"] == 0.0


def test_sharded_fused_qint8_within_quant_error(sharded_run):
    """fsdp=2 coverage for the fused single-buffer qint8 pack: the
    sharded bucket reduction lands within the quantizer's error bound
    of the dense mean, and ships exactly one packed message per
    bucket."""
    _, _, meta = sharded_run
    assert meta["maxdiff_qint8"] <= meta["absmax_qint8"] / 100.0, meta
    assert meta["qint8_messages"] == meta["qint8_buckets"], meta


def test_sharded_ef_state_roundtrips_through_checkpoint(sharded_run):
    """Sparse EF state lives in shard space — codec view, shards merged
    into the local-learner axis (lead S*F = 2*2 = 4 on the default
    topo) — and restores bit-exactly onto its mesh-backed shardings.
    qint8 runs the same save/restore path (stateless today, so the
    round-trip degenerates to the empty tree)."""
    _, _, meta = sharded_run
    assert meta["topk_nonzero"]       # EF actually carried something
    assert meta["topk_equal"] and meta["topk_mesh_backed"]
    lead_merged = [s for s in meta["topk_state_shapes"]
                   if s.startswith("(1, 2, 4")]
    assert lead_merged, meta["topk_state_shapes"]
    assert meta["qint8_equal"] and meta["qint8_mesh_backed"]


_SWEEP_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=16")
import json
import jax, jax.numpy as jnp
import numpy as np
jax.config.update("jax_enable_x64", False)
from jax.sharding import Mesh
from repro.configs.base import HierAvgParams
from repro.configs.resnet18_cifar import MLPConfig
from repro.core import (HierTopology, init_state, make_hier_round,
                        unstack_first)
from repro.data.synthetic import make_classification_task
from repro.models.resnet import mlp_cls_init, mlp_cls_loss
from repro.optim import sgd
from repro.parallel.sharding import shard_plan

cfg = MLPConfig(in_dim=16, hidden=(32,), n_classes=4)
sample = make_classification_task(16, 4, seed=11, noise=0.5)
loss_fn = lambda p, b: mlp_cls_loss(p, b)
eval_batch = sample(jax.random.PRNGKey(123), 256)
topo = HierTopology(2, 2, 2)
B = 16
h = HierAvgParams(k1=2, k2=8,
                  plan="local@2:mean:bucketed/pod@4:mean:bucketed/"
                       "global@8:mean:bucketed")
opt = sgd(0.05)


def run(shards):
    rnd = jax.jit(make_hier_round(loss_fn, opt, h, shards=shards))
    state = init_state(topo, lambda k: mlp_cls_init(k, cfg), opt,
                       jax.random.PRNGKey(0), plan=h.resolved_plan,
                       shards=shards)
    dims = tuple(h.resolved_plan.batch_dims)
    losses, dk = [], jax.random.PRNGKey(42)
    for r in range(3):
        dk, sk = jax.random.split(dk)
        batch = sample(sk, h.k2 * topo.n_learners * B)
        shaped = jax.tree.map(
            lambda x: x.reshape(dims + topo.shape + (B,) + x.shape[1:]),
            batch)
        state, _ = rnd(state, shaped)
        l, _ = loss_fn(unstack_first(state.params), eval_batch)
        losses.append(float(l))
    return losses


out = {"fsdp1": run(None)}
mesh = Mesh(np.array(jax.devices()[:16]).reshape(2, 2, 2, 2, 1),
            ("pod", "group", "local", "fsdp", "model"))
out["fsdp2"] = run(shard_plan(mesh))
print(json.dumps(out))
"""


@pytest.mark.slow
def test_three_level_sweep_at_fsdp2_matches_replicated():
    """The fsdp=2 leg of the 3-level convergence sweep (the sweep itself
    — pod on/off vs the Thm-3.2 bars — lives in tests/test_hier_avg.py):
    the same 3-level bucketed-mean plan on a 2x2x2 topology, trained
    replicated and trained with every learner 2-way sharded on a forced
    16-host-device mesh, must produce the same loss trajectory — the
    RS/AG decomposition is an implementation detail, not an algorithm
    change — and must converge."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _SWEEP_CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["fsdp2"][-1] < 0.8 * out["fsdp2"][0], out
    np.testing.assert_allclose(out["fsdp1"], out["fsdp2"],
                               rtol=1e-4, atol=1e-4)


# ------------------- replica groups (no devices) --------------------- #

def _mesh_stub(shape, names):
    """replica_groups/level_replica_groups only touch ``devices.shape``
    and ``axis_names`` — a stub stands in for an 8-device mesh."""
    return types.SimpleNamespace(devices=np.empty(shape), axis_names=names)


_HIER_NAMES = ("pod", "group", "local", "fsdp", "model")


def test_replica_groups_keep_shard_axis():
    """A global reduction on a (1,2,2,2,1) hier mesh keeps fsdp: each
    shard averages only with its 4 peers (row-major device order,
    reduced axes minor)."""
    mesh = _mesh_stub((1, 2, 2, 2, 1), _HIER_NAMES)
    assert replica_groups(mesh, ("pod", "group", "local")) \
        == [[0, 2, 4, 6], [1, 3, 5, 7]]
    # local level: one group per (group, fsdp) coordinate
    assert replica_groups(mesh, ("local",)) \
        == [[0, 2], [1, 3], [4, 6], [5, 7]]


def test_level_replica_groups_matches_plan_axes():
    from repro.launch.mesh import level_replica_groups
    mesh = _mesh_stub((1, 2, 2, 2, 1), _HIER_NAMES)
    assert level_replica_groups(mesh, "global") \
        == replica_groups(mesh, ("pod", "group", "local"))
    assert level_replica_groups(mesh, "local") \
        == replica_groups(mesh, ("local",))
    # pod level spans group+local on a single-pod mesh
    assert level_replica_groups(mesh, "pod") \
        == replica_groups(mesh, ("group", "local"))


# ------------- safe_pspec non-dividing drop (regression) ------------- #

def test_safe_pspec_surfaces_nondividing_model_zoo_shapes():
    """The shapes that historically hit the silent-replication fallback:
    hymba's 25 attention heads vs TP-16 and seamless' 256206-token vocab
    vs TP-16 don't divide — the drop must warn (PSpecDropWarning) and
    resolve_pspec must expose exactly which axes fell off, so layout and
    billing key off the resolved spec."""
    from jax.sharding import PartitionSpec as P
    mesh = AbstractMesh((2, 16), ("fsdp", "model"))
    # hymba: 25 heads -> head-stacked (25, 128) leaf, TP on the head dim
    resolved, dropped = resolve_pspec(P("model", None), (25, 128), mesh)
    assert tuple(resolved) == (None, None)
    assert dropped == ((0, "model"),)
    with pytest.warns(PSpecDropWarning, match="25, 128"):
        assert safe_pspec(P("model", None), (25, 128), mesh) \
            == P(None, None)
    # seamless: vocab 256206 = 2 * 128103 divides fsdp=2 but not TP-16
    resolved, dropped = resolve_pspec(P("model", "fsdp"), (256206, 1024),
                                      mesh)
    assert tuple(resolved) == (None, "fsdp")
    assert dropped == ((0, "model"),)
    # dividing specs resolve unchanged, drop-free and warning-free
    with warnings.catch_warnings():
        warnings.simplefilter("error", PSpecDropWarning)
        assert safe_pspec(P("fsdp", "model"), (256206, 1024), mesh) \
            == P("fsdp", "model")


def test_shard_plan_mirrors_safe_pspec_drop():
    """ShardPlan.leaf_shard_dim (what the bucket layout packs from) and
    the resolve_pspec drop agree: a non-dividing leaf stays flat, a
    dividing one shards its rules-resolved dim."""
    mesh = AbstractMesh((1, 2, 2, 2, 1), _HIER_NAMES)
    sp = ShardPlan(mesh=mesh)
    # hymba-style head-count leaf: fallback (fsdp, model) on (25, 128),
    # 25 % 2 != 0 -> replicated, exactly the safe_pspec drop
    assert sp.leaf_shard_dim("blocks/0/attn/heads", (25, 128)) is None
    # the same rule with a dividing dim shards dim 0
    assert sp.leaf_shard_dim("blocks/0/attn/wq", (1600, 512)) == 0
    # seamless embed: rules put only "model" on the vocab dim -> no
    # fsdp dim anywhere, replicated for the reduction stack
    assert sp.leaf_shard_dim("embed", (256206, 1024)) is None
