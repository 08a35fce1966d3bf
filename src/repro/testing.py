"""Shared harness for the 8-host-device serial-vs-pipelined reduction
A/B.

benchmarks/bench_bucketing.py (the wall-clock/record rows) and
tests/test_pipeline.py (the HLO overlap-structure assertions) must
measure the SAME program — this module is the single builder both call,
so the benchmarked reduction and the structurally-verified reduction
cannot drift apart.  The autotune probe (autotune/probe.py) reuses the
same builder with non-default ``topo_shape``/``level``/size arguments,
so calibration samples measure the same reduction program too.

Callers are responsible for forcing >= 8 host devices
(``--xla_force_host_platform_device_count=8``) before jax initializes.
"""
from __future__ import annotations

import re

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm import Bucketed, Pipelined, get_reducer, reduce_with
from repro.core import HierTopology
from repro.core.topology import (global_average, local_average, pod_average,
                                 stack_like)

LEVEL_AVG_FNS = {
    "local": local_average,
    "pod": pod_average,
    "global": global_average,
}

# the A/B shape: 24 leaves x 96*64 fp32 = 24 KiB each, stacked over the
# 8-learner (1, 2, 4) mesh.  32 KiB cap -> 24 buckets (one leaf each);
# 4 MiB cap -> 1 bucket (the schedules provably coincide).
AB_LEAVES = 24
AB_LEAF_SHAPE: Tuple[int, int] = (96, 64)
AB_SMALL_CAP = 32 << 10
AB_LARGE_CAP = 4 << 20


def build_ab_reduction(sched: str, cap: int, *, n_leaves: int = AB_LEAVES,
                       leaf_shape: Tuple[int, ...] = AB_LEAF_SHAPE,
                       spec: str = "topk:0.05",
                       topo_shape: Tuple[int, int, int] = (1, 2, 4),
                       level: str = "global") -> Dict:
    """One A/B variant: the jitted ``level`` reduction (local / pod /
    global grouped mean) of a synthetic ``n_leaves``-leaf tree over the
    ``topo_shape`` learner mesh, on the serial (``Bucketed``) or
    pipelined (``Pipelined``) schedule at bucket cap ``cap``, or with
    ``sched="perleaf"`` the raw un-bucketed reducer (``cap`` unused) —
    the one-collective-per-leaf baseline of the codec A/B.  Returns
    the pieces the benchmark, the HLO test, and the autotune probe all
    need: reducer, single-learner tree, stacked params, carried state,
    shardings, the jitted fn, and the bucket count."""
    topo = HierTopology(*topo_shape)
    mesh = Mesh(np.array(jax.devices()[:topo.n_learners])
                .reshape(topo.shape), ("pod", "group", "local"))
    key = jax.random.PRNGKey(0)
    tree1 = {f"w{i:02d}": jax.random.normal(jax.random.fold_in(key, i),
                                            leaf_shape)
             for i in range(n_leaves)}
    params = stack_like(topo, tree1)

    def shard(leaf):
        pspec = P("pod", "group", "local") if leaf.ndim >= 3 else P()
        return NamedSharding(mesh, pspec)

    if sched == "perleaf":
        # the un-bucketed baseline: one collective per leaf (two for
        # two-message codecs), what the codec A/B rows beat
        red = get_reducer(spec)
    else:
        engine = Pipelined if sched == "pipelined" else Bucketed
        red = engine(get_reducer(spec), cap)
    state = red.init_state(jax.tree.map(jnp.zeros_like, params))
    shardings = (jax.tree.map(shard, params), jax.tree.map(shard, state))
    avg_fn = LEVEL_AVG_FNS[level]

    def reduction(p, s):
        return reduce_with(red, avg_fn, p, s)

    return {
        "reducer": red,
        "tree1": tree1,
        "params": params,
        "state": state,
        "shardings": shardings,
        "fn": jax.jit(reduction, in_shardings=shardings),
        "n_buckets": (red.layout_for(params).n_buckets
                      if hasattr(red, "layout_for") else n_leaves),
    }


def build_sharded_ab_reduction(sched: str, cap: int, *,
                               n_leaves: int = AB_LEAVES,
                               leaf_shape: Tuple[int, ...] = AB_LEAF_SHAPE,
                               spec: str = "topk:0.05",
                               topo_shape: Tuple[int, int, int] = (1, 2, 2),
                               fsdp: int = 2,
                               level: str = "global") -> Dict:
    """The fsdp>1 counterpart of :func:`build_ab_reduction`: the same
    ``level`` reduction on a 5-axis hier mesh (learners x fsdp x model=1)
    with a :class:`~repro.parallel.sharding.ShardPlan`, so the bucket
    engine packs per-shard runs and the grouped mean lowers to
    reduce-scatter + all-gather.  Default shape uses all 8 forced host
    devices as 4 learners x 2 shards.  Rank-2 leaves shard trailing dim 0
    over fsdp (DEFAULT_RULES fallback).  Returns the same dict keys as
    the replicated builder plus ``mesh`` and ``shards``."""
    from repro.parallel.sharding import shard_plan
    topo = HierTopology(*topo_shape)
    n_dev = topo.n_learners * fsdp
    mesh = Mesh(np.array(jax.devices()[:n_dev])
                .reshape(topo.shape + (fsdp, 1)),
                ("pod", "group", "local", "fsdp", "model"))
    sp = shard_plan(mesh)
    assert sp is not None, (topo_shape, fsdp)
    key = jax.random.PRNGKey(0)
    tree1 = {f"w{i:02d}": jax.random.normal(jax.random.fold_in(key, i),
                                            leaf_shape)
             for i in range(n_leaves)}
    params = stack_like(topo, tree1)
    s_sz = topo.local

    def shard(leaf):
        if leaf.ndim >= 4 and leaf.shape[:3] == topo.shape:
            # stacked param leaf: learner axes + fsdp on trailing dim 0
            return NamedSharding(mesh, P("pod", "group", "local", "fsdp",
                                         *(None,) * (leaf.ndim - 4)))
        if leaf.ndim >= 3 and leaf.shape[2] == s_sz * fsdp:
            # codec-view EF state (shard space): shards merged into the
            # local-learner axis, major-minor mesh order
            return NamedSharding(mesh, P("pod", "group",
                                         ("local", "fsdp"),
                                         *(None,) * (leaf.ndim - 3)))
        return NamedSharding(mesh, P())

    engine = Pipelined if sched == "pipelined" else Bucketed
    red = engine(get_reducer(spec), cap, shards=sp)
    state = red.init_state(jax.tree.map(jnp.zeros_like, params))
    shardings = (jax.tree.map(shard, params), jax.tree.map(shard, state))
    avg_fn = LEVEL_AVG_FNS[level]

    def reduction(p, s):
        return reduce_with(red, avg_fn, p, s)

    return {
        "reducer": red,
        "tree1": tree1,
        "params": params,
        "state": state,
        "shardings": shardings,
        "fn": jax.jit(reduction, in_shardings=shardings),
        "n_buckets": red.layout_for(params).n_buckets,
        "mesh": mesh,
        "shards": sp,
    }


def count_allreduce_ops(hlo_text: str) -> int:
    """All-reduce ops in a compiled module (sync or async spelling) —
    the program-size metric the A/B and the overlap test both gate on."""
    return hlo_text.count("all-reduce(") + hlo_text.count("all-reduce-start(")


def count_allreduce_operands(hlo_text: str) -> int:
    """Arrays reduced by all-reduce ops in a compiled module.  XLA's
    all-reduce combiner merges independent all-reduces into one op over a
    tuple of operands, so the op count says how many collectives were
    issued and this count says how many arrays they carried."""
    n = 0
    for m in re.finditer(r"all-reduce(?:-start)?\(([^)]*)\)", hlo_text):
        n += m.group(1).count("%")
    return n


def count_collective_ops(hlo_text: str) -> Dict[str, int]:
    """Per-kind collective op counts (sync + async spellings) — what the
    sharded RS/AG tests and benchmark rows gate on: a sharded bucket
    reduction must show reduce-scatter + all-gather, zero all-reduce for
    its buckets, and no stray all-to-all / collective-permute from a
    non-shard-local reshape."""
    c = hlo_text.count
    return {
        "all_reduce": c("all-reduce(") + c("all-reduce-start("),
        "reduce_scatter": c("reduce-scatter(") + c("reduce-scatter-start("),
        "all_gather": c("all-gather(") + c("all-gather-start("),
        "all_to_all": c("all-to-all(") + c("all-to-all-start("),
        "collective_permute": (c("collective-permute(")
                               + c("collective-permute-start(")),
    }
