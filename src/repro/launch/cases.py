"""Dry-run case construction: (arch x input shape x mesh) -> a lowerable
jitted program with ShapeDtypeStruct inputs and NamedSharding in_shardings.

No arrays are ever allocated here: parameter/cache structures come from
``jax.eval_shape`` over the real init functions.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm import EFState, LowRankState
from repro.configs.base import (ArchConfig, HierAvgParams, InputShape,
                                INPUT_SHAPES, ParallelLayout)
from repro.core.hier_avg import init_state, make_hier_round
from repro.core.topology import HierTopology
from repro.launch.mesh import PODS_MULTI, make_hier_mesh, make_production_mesh
from repro.models import build
from repro.models.stubs import train_batch_specs
from repro.optim import sgd
from repro.parallel.sharding import (PartitionRules, param_pspecs,
                                     safe_pspec, shard_plan)


@dataclasses.dataclass
class DryrunCase:
    name: str
    mesh: Mesh
    jitted: Any                 # jax.jit(...) ready to .lower(*arg_specs)
    arg_specs: Tuple            # ShapeDtypeStructs
    steps: int                  # SGD steps (or decode steps) per program
    notes: str = ""


# --------------------------------------------------------------------- #
# training case (hier mesh)
# --------------------------------------------------------------------- #

def parse_layout(spec: str) -> ParallelLayout:
    """'GxSxFxTP[:micro]' -> ParallelLayout (hillclimb override)."""
    micro = 1
    if ":" in spec:
        spec, m = spec.split(":")
        micro = int(m)
    g, s, f, tp = (int(x) for x in spec.split("x"))
    return ParallelLayout(groups=g, local=s, fsdp=f, tp=tp,
                          microbatch=micro)


def default_hier_params(cfg: ArchConfig) -> HierAvgParams:
    """Paper-faithful defaults: K1=4, K2=8 (beta=2) — small enough to keep
    the lowered round compact, large enough that local+global reductions
    both appear in the collective schedule."""
    return HierAvgParams(k1=4, k2=8)


def learner_state_placement(state_struct, mesh: Mesh, shards=None,
                            rules: Optional[PartitionRules] = None):
    """Placement of a stacked-learner ``TrainState`` on a hier mesh
    ``("pod", "group", "local", "fsdp", "model")``.

    Returns ``(state_shardings, constraint_fn)``: NamedShardings for every
    state leaf (params by the partition rules with the learner axes on
    their three leading dims; optimizer state and reducer comm state
    mirroring them), and the ``constraint_fn`` the round builder applies
    after each grouped mean to keep GSPMD on that placement.
    """
    rules = rules or PartitionRules()
    pspecs = param_pspecs(state_struct.params, mesh, stacked_learners=True,
                          rules=rules)
    params_treedef = jax.tree_util.tree_structure(state_struct.params)
    # momentum mirrors params: reuse param specs when structures match
    if jax.tree_util.tree_structure(state_struct.opt_state) \
            == params_treedef:
        opt_specs = pspecs
    else:
        opt_specs = jax.tree.map(
            lambda leaf: safe_pspec(
                P(*(("pod", "group", "local")
                    + (None,) * (leaf.ndim - 3))),
                leaf.shape, mesh),
            state_struct.opt_state)
    # reducer comm state, per plan level: EF ref/err (and PowerSGD ref/err)
    # mirror the params tree exactly (same shapes, fp32 err), so they reuse
    # the params' specs — learner axes AND trailing fsdp/tp shards; PRNG
    # keys stay replicated, and PowerSGD's warm Q shards over the learner
    # axes only (its trailing [b, rank] dims are tiny)
    s_sz = int(mesh.shape["local"])
    f_sz = int(mesh.shape.get("fsdp", 1))

    def bucket_lead_spec(leaf) -> P:
        """Lead spec for bucket-space leaves: learner axes sharded,
        trailing dims replicated.  Under an fsdp>1 ShardPlan the bucket
        engine keeps EF state in the *codec view* — shards merged into
        the local-learner dim, [pods, G, S*F, run] — so dim 2 shards
        over the ("local", "fsdp") tuple (major-minor mesh order, the
        shard-local merge comm/bucket.py performs)."""
        lead = ("pod", "group", "local")
        if (shards is not None and leaf.ndim >= 3
                and leaf.shape[2] == s_sz * f_sz):
            lead = ("pod", "group", ("local", "fsdp"))
        return safe_pspec(P(*(lead + (None,) * (leaf.ndim - 3))),
                          leaf.shape, mesh)

    def stacked_specs(tree):
        """Learner axes sharded, trailing dims replicated — the fallback
        for state trees that do NOT mirror the params (bucket-space EF
        from comm/bucket.py: [pods, G, S, n] packed buckets, or
        [pods, G, S*F, n] codec-view buckets under fsdp sharding)."""
        return jax.tree.map(bucket_lead_spec, tree)

    def level_comm_specs(cs):
        if isinstance(cs, EFState):
            mirrors = (jax.tree_util.tree_structure(cs.ref)
                       == params_treedef)
            specs = pspecs if mirrors else stacked_specs(cs.ref)
            err_specs = pspecs if mirrors else stacked_specs(cs.err)
            return EFState(ref=specs, err=err_specs, key=P())
        if isinstance(cs, LowRankState):
            q_specs = stacked_specs(cs.q)
            mirrors = (jax.tree_util.tree_structure(cs.ref)
                       == params_treedef)
            specs = pspecs if mirrors else stacked_specs(cs.ref)
            err_specs = pspecs if mirrors else stacked_specs(cs.err)
            return LowRankState(ref=specs, err=err_specs, q=q_specs)
        return jax.tree.map(lambda leaf: P(), cs)

    if isinstance(state_struct.comm_state, dict):
        comm_specs = {name: level_comm_specs(cs)
                      for name, cs in state_struct.comm_state.items()}
    else:
        comm_specs = level_comm_specs(state_struct.comm_state)
    state_specs = state_struct.__class__(pspecs, opt_specs, P(), comm_specs)
    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))
    param_shardings = state_shardings.params

    def pin_learner_axes(leaf):
        """Generic re-pin for trees that do NOT mirror the params
        (bucket-space reductions, comm/bucket.py): learner axes
        sharded, trailing bucket dims replicated (codec-view leaves
        keep their fsdp shard via ``bucket_lead_spec``)."""
        if getattr(leaf, "ndim", 0) < 3:
            return leaf
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, bucket_lead_spec(leaf)))

    def constraint_fn(tree):
        if jax.tree_util.tree_structure(tree) == params_treedef:
            return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                                param_shardings)
        return jax.tree.map(pin_learner_axes, tree)

    return state_shardings, constraint_fn


def train_case(cfg: ArchConfig, shape: InputShape, *, multi_pod: bool,
               hier: Optional[HierAvgParams] = None,
               remat: bool = True,
               param_dtype=jnp.bfloat16,
               sync_opt_state: bool = False,
               use_constraints: bool = True) -> DryrunCase:
    hier = hier or default_hier_params(cfg)
    plan = hier.resolved_plan
    lay = cfg.layout
    mesh = make_hier_mesh(lay, multi_pod=multi_pod)
    pods = PODS_MULTI if multi_pod else 1
    topo = HierTopology(pods=pods, groups=lay.groups, local=lay.local)

    bundle = build(cfg, param_dtype=param_dtype, remat=remat)
    optimizer = sgd(0.1)          # paper: plain SGD, step-decayed lr
    rules = PartitionRules()
    # fsdp>1: shard-aware bucket layout — buckets pack each device's
    # shard slice and every level's mean lowers to RS+AG (comm/bucket.py)
    shards = shard_plan(mesh, rules=rules) if lay.fsdp > 1 else None

    # ---- state structure without allocation ----
    state_struct = jax.eval_shape(
        lambda k: init_state(topo, bundle.init, optimizer, k, plan=plan,
                             shards=shards),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    state_shardings, constraint_fn = learner_state_placement(
        state_struct, mesh, shards, rules)
    if not use_constraints:
        constraint_fn = None

    # ---- per-learner batch ----
    per_learner_b = shape.global_batch // topo.n_learners
    assert per_learner_b >= 1, (cfg.name, shape.name, topo)
    inner = train_batch_specs(cfg, per_learner_b, shape.seq_len,
                              dtype=param_dtype)
    lead = plan.batch_dims + topo.shape

    def wrap(s):
        return jax.ShapeDtypeStruct(lead + s.shape, s.dtype)

    batch_specs = {k: wrap(v) for k, v in inner.items()}

    # schedule-aware round-batch shardings, generic in the plan depth
    # (data/loader.py owns the [*batch_dims, pod, group, local, fsdp]
    # assignment — the loader and the lowered case cannot disagree)
    from repro.data.loader import round_batch_shardings
    batch_shardings = round_batch_shardings(mesh, hier, batch_specs)

    round_fn = make_hier_round(bundle.loss_fn, optimizer, hier,
                               sync_opt_state=sync_opt_state,
                               constraint_fn=constraint_fn,
                               microbatch=lay.microbatch,
                               shards=shards)

    jitted = jax.jit(round_fn,
                     in_shardings=(state_shardings, batch_shardings),
                     out_shardings=(state_shardings, None),
                     donate_argnums=(0,))
    return DryrunCase(
        name=f"{cfg.name}:{shape.name}:{'2pod' if multi_pod else '1pod'}",
        mesh=mesh, jitted=jitted, arg_specs=(state_struct, batch_specs),
        steps=hier.steps_per_round,
        notes=f"hier_round plan={plan.describe()} "
              f"{topo.describe()} fsdp={lay.fsdp} tp={lay.tp} "
              f"B/learner={per_learner_b}")


# --------------------------------------------------------------------- #
# serving cases (production mesh)
# --------------------------------------------------------------------- #

_SERVE_AXIS_MAP_1POD = {"pod": None, "group": None, "local": None,
                        "fsdp": "data", "model": "model"}


def _serve_param_shardings(params_struct, mesh: Mesh, multi_pod: bool):
    amap = dict(_SERVE_AXIS_MAP_1POD)
    if multi_pod:
        amap["fsdp"] = ("pod", "data")
    rules = PartitionRules(axis_map=amap)
    specs = param_pspecs(params_struct, mesh, stacked_learners=False,
                         rules=rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_axis(mesh: Mesh, multi_pod: bool):
    return ("pod", "data") if multi_pod else "data"


def _cache_pspec(path: str, leaf, mesh: Mesh, batch: int, multi_pod: bool
                 ) -> P:
    """Heuristic cache sharding:
    batch dim over data (when divisible), heads/state over model, and for
    batch-1 long-context the sequence dim over data."""
    bax = _batch_axis(mesh, multi_pod)
    ndim = leaf.ndim
    if ndim == 0:          # pos counters
        return P()
    if ndim == 1:          # stacked pos [L]
        return P(None)
    # leading dim is the layer stack L; dim 1 is batch
    spec = [None] * ndim
    spec[1] = bax
    name = path.split("/")[-1]
    tp = mesh.shape["model"]
    if name in ("k", "v", "cross_k", "cross_v") and ndim >= 5:
        # [L,B,T,H,D]: shard heads over TP when divisible; otherwise shard
        # HEAD_DIM over TP (keeps the per-step cache write local; avoids
        # 16x cache replication for kv-head counts < 16)
        if leaf.shape[3] % tp == 0:
            spec[3] = "model"
        elif leaf.shape[4] % tp == 0:
            spec[4] = "model"
        elif leaf.shape[2] % tp == 0:
            spec[2] = "model"
        if batch == 1:
            spec[1] = None
            spec[2] = bax if leaf.shape[2] % 16 == 0 else None
    elif name in ("ckv", "k_rope") and ndim >= 4:
        if leaf.shape[3] % tp == 0:
            spec[3] = "model"      # [L,B,T,lora] — latent dim over TP
        if batch == 1:
            spec[1] = None
            spec[2] = bax if leaf.shape[2] % 16 == 0 else None
    elif name == "wkv" and ndim >= 3:
        spec[2] = "model"          # [L,B,H,D,D]
    elif name in ("ssm", "conv") and ndim >= 3:
        spec[2] = "model" if name == "ssm" else None  # [L,B,Ci,N]/[L,B,K,Ci]
        if name == "conv" and ndim >= 4:
            spec[3] = "model"
    elif name in ("tm_shift", "cm_shift") and ndim >= 3:
        spec[2] = "model"          # [L,B,d]
    return safe_pspec(P(*spec), leaf.shape, mesh)


def decode_case(cfg: ArchConfig, shape: InputShape, *, multi_pod: bool,
                param_dtype=jnp.bfloat16,
                cache_dtype=jnp.bfloat16) -> DryrunCase:
    mesh = make_production_mesh(multi_pod=multi_pod)
    rolling = (shape.name == "long_500k"
               and cfg.family in ("dense", "moe", "vlm", "audio")
               and not cfg.kv_lora_rank)
    bundle = build(cfg, param_dtype=param_dtype, rolling_decode=rolling,
                   cache_dtype=cache_dtype)
    B = shape.global_batch
    max_len = shape.seq_len

    params_struct = jax.eval_shape(
        bundle.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_shard = _serve_param_shardings(params_struct, mesh, multi_pod)

    cache_struct = jax.eval_shape(
        functools.partial(bundle.init_cache, B, max_len))
    c_shard = jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh, _cache_pspec("/".join(str(getattr(k, "key", k))
                                        for k in kp), leaf, mesh, B,
                               multi_pod)),
        cache_struct)

    bax = _batch_axis(mesh, multi_pod)
    tok_spec = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok_shard = NamedSharding(mesh, safe_pspec(P(bax), (B,), mesh))

    def serve_step(params, tokens, cache):
        return bundle.decode_step(params, tokens, cache)

    jitted = jax.jit(serve_step,
                     in_shardings=(p_shard, tok_shard, c_shard),
                     out_shardings=(None, c_shard),
                     donate_argnums=(2,))   # cache updated in place
    kind = ("rolling-window" if rolling else
            "mla-latent" if cfg.kv_lora_rank else
            "state" if cfg.family in ("ssm", "hybrid") else "full-kv")
    return DryrunCase(
        name=f"{cfg.name}:{shape.name}:{'2pod' if multi_pod else '1pod'}",
        mesh=mesh, jitted=jitted,
        arg_specs=(params_struct, tok_spec, cache_struct), steps=1,
        notes=f"serve_step cache={kind} B={B} ctx={max_len}")


def prefill_case(cfg: ArchConfig, shape: InputShape, *, multi_pod: bool,
                 param_dtype=jnp.bfloat16, remat: bool = True) -> DryrunCase:
    mesh = make_production_mesh(multi_pod=multi_pod)
    bundle = build(cfg, param_dtype=param_dtype, remat=remat)
    B = shape.global_batch

    params_struct = jax.eval_shape(
        bundle.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_shard = _serve_param_shardings(params_struct, mesh, multi_pod)

    inner = train_batch_specs(cfg, B, shape.seq_len, dtype=param_dtype)
    inner.pop("labels", None)
    bax = _batch_axis(mesh, multi_pod)
    b_shard = {k: NamedSharding(
        mesh, safe_pspec(P(*((bax,) + (None,) * (len(v.shape) - 1))),
                         v.shape, mesh))
        for k, v in inner.items()}

    def prefill(params, batch):
        logits, cache = bundle.prefill(params, batch)
        return logits

    jitted = jax.jit(prefill, in_shardings=(p_shard, b_shard),
                     out_shardings=None)
    return DryrunCase(
        name=f"{cfg.name}:{shape.name}:{'2pod' if multi_pod else '1pod'}",
        mesh=mesh, jitted=jitted, arg_specs=(params_struct, inner), steps=1,
        notes=f"prefill B={B} S={shape.seq_len}")


def build_case(cfg: ArchConfig, shape_name: str, *, multi_pod: bool,
               **kw) -> DryrunCase:
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        return train_case(cfg, shape, multi_pod=multi_pod, **kw)
    if shape.kind == "prefill":
        return prefill_case(cfg, shape, multi_pod=multi_pod)
    return decode_case(cfg, shape, multi_pod=multi_pod)
