"""Hier-AVG (Algorithm 1) as a composable JAX trainer, generalized to an
N-level :class:`~repro.core.plan.ReductionPlan`.

A round is one jitted program built as a recursive nest of ``lax.scan``s —
one scan per plan level, innermost first:

    level 0:  p_1 SGD steps, then the level-0 reduction
    level i:  (p_{i+1}/p_i) runs of level i-1, then the level-i reduction

The paper's Algorithm 1 is the 2-level plan ``local@K1 / global@K2``
(``beta = K2/K1`` runs of K1 local steps + cluster averaging, then one
global averaging), which legacy ``HierAvgParams(k1, k2)`` builds
bit-identically.  A 3-level ICI/DCI-aligned plan adds a ``pod`` rung.

Parameters/optimizer state live in the stacked-learner layout
[pods, G, S, *shape]; per-learner gradients come from one ``jax.grad`` of
the summed per-learner losses through a triple ``vmap``.  Each level's
reduction is a ``jnp.mean`` over that level's stacked axes (see
core/topology.py) which GSPMD turns into grouped all-reduces over the
matching mesh axes, optionally compressed per level by a comm/ Reducer.

The same code runs on a single CPU device (simulator / tests — no mesh)
and on the 512-chip multi-pod mesh (launch/dryrun.py supplies shardings).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm import Reducer, reduce_with
from repro.configs.base import HierAvgParams
from repro.core.plan import (PlanLike, ReductionLevel, ReductionPlan,
                             apply_bucketing, apply_shards, init_comm_state,
                             resolve_plan)
from repro.core.topology import (HierTopology, average_over, stack_like,
                                 where_active)
from repro.optim import Optimizer
from repro.telemetry.spans import reduce_scope


class TrainState(NamedTuple):
    params: Any          # leaves [pods, G, S, *shape]
    opt_state: Any       # same stacking
    step: jax.Array      # scalar int32 — local SGD steps taken
    comm_state: Any = () # per-level reducer carry (comm/), keyed by level
                         # name; () when no level is stateful


def init_state(topo: HierTopology, init_fn, optimizer: Optimizer, key,
               reducer: Optional[Reducer] = None,
               plan: PlanLike = None,
               bucket_bytes: Optional[int] = None,
               overlap: Optional[bool] = None,
               shards: Optional[Any] = None) -> TrainState:
    """All learners start from the same w_1 (paper's initialization).

    ``plan`` (or legacy ``reducer``) must match what the round/step
    function was built with: stateful reducers carry per-level state in
    ``comm_state`` keyed by level name.  Passing only ``reducer`` builds
    the default 2-level (local/global) state for it.

    Bucketing must agree with the round builder's ``resolve_plan``
    (comm/bucket.py): a ``plan`` given as a spec string, or a bare
    ``reducer``, gets the same default bucketing a default
    ``HierAvgParams`` resolves to; pass ``bucket_bytes`` (0 = per-leaf)
    and/or ``overlap=False`` when the round uses non-default
    ``HierAvgParams.bucket_bytes`` / ``HierAvgParams.overlap`` (the
    pipelined engine pads multi-bucket layouts uniform, so its EF state
    shapes differ from the serial schedule's).  A ``ReductionPlan``
    *instance* is taken as already resolved (e.g. ``hier.resolved_plan``)
    unless ``bucket_bytes`` or ``overlap`` is given explicitly — an
    explicit ``overlap`` re-chooses the bucket engine (demoting
    auto-pipelined wrappers to the serial schedule and vice versa; each
    wrapper keeps its own cap when ``bucket_bytes`` stays None).

    ``shards`` — the :class:`~repro.parallel.sharding.ShardPlan` the
    round/step builder was given (fsdp>1 meshes); bucketed reducers then
    carry error-feedback state in *shard space* (codec view), so it must
    match or the state shapes are wrong.
    """
    from repro.comm import DEFAULT_BUCKET_BYTES
    params1 = init_fn(key)
    params = stack_like(topo, params1)
    opt_state = optimizer.init(params)
    ov = True if overlap is None else overlap
    if plan is not None:
        if isinstance(plan, ReductionPlan):
            p = apply_shards(plan, shards) \
                if (bucket_bytes is None and overlap is None) \
                else apply_bucketing(
                    plan, 0 if bucket_bytes is None else bucket_bytes, ov,
                    shards=shards)
        else:
            p = apply_bucketing(
                ReductionPlan.parse(plan),
                DEFAULT_BUCKET_BYTES if bucket_bytes is None
                else bucket_bytes, ov, shards=shards)
        comm_state = init_comm_state(p, params)
    elif reducer is not None:
        comm_state = init_comm_state(
            apply_bucketing(ReductionPlan.from_k1_k2(1, 1, reducer),
                            DEFAULT_BUCKET_BYTES if bucket_bytes is None
                            else bucket_bytes, ov, shards=shards), params)
    else:
        comm_state = ()
    return TrainState(params, opt_state, jnp.zeros((), jnp.int32),
                      comm_state)


def stacked_grad_fn(loss_fn: Callable):
    """loss_fn(params, batch) -> (loss, metrics), single learner.

    Returns grad_fn(stacked_params, stacked_batch) -> (grads, metrics) where
    grads are per-learner (stacked) and metrics keep the learner axes.
    """
    f = loss_fn
    for _ in range(3):
        f = jax.vmap(f)

    def total(params, batch):
        losses, metrics = f(params, batch)
        return losses.sum(), metrics

    return jax.grad(total, has_aux=True)


def make_sgd_step(loss_fn: Callable, optimizer: Optimizer,
                  grad_postprocess: Optional[Callable] = None,
                  microbatch: int = 1,
                  grad_observer: Optional[Callable] = None):
    """One local SGD step on all learners concurrently.

    ``microbatch > 1`` splits each learner's per-step batch (dim 3 of every
    leaf, after the [pods, G, S] axes) into that many slices and accumulates
    gradients over a ``lax.scan`` — activation memory drops by the factor,
    FLOPs unchanged.

    ``grad_observer`` (telemetry/gradstats.py): a pure function of the
    stacked per-learner gradients returning extra scalar metrics keys —
    a read-only tap, the update itself is untouched.
    """
    grad_fn = stacked_grad_fn(loss_fn)

    def one_shot(state: TrainState, batch):
        return grad_fn(state.params, batch)

    def accumulated(state: TrainState, batch):
        def split(x):
            b = x.shape[3]
            assert b % microbatch == 0, (x.shape, microbatch)
            y = x.reshape(x.shape[:3] + (microbatch, b // microbatch)
                          + x.shape[4:])
            return jnp.moveaxis(y, 3, 0)      # [m, pods, G, S, b/m, ...]

        micro = jax.tree.map(split, batch)
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)

        def acc(g, mb):
            grads, metrics = grad_fn(state.params, mb)
            g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                             g, grads)
            return g, metrics

        grads, ms = jax.lax.scan(acc, zeros, micro)
        grads = jax.tree.map(lambda g: g / microbatch, grads)
        metrics = jax.tree.map(lambda m: m.mean(0), ms)
        return grads, metrics

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        if microbatch == 1:
            grads, metrics = one_shot(state, batch)
        else:
            grads, metrics = accumulated(state, batch)
        if grad_observer is not None:
            metrics = dict(metrics)
            metrics.update(grad_observer(grads))
        if grad_postprocess is not None:
            grads = grad_postprocess(grads)
        params, opt_state = optimizer.update(grads, state.params,
                                             state.opt_state, state.step)
        return state._replace(params=params, opt_state=opt_state,
                              step=state.step + 1), metrics

    return step


def _make_reduce(constraint_fn, sync_opt_state):
    """reduce(level, state, active=None) -> state after one compressed
    reduction at that level, touching only that level's comm_state entry.

    ``active`` (elastic membership, repro/elastic): a boolean
    ``[pods, G, S]`` participation mask.  The grouped mean renormalizes
    over the present learners only (core/topology.py ``average_over``),
    and absent learners keep their own params AND their EF/``comm_state``
    untouched across the missed fire (``where_active`` select) — a
    learner that missed a reduction neither contributes to nor observes
    it.  ``active=None`` is the dense path, bit-identical to before.

    The whole reduction, bucket pack and unpack, codec, grouped mean and
    finalize, runs under the scope ``reduce.<level>``
    (telemetry/spans.py), so the device trace bills it to its level.
    """

    def reduce(level: ReductionLevel, state: TrainState,
               active=None) -> TrainState:
        with jax.named_scope(reduce_scope(level.name)):
            avg_fn = lambda tree, cf=None, specs=None: average_over(  # noqa: E731
                tree, level.axes, cf, specs, active)
            if level.reducer.stateful:
                params, lvl_cs = reduce_with(
                    level.reducer, avg_fn, state.params,
                    state.comm_state[level.name], constraint_fn)
                if active is not None:
                    lvl_cs = where_active(active, lvl_cs,
                                          state.comm_state[level.name])
                comm_state = dict(state.comm_state)
                comm_state[level.name] = lvl_cs
            else:
                params, _ = reduce_with(level.reducer, avg_fn, state.params,
                                        (), constraint_fn)
                comm_state = state.comm_state
            if active is not None:
                params = where_active(active, params, state.params)
            if sync_opt_state:
                opt = avg_fn(state.opt_state, constraint_fn)
                if active is not None:
                    opt = where_active(active, opt, state.opt_state)
                state = state._replace(opt_state=opt)
            return state._replace(params=params, comm_state=comm_state)

    return reduce


def make_hier_round(loss_fn: Callable, optimizer: Optimizer,
                    hier: HierAvgParams, *,
                    sync_opt_state: bool = False,
                    skip_local: bool = False,
                    constraint_fn: Optional[Callable] = None,
                    grad_postprocess: Optional[Callable] = None,
                    microbatch: int = 1,
                    reducer: Optional[Any] = None,
                    plan: PlanLike = None,
                    shards: Optional[Any] = None,
                    elastic: bool = False,
                    telemetry: Any = None):
    """Build the jitted Hier-AVG round for an N-level reduction plan.

    round(state, round_batch) -> (state, metrics); round_batch leaves are
    shaped [*hier.batch_dims, pods, G, S, *per_learner_batch] — for the
    legacy 2-level plan that is the familiar [beta, K1, ...].

    ``elastic=True`` builds the participation-masked round instead:
    ``round(state, round_batch, active) -> (state, metrics)`` with
    ``active`` a boolean ``[n_levels, pods, G, S]`` mask (level *i* of the
    plan, innermost first, uses ``active[i]`` for every one of its fires
    this round).  Absent learners contribute weight 0 to that level's
    renormalized mean and keep their params and EF state untouched
    (see ``_make_reduce``); metrics gain ``active_frac/<level>``.  With
    an all-true mask the round is bit-identical to the dense build.

    ``plan`` — a ReductionPlan, a spec string
    ("local@4:cast:bfloat16/pod@8/global@16:topk:0.05"), or None to use
    ``hier.plan`` / the legacy 2-level plan from ``hier.k1``/``hier.k2``.

    ``skip_local=True`` skips every reduction except the outermost (for
    the 2-level plan this turns the round into K-AVG with K = K2).
    ``sync_opt_state`` additionally averages optimizer state at each
    reduction (beyond-paper option; default False keeps momentum local,
    matching the paper's parameter-only averaging).

    ``reducer`` (comm/): legacy single-reducer override — replaces the
    reducer of EVERY level.  Per-level reducers come from the plan spec.
    Stateful reducers carry ``TrainState.comm_state`` keyed by level name —
    build the initial state with ``init_state(..., plan=...)``.

    ``shards`` (parallel/sharding.py ShardPlan): fsdp>1 meshes pack
    buckets shard-locally and lower each level's mean to
    reduce-scatter + all-gather; pass the same plan to ``init_state``.

    ``telemetry`` (repro/telemetry): ``True`` or a ``TelemetryConfig``
    adds device-side statistics to the round's metrics as cheap ``jnp``
    reductions — per-level pre/post-average parameter divergence (the
    Thm-3.2 discrepancy), cross-learner gradient-norm variance (the
    Jiang & Agrawal period trigger), EF residual mass, and codec
    compression error (``telemetry/...`` keys).  Pure observers: the
    training trajectory is bit-identical to ``telemetry=None``
    (gated by benchmarks/bench_telemetry.py).
    """
    from repro.telemetry.gradstats import (level_stats,
                                           make_grad_observer,
                                           resolve_telemetry)
    tcfg = resolve_telemetry(telemetry)
    p = resolve_plan(hier, reducer, plan, shards=shards)
    sgd_step = make_sgd_step(loss_fn, optimizer, grad_postprocess,
                             microbatch=microbatch,
                             grad_observer=make_grad_observer(
                                 tcfg, p.levels) if tcfg else None)
    _reduce = _make_reduce(constraint_fn, sync_opt_state)
    last = len(p.levels) - 1

    if not elastic:
        def make_phase(inner, level: ReductionLevel, skipped: bool):
            """scan ``inner`` over this level's leading batch dim, then
            apply this level's reduction."""
            def phase(state: TrainState, batches):
                state, metrics = jax.lax.scan(inner, state, batches)
                if not skipped:
                    pre = state.params if tcfg is not None else None
                    state = _reduce(level, state)
                    if tcfg is not None:
                        metrics = dict(metrics)
                        metrics.update(level_stats(
                            tcfg, level, pre, state.params,
                            state.comm_state))
                return state, metrics
            return phase

        phase = sgd_step
        for i, level in enumerate(p.levels):
            phase = make_phase(phase, level, skip_local and i < last)

        def round_fn(state: TrainState, round_batch):
            state, metrics = phase(state, round_batch)
            # metrics leaves: [*batch_dims, pods, G, S] -> scalar means
            metrics = jax.tree.map(lambda m: m.mean(), metrics)
            return state, metrics

        return round_fn

    # elastic build: the per-level masks ride the scan carry next to the
    # TrainState so every nesting depth sees them
    def estep(carry, batch):
        state, active = carry
        state, metrics = sgd_step(state, batch)
        return (state, active), metrics

    def make_ephase(inner, level: ReductionLevel, skipped: bool, i: int):
        def phase(carry, batches):
            carry, metrics = jax.lax.scan(inner, carry, batches)
            state, active = carry
            if not skipped:
                pre = state.params if tcfg is not None else None
                state = _reduce(level, state, active[i])
                if tcfg is not None:
                    # absent learners keep their (stale) params and
                    # count toward divergence — informative, not a bug
                    metrics = dict(metrics)
                    metrics.update(level_stats(
                        tcfg, level, pre, state.params,
                        state.comm_state))
            return (state, active), metrics
        return phase

    ephase = estep
    for i, level in enumerate(p.levels):
        ephase = make_ephase(ephase, level, skip_local and i < last, i)

    def elastic_round_fn(state: TrainState, round_batch, active):
        assert active.shape == (len(p.levels),) + tuple(
            jax.tree.leaves(state.params)[0].shape[:3]), (
            f"active mask must be [n_levels, pods, G, S] = "
            f"{(len(p.levels),)} + learner grid, got {active.shape}")
        (state, _), metrics = ephase((state, active), round_batch)
        metrics = dict(jax.tree.map(lambda m: m.mean(), metrics))
        for i, lvl in enumerate(p.levels):
            metrics[f"active_frac/{lvl.name}"] = \
                active[i].astype(jnp.float32).mean()
        return state, metrics

    return elastic_round_fn


# --------------------------------------------------------------------- #
# step-wise API (serving-style loops / adaptive schedules)
# --------------------------------------------------------------------- #

def make_hier_step(loss_fn: Callable, optimizer: Optimizer,
                   hier: HierAvgParams, *,
                   skip_local: bool = False,
                   constraint_fn: Optional[Callable] = None,
                   reducer: Optional[Any] = None,
                   plan: PlanLike = None,
                   shards: Optional[Any] = None,
                   elastic: bool = False):
    """Single-step variant: per-level counter masking on the step counter.

    ``elastic=True`` builds ``step(state, batch, active)`` with ``active``
    a boolean ``[n_levels, pods, G, S]`` participation mask; a firing
    level reduces over its present learners only, and absent learners
    keep their params/EF untouched (same semantics as the elastic
    ``make_hier_round``).  An all-true mask is bit-identical to the
    dense build.

    Level i fires when ``t % period_i == 0`` and the next level does NOT
    fire (an outer reduction subsumes all inner ones at the same step);
    the outermost level fires whenever its period divides t.  Semantics
    identical to the round API; useful when periods change adaptively
    between rounds (core/schedules.py AdaptivePlan).

    Each level's reduction sits under a ``lax.cond`` on its fire
    predicate, so non-firing steps skip the compress AND the grouped
    collective entirely (they used to run every step and be masked out
    with ``jnp.where`` — paying the full wire and kernel bill K2 times
    per round instead of the plan's billable counts).  The total-period
    equivalence with ``make_hier_round`` is exact for dense/stateless
    reducers (tests/test_plan.py::test_step_api_matches_round_api_3level);
    for error-feedback reducers the round API reduces inner levels at
    outer boundaries too (subsumed in time, not in the nest), so
    trajectories differ by the compression of an already-averaged delta.
    Each level's reduction runs under the scope ``reduce.<level>``, as in
    the round API.
    """
    sgd_step = make_sgd_step(loss_fn, optimizer)
    p = resolve_plan(hier, reducer, plan, shards=shards)
    last = len(p.levels) - 1

    def step(state: TrainState, batch, active=None
             ) -> Tuple[TrainState, Dict]:
        if elastic:
            assert active is not None, \
                "elastic step needs the [n_levels, pods, G, S] active mask"
        state, metrics = sgd_step(state, batch)
        t = state.step  # steps completed
        params, cs = state.params, state.comm_state
        for i, level in enumerate(p.levels):
            if skip_local and i < last:
                continue
            fire = (t % level.period) == 0
            if i < last:
                fire = jnp.logical_and(
                    fire, (t % p.levels[i + 1].period) != 0)
            mask = active[i] if elastic else None
            avg_fn = (lambda lv, mk: lambda tree, cf=None, specs=None:
                      average_over(tree, lv.axes, cf, specs, mk)
                      )(level, mask)
            lvl_cs = cs[level.name] if level.reducer.stateful else ()

            def reduce_branch(operand, level=level, avg_fn=avg_fn,
                              mask=mask):
                pp, lcs = operand
                with jax.named_scope(reduce_scope(level.name)):
                    out, ncs = reduce_with(level.reducer, avg_fn, pp, lcs,
                                           constraint_fn)
                    if mask is not None:
                        out = where_active(mask, out, pp)
                        ncs = where_active(mask, ncs, lcs)
                return out, ncs

            params, lvl_cs = jax.lax.cond(
                fire, reduce_branch, lambda operand: operand,
                (params, lvl_cs))
            if level.reducer.stateful:
                cs = dict(cs)
                cs[level.name] = lvl_cs
        return state._replace(params=params, comm_state=cs), metrics

    return step


# --------------------------------------------------------------------- #
# batch reshaping helpers
# --------------------------------------------------------------------- #

def round_batch_shape(hier: HierAvgParams, topo: HierTopology,
                      per_learner_batch: int) -> Tuple[int, ...]:
    return hier.batch_dims + topo.shape + (per_learner_batch,)


def shard_round_batch(batch, hier: HierAvgParams, topo: HierTopology):
    """Reshape leaves [steps*P*B, ...] -> [*batch_dims, pods, G, S, B, ...]."""
    def rs(x):
        total = hier.steps_per_round * topo.n_learners
        b = x.shape[0] // total
        return x.reshape(hier.batch_dims + topo.shape + (b,) + x.shape[1:])
    return jax.tree.map(rs, batch)
