"""Distributed Hier-AVG training driver.

Runs a zoo architecture at its published widths through the Hier-AVG
round: config, topology, loader, rounds, checkpointing, LR decay.  The
learners go one per device when ``learners x fsdp`` devices are present
(a ``("pod", "group", "local", "fsdp", "model")`` mesh built from the
devices), and are stacked on the first device otherwise.  ``--layers``
cuts the depth only; ``--reduced`` swaps in the tiny smoke-test widths
for CPU runs.

  PYTHONPATH=src python -m repro.launch.train --arch hymba-1.5b --reduced \
      --rounds 5 --k1 2 --k2 4 --learners 4 --s 2
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from contextlib import nullcontext
from typing import Any, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint import save_checkpoint
from repro.comm import DEFAULT_BUCKET_BYTES
from repro.configs import HierAvgParams, get_config
from repro.core import (HierTopology, init_state, make_hier_round,
                        unstack_first)
from repro.data.loader import HierDataLoader
from repro.launch.cases import learner_state_placement
from repro.models import attention, build
from repro.models.stubs import make_train_batch
from repro.optim import sgd, step_decay_lr
from repro.parallel.sharding import shard_plan
from repro.runtime import use_compile_cache


class TrainRun(NamedTuple):
    """What an in-process caller of :func:`main` gets back."""
    state: Any            # TrainState after the last round
    losses: List[float]   # mean loss of each round
    round_fn: Any         # the jitted round (``.lower`` for its HLO)
    batch: Any            # the last round batch
    mesh: Optional[Mesh]  # the learner mesh, None when stacked


def learner_mesh(topo: HierTopology, fsdp: int,
                 devices: Sequence) -> Optional[Mesh]:
    """One learner (times ``fsdp`` shards) per device on a
    ``("pod", "group", "local", "fsdp", "model")`` mesh when
    ``learners x fsdp`` devices are given; None — learners stacked on
    the first device — when the job fits on one device and there are too
    few for the mesh."""
    need = topo.n_learners * fsdp
    if need > 1 and len(devices) >= need:
        return Mesh(np.array(devices[:need]).reshape(
            topo.pods, topo.groups, topo.local, fsdp, 1),
            ("pod", "group", "local", "fsdp", "model"))
    if fsdp > 1:
        raise ValueError(
            f"--fsdp {fsdp} needs {need} devices, have {len(devices)} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} on CPU)")
    return None


def main(argv: Optional[Sequence[str]] = None,
         devices: Optional[Sequence] = None) -> TrainRun:
    """Train from command-line ``argv`` (default ``sys.argv[1:]``) on
    ``devices`` (default ``jax.devices()``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny smoke-test widths (ArchConfig.reduced) "
                         "instead of the published ones")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers; widths "
                         "stay as configured")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--k1", type=int, default=2)
    ap.add_argument("--k2", type=int, default=4)
    ap.add_argument("--learners", type=int, default=4)
    ap.add_argument("--s", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--reducer", default="mean",
                    help="reduction payload spec (comm/): mean | "
                         "cast[:dtype] | topk[:ratio] | randk[:ratio] | "
                         "qint8[:block] | powersgd[:rank]")
    ap.add_argument("--plan", default=None,
                    help="N-level reduction plan spec, e.g. "
                         "'local@4:cast:bfloat16/pod@8/global@16:topk:0.05'"
                         " — wins over --k1/--k2/--reducer")
    ap.add_argument("--bucket-bytes", type=int,
                    default=DEFAULT_BUCKET_BYTES,
                    help="flat-buffer bucket cap for compressed reducers "
                         "(comm/bucket.py); 0 = per-leaf reductions")
    ap.add_argument("--no-overlap", action="store_true",
                    help="pin the serial bucket schedule (default: the "
                         "pipelined engine overlaps each bucket's grouped "
                         "collective with the next bucket's compress)")
    ap.add_argument("--fsdp", type=int, default=1,
                    help="shard the per-learner trailing dims F ways "
                         "(parallel/sharding.py ShardPlan): bucketed "
                         "reductions pack shard-local runs and lower "
                         "each level's mean to reduce-scatter + "
                         "all-gather.  Needs learners*fsdp devices")
    ap.add_argument("--autotune", default=None, metavar="CALIB_JSON",
                    help="calibration artifact (autotune/calibrate.py); "
                         "runs the cost-aware plan search over the real "
                         "param tree and trains the recommended plan — "
                         "wins over --plan/--k1/--k2")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="elastic membership: a deterministic fault "
                         "schedule (repro/elastic) driving per-round "
                         "participation masks, e.g. "
                         "'crash:0.02/flaky:pod:0.2:3/straggler:0.1:1.5' "
                         "— seeded by --seed, straggler deadlines priced "
                         "from the CommModel level walls")
    ap.add_argument("--drop-prob", type=float, default=0.0,
                    help="expected per-member miss probability the "
                         "--autotune plan search bills rounds under "
                         "(theory.py n_eff billing; 0 = dense)")
    ap.add_argument("--telemetry", action="store_true",
                    help="device-side gradient/divergence statistics "
                         "inside the jitted round (repro/telemetry "
                         "gradstats.py; losses bit-identical, extra "
                         "telemetry/* metric keys)")
    ap.add_argument("--metrics-out", default=None, metavar="JSONL",
                    help="write one schema-versioned train_round row "
                         "per round (telemetry/metrics.py JSONL sink)")
    ap.add_argument("--trace-out", default=None, metavar="TRACE_JSON",
                    help="export host-side round spans as a Chrome "
                         "trace (open in ui.perfetto.dev)")
    ap.add_argument("--profile-dir", default=None,
                    help="bracket rounds with jax.profiler trace "
                         "annotations into this directory (TensorBoard "
                         "/ Perfetto device timeline)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    if args.learners % args.s:
        ap.error(f"--learners {args.learners} is not a multiple of "
                 f"--s {args.s}")
    topo = HierTopology(pods=1, groups=args.learners // args.s,
                        local=args.s)
    hier = HierAvgParams(k1=args.k1, k2=args.k2, reducer=args.reducer,
                         plan=args.plan, bucket_bytes=args.bucket_bytes,
                         overlap=not args.no_overlap)
    bundle = build(cfg)
    devices = jax.devices() if devices is None else devices
    mesh = learner_mesh(topo, args.fsdp, devices)
    shards = shard_plan(mesh) if args.fsdp > 1 else None
    controller = None
    if args.autotune:
        from repro.autotune import (Calibration, CostAwarePlan,
                                    search_plans)
        cal = Calibration.load(args.autotune)
        template = jax.eval_shape(
            bundle.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        ranked = search_plans(topo, cal, template=template,
                              B=args.batch,
                              T_ref=args.rounds * hier.steps_per_round,
                              bucket_bytes=hier.bucket_bytes,
                              overlap=hier.overlap, top=3,
                              drop_prob=args.drop_prob)
        print(f"autotune [{args.autotune}; fitted {list(cal.fitted)}"
              + (f"; drop_prob={args.drop_prob:g}" if args.drop_prob
                 else "") + "]:")
        for i, sp in enumerate(ranked):
            print(f"  #{i} {sp.spec}  comm_ms/step="
                  f"{sp.comm_s_per_step * 1e3:.3f} score={sp.score:.3e} "
                  f"feasible={sp.feasible}")
        hier = dataclasses.replace(hier, plan=ranked[0].spec)
        # first telemetry consumer: the controller ingests measured
        # per-round walls / active fracs (observe) so measured-vs-
        # modeled wall is reported at the end of the run
        controller = CostAwarePlan(plan=ranked[0].spec, topo=topo,
                                   comm=cal, template=template,
                                   bucket_bytes=hier.bucket_bytes,
                                   overlap=hier.overlap, shards=shards,
                                   drop_prob=args.drop_prob)
    plan = hier.resolved_plan
    optimizer = sgd(step_decay_lr(
        args.lr, [args.rounds * hier.steps_per_round * 3 // 4], [0.1]))

    key = jax.random.PRNGKey(args.seed)

    def sample(k, n):
        return make_train_batch(k, cfg, batch=n, seq_len=args.seq)

    loader = HierDataLoader(sample, topo=topo, hier=hier,
                            per_learner_batch=args.batch, seed=args.seed,
                            mesh=mesh)
    faults = None
    if args.faults:
        from repro.core.theory import level_reduction_seconds
        from repro.elastic import FaultSchedule, level_deadlines
        template = jax.eval_shape(
            bundle.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        deadlines = level_deadlines(plan, topo, template, None)
        faults = FaultSchedule(args.faults, topo,
                               [lvl.name for lvl in plan.levels],
                               seed=args.seed, deadlines=deadlines)
        counts = dict(plan.counts_per_round())

        def round_wall(fracs):
            return sum(
                counts[lvl.name] * level_reduction_seconds(
                    lvl, topo, template, None,
                    drop_prob=1.0 - float(f))[2]
                for lvl, f in zip(plan.levels, fracs))

    def init(k):
        return init_state(topo, bundle.init, optimizer, k, plan=plan,
                          shards=shards)

    state_shardings = constraint_fn = None
    if mesh is not None:
        state_shardings, constraint_fn = learner_state_placement(
            jax.eval_shape(init, key), mesh, shards)
    hier_round = make_hier_round(bundle.loss_fn, optimizer, hier,
                                 shards=shards, constraint_fn=constraint_fn,
                                 elastic=faults is not None,
                                 telemetry=args.telemetry or None)
    if mesh is not None:
        # traced with the mesh in context, the codec kernels run on the
        # learner rows each device holds (kernels/ops.py)
        def hier_round(*a, _round=hier_round):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return _round(*a)
    # donate the carried TrainState (params/opt_state/EF update in place —
    # no doubled peak memory); the loop only ever uses the returned state
    round_fn = jax.jit(hier_round, out_shardings=(state_shardings, None),
                       donate_argnums=(0,))
    state = jax.jit(init, out_shardings=state_shardings)(key)

    from repro.telemetry import MetricsLogger, SpanTracer
    logger = MetricsLogger(args.metrics_out) if args.metrics_out else None
    tracer = (SpanTracer(profile_dir=args.profile_dir)
              if (args.trace_out or args.profile_dir) else None)
    if tracer is not None:
        tracer.start_profiler()

    print(f"Hier-AVG: {topo.describe()}  plan={plan.describe()} "
          f"arch={cfg.name}"
          + (f"  faults={faults.describe()}" if faults else "")
          + (f"  mesh={dict(mesh.shape)}" if mesh is not None
             else f"  stacked on {devices[0]}"))
    losses = []
    record = attention.DispatchRecord()
    for r in range(args.rounds):
        t0 = time.time()
        with (tracer.span(f"round[{r}]", args={"round": r})
              if tracer else nullcontext()):
            with tracer.span("data") if tracer else nullcontext():
                batch = loader.next_round()
            with (tracer.span("device", cat="device")
                  if tracer else nullcontext()):
                # the first call traces the round: record its attention
                with record if r == 0 else nullcontext():
                    if faults is not None:
                        state, metrics = round_fn(
                            state, batch, jnp.asarray(faults.active(r)))
                    else:
                        state, metrics = round_fn(state, batch)
                if tracer:
                    # bill the device wait to this span, not host_sync
                    tracer.fence(metrics)
            with (tracer.span("host_sync")
                  if tracer else nullcontext()):
                # ONE device->host transfer for the whole metrics dict
                # (the old per-key float() calls each blocked)
                m = jax.device_get(metrics)
        wall = time.time() - t0
        losses.append(float(m["loss"]))
        if faults is not None:
            # host-side schedule mask: no extra device sync for fracs
            fracs = [float(f) for f in faults.active_frac(r)]
            extra = ("  active=" + "/".join(
                f"{lvl.name}:{f:.2f}" for lvl, f in zip(plan.levels, fracs))
                + f" wall~{round_wall(fracs) * 1e3:.2f}ms")
        else:
            fracs, extra = None, ""
        print(f"round {r:3d}  loss={float(m['loss']):.4f} "
              f"acc={float(m.get('accuracy', float('nan'))):.3f} "
              f"({wall:.1f}s, "
              f"{loader.tokens_per_round * args.seq} tokens)"
              + extra, flush=True)
        if r == 0 and record.calls:
            print(record.describe(), flush=True)
        if logger is not None or controller is not None:
            row = {"round": r, "loss": float(m["loss"]),
                   "accuracy": float(m.get("accuracy", float("nan"))),
                   "wall_s": wall, "plan": plan.describe()}
            row.update({k: float(v) for k, v in m.items()
                        if k.startswith("telemetry/")})
            if fracs is not None:
                row["active_frac"] = {
                    lvl.name: f for lvl, f in zip(plan.levels, fracs)}
                row["modeled_wall_s"] = round_wall(fracs)
            if logger is not None:
                logger.log_row("train_round", **row)
            if controller is not None:
                controller.observe(row)

    if tracer is not None:
        tracer.stop_profiler()
        if args.trace_out:
            tracer.export_chrome_trace(args.trace_out)
            print(f"wrote Chrome trace to {args.trace_out} "
                  f"(open in ui.perfetto.dev)")
    if logger is not None:
        logger.close()
        print(f"wrote {args.rounds} train_round rows to "
              f"{args.metrics_out}")
    if controller is not None and controller.observed_wall_s is not None:
        print(f"controller: measured {controller.observed_wall_s * 1e3:.2f}"
              f"ms/round vs modeled comm "
              f"{controller.modeled_round_wall_s * 1e3:.3f}ms "
              f"(x{controller.wall_bias():.0f} incl. compute/host; live "
              f"re-planning is the ROADMAP online-control follow-up)")

    if args.ckpt:
        save_checkpoint(args.ckpt, unstack_first(state.params),
                        step=int(state.step))
        print(f"saved averaged model to {args.ckpt}")
    return TrainRun(state, losses, round_fn, batch, mesh)


if __name__ == "__main__":
    main()
