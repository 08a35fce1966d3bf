"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production meshes, record memory/cost/collective analysis for §Roofline.

Run:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
      --out experiments/dryrun

Exit status is non-zero if any case fails to lower/compile — a failure here
is a sharding bug in the framework, per the assignment.
"""

import argparse
import json
import os
import time
import traceback

from repro.configs import ALL_ARCHS, INPUT_SHAPES, get_config
from repro.launch.cases import build_case, parse_layout
from repro.launch import hlo_analysis as ha
from repro.launch.analytic import analytic_roofline


def applicable_shapes(cfg):
    """All 10 pool archs support all 4 shapes (long_500k via rolling-window
    SWA for full-attention archs, MLA latents for deepseek-v2, native state
    for ssm/hybrid) — see DESIGN.md long_500k policy."""
    return list(INPUT_SHAPES)


def run_case(arch: str, shape: str, multi_pod: bool, *, case_kwargs=None,
             layout=None, calibration=None) -> dict:
    case_kwargs = case_kwargs or {}
    cfg = get_config(arch)
    if layout is not None:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, layout=layout)
    case = build_case(cfg, shape, multi_pod=multi_pod, **case_kwargs)
    t0 = time.time()
    with case.mesh:
        lowered = case.jitted.lower(*case.arg_specs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost_list = compiled.cost_analysis()
        cost = cost_list if isinstance(cost_list, dict) else cost_list[0]
        hlo = compiled.as_text()
    colls = ha.parse_collectives(hlo)
    chips = case.mesh.devices.size
    # MODEL_FLOPS = 6 N_active D per step (train fwd+bwd); serving fwd = 2ND
    tokens = _tokens_per_step(cfg, shape)
    n_active = cfg.active_param_count()
    mult = 6.0 if INPUT_SHAPES[shape].kind == "train" else 2.0
    model_flops_total = mult * n_active * tokens * case.steps
    terms = ha.roofline_terms(
        cost, colls, model_flops_per_device=model_flops_total / chips,
        steps=case.steps)
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2pod-512" if multi_pod else "1pod-256",
        "chips": chips,
        "notes": case.notes,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_est_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "collectives": {k: v for k, v in
                        ha.collective_summary(colls).items()},
        # HLO-derived terms are PER-SCAN-BODY (XLA cost analysis is
        # trip-count blind); the analytic model below gives per-step
        # magnitudes — see launch/analytic.py and EXPERIMENTS.md §Roofline.
        "roofline_hlo_per_body": terms,
        # --autotune: the artifact's roofline is costed with the SAME
        # calibration the recommended plan was chosen by
        "roofline": analytic_roofline(
            cfg, shape, multi_pod=multi_pod,
            hier=case_kwargs.get("hier"),
            comm_model=calibration).as_dict(),
    }
    return rec


def _tokens_per_step(cfg, shape) -> float:
    s = INPUT_SHAPES[shape]
    if s.kind == "decode":
        return s.global_batch          # one new token per sequence
    return s.global_batch * s.seq_len


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--layout", default=None,
                    help="override layout 'GxSxFxTP[:micro]' (hillclimb)")
    ap.add_argument("--k1", type=int, default=None)
    ap.add_argument("--k2", type=int, default=None)
    ap.add_argument("--plan", default=None,
                    help="N-level reduction plan spec (wins over "
                         "--k1/--k2), e.g. "
                         "'local@4:cast:bfloat16/pod@8/global@16:topk:0.05'")
    ap.add_argument("--no-overlap", action="store_true",
                    help="pin the serial bucket schedule when lowering "
                         "(default: pipelined/overlapped engine)")
    ap.add_argument("--autotune", default=None, metavar="CALIB_JSON",
                    help="calibration artifact (autotune/calibrate.py): "
                         "lower the plan the cost-aware search recommends "
                         "for each arch instead of --plan/--k1/--k2")
    args = ap.parse_args()
    # the production meshes as host devices; set before the first JAX
    # call, which creates the backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    cases = []
    archs = ALL_ARCHS if (args.all or not args.arch) else [args.arch]
    for a in archs:
        shapes = applicable_shapes(get_config(a)) \
            if (args.all or not args.shape) else [args.shape]
        for s in shapes:
            meshes = [False, True] if args.both_meshes else [args.multi_pod]
            for mp in meshes:
                cases.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    # --autotune: one artifact load, one plan search per (arch, layout,
    # mesh) — the recommendation does not depend on the input shape
    autotune_cal = None
    autotune_memo = {}
    if args.autotune:
        from repro.autotune import Calibration
        autotune_cal = Calibration.load(args.autotune)
    failures = 0
    for a, s, mp in cases:
        tag = f"{a}__{s}__{'2pod' if mp else '1pod'}"
        lay = parse_layout(args.layout) if args.layout else None
        if lay is not None:
            tag += f"__L{args.layout.replace(':', 'm')}"
        kw = {}
        if args.autotune:
            from repro.autotune import recommend_plan
            from repro.configs.base import HierAvgParams
            from repro.core.theory import param_template
            from repro.core.topology import HierTopology
            cfg = get_config(a)
            layc = lay or cfg.layout
            key = (a, args.layout, mp)
            best = autotune_memo.get(key)
            if best is None:
                best = recommend_plan(
                    HierTopology(pods=2 if mp else 1, groups=layc.groups,
                                 local=layc.local),
                    autotune_cal,
                    template=param_template(
                        cfg.param_count(),
                        n_leaves=max(1, 8 * cfg.n_layers)),
                    overlap=not args.no_overlap)
                autotune_memo[key] = best
                print(f"autotune {a}: {best.spec} "
                      f"(comm_ms/step={best.comm_s_per_step * 1e3:.3f}, "
                      f"feasible={best.feasible})", flush=True)
            kw["hier"] = HierAvgParams(plan=best.spec,
                                       overlap=not args.no_overlap)
            tag += "__AUTO"
        elif args.plan:
            from repro.configs.base import HierAvgParams
            hp = HierAvgParams(plan=args.plan,
                               overlap=not args.no_overlap)
            kw["hier"] = hp
            tag += "__P" + args.plan.replace("/", "-").replace(":", "_")
        elif args.k1 or args.k2 or args.no_overlap:
            from repro.configs.base import HierAvgParams
            hp = HierAvgParams(k1=args.k1 or 4, k2=args.k2 or 8,
                               overlap=not args.no_overlap)
            kw["hier"] = hp
            tag += f"__K{hp.k1}-{hp.k2}"
        try:
            rec = run_case(a, s, mp, layout=lay, case_kwargs=kw,
                           calibration=autotune_cal)
            path = os.path.join(args.out, tag + ".json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=2)
            r = rec["roofline"]
            print(f"OK   {tag:58s} compile={rec['compile_s']:6.1f}s "
                  f"bottleneck={r['bottleneck']:10s} "
                  f"c/m/coll(ms)={1e3*r['compute_s']:.2f}/"
                  f"{1e3*r['memory_s']:.2f}/{1e3*r['collective_s']:.2f} "
                  f"peakGiB={rec['memory']['peak_est_bytes']/2**30:.2f}",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run case(s) failed")


if __name__ == "__main__":
    main()
