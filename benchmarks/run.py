"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Mapping:
  bench_k2          -> paper Fig. 1 (train acc) + Fig. 2 (test acc) K2 sweep
  bench_k1_s        -> paper Fig. 3 (K1 sweep) + Fig. 4 (S sweep)
  bench_vs_kavg     -> paper Table 1 (Hier-AVG vs K-AVG, P in {16,32,64})
  bench_large_proxy -> paper Fig. 5 (larger-scale vs K-AVG)
  bench_adaptive_k2 -> paper §3.3 'adaptive K2' remark (beyond-paper ablation)
  bench_layouts     -> beyond-paper per-arch layout optimization sweep
  bench_comm        -> the paper's communication-saving claim, quantified
  bench_compression -> reducer sweep: payload bytes vs converged accuracy
  bench_bucketing   -> per-leaf vs bucketed reduction A/B (comm/bucket.py)
  bench_autotune    -> probe -> calibrate -> recommend pipeline (autotune/)
  bench_serving     -> paged continuous batching vs dense wave serving A/B
                       + flash-decode kernel vs oracle (serve/, kernels/)
  bench_elastic     -> elastic membership: 20%-dropout convergence vs the
                       Thm 3.2 bars, masked-reduction overhead, fleet
                       reshape round-trip, fault determinism (elastic/)
  bench_telemetry   -> telemetry plane: gradstats bit-identity on the
                       serial/pipelined/fsdp=2 engines, logger host
                       overhead, measured-vs-modeled reduction walls,
                       Chrome-trace + JSONL round-trips (telemetry/)
  roofline          -> §Roofline rows from the dry-run artifacts (if present)

``bench_bucketing`` additionally writes machine-readable
``BENCH_reduction.json`` at the repo root (schema per row: name, us,
payload_B, collectives; the serial-vs-pipelined A/B rows add n_buckets,
compile_s, warm_us, min_us, speedup_vs_serial, same_hlo_as_serial; the
sharded fsdp=2 A/B rows add wire_payload_B plus reduce_scatter /
all_gather op counts — CI asserts zero bucket all-reduces and half the
replicated wire payload on those) so
successive PRs can track the reduction-path perf trajectory; CI uploads
it as an artifact and fails if the A/B rows go missing.  Likewise
``bench_autotune`` writes ``BENCH_autotune.json`` (the ``calibration``
record with fitted CommModel constants + round-trip fit error, the
``recommended/*`` plan-search records, and the ``controller/*`` adapted
periods); CI runs its probe+calibrate smoke and fails if the calibration
or recommended-plan records go missing.  ``bench_serving`` writes
``BENCH_serving.json`` (per-slot-count dense/paged rows with
tokens_per_s, p99_ms, wasted_ratio, decode_steps and speedup_vs_dense on
the paged rows, plus the flashdecode oracle/kernel pair); CI runs its
2-round smoke and fails if the paged+dense or flashdecode rows go
missing.  ``bench_elastic`` writes ``BENCH_elastic.json`` (the
fault-free vs 20%-pod-dropout convergence pair with loss_gap /
thm32_bar / within_bars, the masked-overhead A/B, the 4->6->4 reshape
round-trip flags, and the cross-process fault-schedule hash); CI runs
its smoke and asserts within_bars, determinism, and the reshape
bit-preservation flags.  ``bench_telemetry`` writes
``BENCH_telemetry.json`` (the three per-engine bit_identical flags, the
logger host-overhead A/B vs its documented ceiling, the
measured-vs-modeled wall agreement with per-point rel errors, and the
trace/JSONL round-trip flags); CI runs its smoke and asserts
bit-identity on every engine, the overhead ceiling, within_tolerance,
and the export flags.

Run: PYTHONPATH=src python -m benchmarks.run [--only fig1] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module name")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal rounds (CI regression canary)")
    args = ap.parse_args()

    if args.only is not None and args.only in "bench_bucketing":
        # >= 8 host devices so bench_bucketing can compile the
        # SPMD-partitioned reduction and count its grouped collectives
        # from HLO; set before the suites import jax (below), and ONLY
        # for a filtered bucketing run so every other suite's timings
        # keep their single-device baseline (in unfiltered full runs
        # bench_bucketing reports collectives=0 instead — use
        # `--only bucketing` for the collective counts, as CI does)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()

    from repro.runtime import refuse_on_tpu
    refuse_on_tpu("benchmarks/run.py")
    from benchmarks import (bench_adaptive_k2, bench_autotune,
                            bench_bucketing, bench_comm, bench_compression,
                            bench_elastic, bench_k1_s, bench_k2,
                            bench_large_proxy, bench_layouts,
                            bench_serving, bench_telemetry, bench_vs_kavg,
                            roofline)
    suites = [
        ("bench_k2", bench_k2.run),
        ("bench_k1_s", bench_k1_s.run),
        ("bench_vs_kavg", bench_vs_kavg.run),
        ("bench_large_proxy", bench_large_proxy.run),
        ("bench_adaptive_k2", bench_adaptive_k2.run),
        ("bench_layouts", bench_layouts.run),
        ("bench_comm", bench_comm.run),
        ("bench_compression", bench_compression.run),
        ("bench_bucketing",
         lambda: bench_bucketing.run(smoke=args.smoke)),
        ("bench_autotune",
         lambda: bench_autotune.run(smoke=args.smoke)),
        ("bench_serving",
         lambda: bench_serving.run(smoke=args.smoke)),
        ("bench_elastic",
         lambda: bench_elastic.run(smoke=args.smoke)),
        ("bench_telemetry",
         lambda: bench_telemetry.run(smoke=args.smoke)),
        ("roofline", roofline.run),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        try:
            for row in fn():
                n, us, derived = row
                print(f"{n},{us:.0f},{derived}", flush=True)
        except Exception:  # noqa: BLE001
            failed += 1
            print(f"{name},0,ERROR", flush=True)
            traceback.print_exc()
        records = {"bench_bucketing": (bench_bucketing, "BENCH_reduction"),
                   "bench_autotune": (bench_autotune, "BENCH_autotune"),
                   "bench_serving": (bench_serving, "BENCH_serving"),
                   "bench_elastic": (bench_elastic, "BENCH_elastic"),
                   "bench_telemetry": (bench_telemetry,
                                       "BENCH_telemetry")}
        if name in records and records[name][0].RECORDS:
            # smoke runs go to a sibling file so they never clobber the
            # checked-in full-round snapshot (README "Bucketed reductions")
            mod, stem = records[name]
            fname = f"{stem}.smoke.json" if args.smoke else f"{stem}.json"
            out = os.path.join(_REPO_ROOT, fname)
            with open(out, "w") as f:
                json.dump(mod.RECORDS, f, indent=2)
            print(f"# wrote {out}", file=sys.stderr, flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
