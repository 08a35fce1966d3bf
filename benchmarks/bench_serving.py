"""Continuous-batching serving A/B: paged PagedServeEngine vs the dense
wave-batched ServeEngine, plus the flash-decode kernel vs its XLA oracle.

Methodology (mirrors bench_bucketing's reduction A/B): every engine
variant runs in a FRESH subprocess so neither inherits the other's warm
XLA/LLVM state, prints one json record on stdout, and the parent
assembles the rows.  The trace is a seeded mixed-length workload — both
prompt lengths AND per-request token budgets vary (the budget plays the
role EOS plays in production: requests finish at different steps).  At
equal slot count the dense engine must decode every wave to the longest
budget and pad every prompt to the wave bucket, while the paged engine
refills a finished slot on the very next token — ``wasted_ratio`` is the
fraction of dense decode-slot steps that produced no kept token, and the
``paged@B`` rows carry ``speedup_vs_dense``.

Rows:
  serving/{dense,paged}@B     tokens/s + p99 latency at B slots over the
                              mixed trace (1 warm run, then timed rounds)
  serving/flashdecode/*       the paged attention kernel A/B at serving
                              shape: XLA gather oracle timing vs the
                              Pallas kernel (compiled on TPU; interpreted
                              on CPU, where only its max |diff| vs the
                              oracle is meaningful, not its wall-clock)

``run(smoke=True)`` (CI) uses 2 timed rounds, one slot count, and a
smaller trace.  Machine-readable records for BENCH_serving.json are left
in ``RECORDS``.

Standalone: PYTHONPATH=src python -m benchmarks.bench_serving [--smoke]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmarks.common import Row
from repro.runtime import refuse_on_tpu

ARCH = "yi-34b"
PAGE_SIZE = 8
PREFILL_CHUNK = 16
MAX_LEN = 128    # headroom: 64-bucket prompts + the 48-token budget tail
ROUNDS = 6
SLOT_COUNTS = (2, 4, 8)

# machine-readable rows for BENCH_serving.json (benchmarks/run.py)
RECORDS: List[Dict] = []


def _trace(n: int, seed: int = 0) -> Tuple[List[np.ndarray], List[int]]:
    """Mixed-length request trace: prompts 4..40 tokens, long-tailed
    per-request token budgets (the EOS stand-in).  Decode lengths in
    production are short-headed with a long tail — most requests stop
    after a few tokens, a minority runs long — which is the workload
    continuous batching targets: a dense wave decodes EVERY request to
    the wave's longest survivor, so its wasted-step ratio is
    1 - mean/max of the wave's lengths (~0.7 here)."""
    rng = np.random.default_rng(seed)
    plens = rng.integers(4, 41, size=n)
    short = rng.integers(2, 9, size=n)
    long_ = rng.integers(24, 49, size=n)
    budgets = [int(b) for b in
               np.where(rng.random(n) < 0.75, short, long_)]
    prompts = [rng.integers(0, 512, size=int(p)).astype(np.int32)
               for p in plens]
    return prompts, budgets


def _measure_engine(engine: str, slots: int, rounds: int,
                    n_requests: int) -> Dict:
    """Child mode: serve the trace with ONE engine variant and report
    throughput/latency.  One warm run compiles everything; ``rounds``
    timed runs follow (tokens/s from the median, p99 from pooled
    per-request latencies)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import build
    from repro.serve import GenerationConfig, PagedServeEngine, ServeEngine

    cfg = get_config(ARCH).reduced()
    bundle = build(cfg, cache_dtype=jnp.float32, decode_impl="auto")
    params = bundle.init(jax.random.PRNGKey(0))
    prompts, budgets = _trace(n_requests)
    gen = GenerationConfig(max_new_tokens=max(budgets), temperature=0.0)

    if engine == "paged":
        eng = PagedServeEngine(bundle, params, slots=slots,
                               page_size=PAGE_SIZE, max_len=MAX_LEN,
                               prefill_chunk=PREFILL_CHUNK,
                               cache_dtype=jnp.float32, gen=gen)
        serve = lambda: eng.serve_queue(prompts, max_new=budgets)  # noqa: E731
    else:
        eng = ServeEngine(bundle, params, max_len=MAX_LEN, gen=gen)
        serve = lambda: eng.serve_queue(prompts, slots=slots,   # noqa: E731
                                        max_new=budgets)

    results = serve()                                  # warm (compiles)
    tokens = sum(r.steps for r in results)
    decode_steps = sum(r.decode_steps for r in results)
    walls, lats = [], []
    for _ in range(rounds):
        t0 = time.time()
        out = serve()
        walls.append(time.time() - t0)
        lats.extend(eng.finish_times.values())
        assert sum(r.steps for r in out) == tokens
    wall = float(np.median(walls))
    return {
        "tokens": tokens,
        "decode_steps": decode_steps,
        # fraction of decode-slot work that produced no kept token
        # (tokens includes the free prefill-sampled first token per req)
        "wasted_ratio": round(
            1.0 - (tokens - len(results)) / max(1, decode_steps), 3),
        "tokens_per_s": round(tokens / wall, 1),
        "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 1),
        "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 1),
        "wall_s": round(wall, 3),
        "requests": len(results),
        "prefill_traces": eng.prefill_traces,
        "decode_traces": eng.decode_traces,
    }


def _measure_flash(which: str, rounds: int) -> Dict:
    """Child mode: the decode-attention kernel at serving shape — the XLA
    gather oracle vs the Pallas flash-decode kernel (compiled on TPU,
    interpreted elsewhere).  Both report timing; the kernel row adds its
    max |diff| vs the oracle (the bit-parity claim lives in
    tests/test_kernels.py — this is the drift canary)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    B, HQ, HKV, D, PAGE, MAXP = 8, 8, 4, 64, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, HQ, D), jnp.float32)
    n_pages = 1 + B * MAXP
    k_pages = jax.random.normal(keys[1], (HKV, n_pages, PAGE, D),
                                jnp.float32)
    v_pages = jax.random.normal(keys[2], (HKV, n_pages, PAGE, D),
                                jnp.float32)
    tables = jnp.asarray(
        np.arange(1, 1 + B * MAXP, dtype=np.int32).reshape(B, MAXP))
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, MAXP * PAGE, size=B),
        jnp.int32)

    impl = "xla" if which == "oracle" else (
        "pallas" if jax.default_backend() == "tpu" else "pallas_interpret")
    fn = jax.jit(lambda *a: kops.flash_decode(*a, impl=impl))
    t0 = time.time()
    out = jax.block_until_ready(fn(q, k_pages, v_pages, tables, lengths))
    compile_s = time.time() - t0
    per = []
    for _ in range(rounds):
        t1 = time.time()
        jax.block_until_ready(fn(q, k_pages, v_pages, tables, lengths))
        per.append(time.time() - t1)
    rec = {
        "impl": impl,
        "us": round(float(np.median(per)) * 1e6, 1),
        "compile_s": round(compile_s, 2),
        "shape": f"B{B}xH{HQ}/{HKV}xD{D}xpage{PAGE}x{MAXP}",
    }
    if which != "oracle":
        ref = kops.flash_decode(q, k_pages, v_pages, tables, lengths,
                                impl="xla")
        rec["max_abs_diff_vs_oracle"] = float(jnp.abs(out - ref).max())
    return rec


def _child(argv: List[str]) -> Dict:
    refuse_on_tpu("benchmarks/bench_serving.py")
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-m", "benchmarks.bench_serving",
                        *argv], env=env, cwd=repo, capture_output=True,
                       text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(r.stderr.strip()[-400:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def run(smoke: bool = False) -> List[Row]:
    RECORDS.clear()
    rounds = 2 if smoke else ROUNDS
    slot_counts = (2,) if smoke else SLOT_COUNTS
    rows: List[Row] = []

    for slots in slot_counts:
        n_requests = 3 * slots if not smoke else 5
        dense_rec = None
        for engine in ("dense", "paged"):
            name = f"serving/{engine}@{slots}"
            try:
                rec = _child(["--engine", engine, "--slots", str(slots),
                              "--rounds", str(rounds),
                              "--requests", str(n_requests)])
            except RuntimeError as e:  # noqa: BLE001
                rows.append((name, 0.0, f"ERROR {e}"))
                continue
            rec["name"] = name
            if engine == "dense":
                dense_rec = rec
            elif dense_rec:
                rec["speedup_vs_dense"] = round(
                    rec["tokens_per_s"] / max(1e-9,
                                              dense_rec["tokens_per_s"]), 2)
            RECORDS.append(rec)
            derived = (f"tok/s={rec['tokens_per_s']} "
                       f"p99_ms={rec['p99_ms']} "
                       f"wasted={rec['wasted_ratio']} "
                       f"steps={rec['decode_steps']} "
                       f"traces={rec['prefill_traces']}"
                       f"+{rec['decode_traces']}"
                       + (f" speedup={rec.get('speedup_vs_dense')}"
                          if engine == "paged" else ""))
            rows.append((name, rec["wall_s"] * 1e6 / max(1, rec["tokens"]),
                         derived))

    for which in ("oracle", "kernel"):
        name = f"serving/flashdecode/{which}"
        try:
            rec = _child(["--flash", which, "--rounds", str(rounds)])
        except RuntimeError as e:  # noqa: BLE001
            rows.append((name, 0.0, f"ERROR {e}"))
            continue
        rec["name"] = name
        RECORDS.append(rec)
        derived = f"impl={rec['impl']} {rec['shape']}"
        if "max_abs_diff_vs_oracle" in rec:
            derived += f" max_diff={rec['max_abs_diff_vs_oracle']:.2e}"
        rows.append((name, rec["us"], derived))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("dense", "paged"), default=None,
                    help="child mode: serve the trace with one engine "
                         "and print a json record")
    ap.add_argument("--flash", choices=("oracle", "kernel"), default=None,
                    help="child mode: time one decode-attention impl")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    args = ap.parse_args()
    if args.engine:
        print(json.dumps(_measure_engine(args.engine, args.slots,
                                         args.rounds, args.requests)))
    elif args.flash:
        print(json.dumps(_measure_flash(args.flash, args.rounds)))
    else:
        for n, us, d in run(smoke=args.smoke):
            print(f"{n},{us:.0f},{d}")
