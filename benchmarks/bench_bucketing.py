"""Per-leaf vs bucketed vs pipelined reduction A/B (comm/bucket.py).

Two sections, both on 8 forced host devices (benchmarks/run.py sets
``--xla_force_host_platform_device_count=8`` for ``--only bucketing``;
this module does the same standalone):

1. **Full training rounds** (Simulator, single device — the PR 3 rows):
   wall-clock per Hier-AVG round, analytic per-learner payload bytes, and
   grouped collectives per global reduction counted from the compiled
   SPMD HLO.  The bucketed rows pin the serial schedule so they stay
   comparable with the PR 3 snapshot.

2. **Reduction-schedule A/B** (the tentpole rows): the jitted global
   reduction of a 12-leaf/3 MB stacked tree over the 8-way learner mesh,
   serial ``Bucketed`` vs the double-buffered ``Pipelined`` engine, at a
   large cap (1 bucket — the schedules coincide) and a small cap
   (12 buckets — the pipeline has stages to overlap).  ``us`` is
   build+compile+``rounds`` executions per round — compile included, like
   every other row in this harness, because program size is where the
   scan-based pipeline wins on CPU: the serial path unrolls one
   compress/collective chain per bucket (O(n_buckets) HLO, one
   ``all-reduce`` pair per bucket), the pipeline compiles one scan body
   (O(1) HLO, collectives hoisted into the loop).  ``collectives`` for
   these rows is the all-reduce *op count in the program* — the
   program-size claim, 2 per bucket serial vs O(1) pipelined.  The
   ``topk:0.05:pipelined`` record carries ``speedup_vs_serial`` — the
   acceptance bar is >= 1.2x over the serial baseline at the same cap.

3. **Codec-kernel A/B** (the codec rows): per codec family, the legacy
   baseline vs the kernel/engine path this PR lands — ``powersgd:2``
   per-leaf (two collectives per leaf, per-leaf QR) vs pipelined
   matrix-bucketed (two collectives per four-leaf bucket, batched QR,
   EF finalized inside the scan), and ``qint8:128:twopass`` per-leaf
   (separate int8 + scale messages) vs the fused single-buffer pack
   pipelined (ONE message per bucket).  Bucket cap ``AB_CODEC_CAP``
   keeps 6 four-leaf buckets so the message-count collapse is visible
   in the records (``messages``); the pipelined rows carry
   ``speedup_vs_serial`` over their per-leaf baseline.  Alongside, the
   ``kernels/*`` records pin Pallas-kernel (interpret mode on CPU) vs
   XLA-oracle parity: ``max_abs_diff_vs_oracle`` per kernel.

4. **Sharded RS/AG A/B** (the fsdp>1 rows): the same global reduction
   with every learner 2-way fsdp-sharded (4 learners x 2 shards = the
   same 8 host devices) vs the replicated baseline at the same learner
   topology.  The sharded rows record the collective op mix (zero bucket
   all-reduces; reduce-scatter + all-gather instead) and
   ``wire_payload_B`` — the per-host wire bytes, half the replicated
   payload because each host compresses and ships only its own shard
   slice.

``run(smoke=True)`` (CI) does 2 rounds instead of 12.  Machine-readable
records for BENCH_reduction.json are left in ``RECORDS``.

Standalone: PYTHONPATH=src python -m benchmarks.bench_bucketing [--smoke]
"""
from __future__ import annotations

import json
import os
import time

if "jax" not in __import__("sys").modules:   # standalone: force devices
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

from typing import Dict, List   # noqa: E402

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.comm import reduce_with                      # noqa: E402
from repro.configs.base import HierAvgParams            # noqa: E402
from repro.core import HierTopology, Simulator          # noqa: E402
from repro.core.plan import resolve_plan                # noqa: E402
from repro.core.topology import global_average, stack_like  # noqa: E402
from repro.launch import hlo_analysis as ha             # noqa: E402
from repro.optim import sgd                             # noqa: E402
from repro.runtime import refuse_on_tpu                 # noqa: E402
from benchmarks.common import Row, cls_setup, timed_run  # noqa: E402

# deep-ish MLP: 7 layers x (w, b) = 14 leaves, so the per-leaf path pays
# 14 grouped collectives where the bucketed path pays 1 (one f32 bucket)
HIDDEN = (48,) * 6
# (row name, reducer spec, bucket_bytes, overlap) — overlap=False pins the
# PR 3 serial schedule so the snapshot rows stay comparable across PRs
VARIANTS = (
    ("mean", "mean", 0, False),              # dense reference (never bucketed)
    ("topk:0.05:perleaf", "topk:0.05", 0, False),
    ("topk:0.05:bucketed", "topk:0.05", 4 << 20, False),
    ("qint8:128:perleaf", "qint8:128", 0, False),
    ("qint8:128:bucketed", "qint8:128", 4 << 20, False),
)
ROUNDS = 12

# -- reduction-schedule A/B: shape and builder shared with
# tests/test_pipeline.py via repro.testing (both must measure the SAME
# program).  Each variant is measured in a FRESH subprocess so neither
# engine inherits the other's warm XLA/LLVM state — on a small CPU box
# the wall-clock of host-device collectives is noisy, and the bucket
# count is chosen high enough that the structural gap (serial compiles
# one compress/collective chain per bucket, the pipeline one scan body)
# dominates that noise.
from repro.testing import (AB_LARGE_CAP, AB_SMALL_CAP,  # noqa: E402
                           build_ab_reduction, build_sharded_ab_reduction,
                           count_allreduce_ops, count_collective_ops)

# machine-readable rows for BENCH_reduction.json (benchmarks/run.py)
RECORDS: List[Dict] = []


def _hlo_collectives(reducer, init_fn) -> int:
    """Grouped all-reduces one global reduction dispatches, from the
    compiled (SPMD-partitioned) HLO over an 8-learner mesh."""
    if jax.device_count() < 8:
        return 0
    topo = HierTopology(1, 2, 4)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(topo.shape),
                ("pod", "group", "local"))

    params1 = jax.eval_shape(init_fn, jax.ShapeDtypeStruct((2,), np.uint32))
    params = jax.eval_shape(lambda p: stack_like(topo, p), params1)
    state = jax.eval_shape(reducer.init_state, params)

    def shard(leaf):
        spec = P("pod", "group", "local") if leaf.ndim >= 3 else P()
        return NamedSharding(mesh, spec)

    def reduction(p, s):
        return reduce_with(reducer, global_average, p, s)

    shardings = (jax.tree.map(shard, params), jax.tree.map(shard, state))
    hlo = jax.jit(reduction, in_shardings=shardings) \
        .lower(params, state).compile().as_text()
    summary = ha.collective_summary(ha.parse_collectives(hlo))
    return summary.get("all-reduce", {}).get("count", 0)


def _ab_measure(sched: str, cap: int, rounds: int, *,
                spec: str = "topk:0.05",
                sharded: bool = False, topo_shape=None) -> Dict:
    """One A/B variant, measured in THIS process (the child side of the
    subprocess-per-variant harness): build the shared reduction
    (repro.testing — same program tests/test_pipeline.py verifies),
    compile, execute ``rounds`` times.  ``us`` is
    (compile + executions) / rounds — compile included, like every other
    row in this harness; ``warm_us``/``min_us`` summarize the per-round
    executions.  ``sharded=True`` builds the fsdp=2 variant (same
    builder tests/test_sharded.py verifies) whose buckets reduce via
    reduce-scatter + all-gather instead of all-reduce."""
    import hashlib
    build = build_sharded_ab_reduction if sharded else build_ab_reduction
    kw = {"topo_shape": tuple(topo_shape)} if topo_shape else {}
    b = build(sched, cap, spec=spec, **kw)
    p_sh = jax.device_put(b["params"], b["shardings"][0])
    s_sh = jax.device_put(b["state"], b["shardings"][1])

    t0 = time.time()
    # execute through the AOT-compiled executable: calling the jitted fn
    # would trace+compile a second time (the jit dispatch cache is
    # separate from the AOT path), double-counting compile in `us`
    compiled = b["fn"].lower(p_sh, s_sh).compile()
    compile_s = time.time() - t0
    per_exec = []
    for _ in range(rounds):
        t1 = time.time()
        out = jax.block_until_ready(compiled(p_sh, s_sh))  # noqa: F841
        per_exec.append(time.time() - t1)
    us = (compile_s + sum(per_exec)) / rounds * 1e6
    txt = compiled.as_text()
    ops = count_collective_ops(txt)
    return {
        "us": round(us, 1),
        "payload_B": b["reducer"].payload_bytes(b["tree1"]),
        # what actually crosses the wire per host: == payload_B when
        # replicated, payload_B / shards for the sharded rows
        "wire_payload_B": b["reducer"].wire_payload_bytes(b["tree1"]),
        "collectives": count_allreduce_ops(txt),
        "reduce_scatter": ops["reduce_scatter"],
        "all_gather": ops["all_gather"],
        # analytic grouped-collective dispatch count — the quantity the
        # fused qint8 pack (2 msgs -> 1 per bucket) and matrix bucketing
        # (2 msgs per leaf -> per bucket) collapse
        "messages": int(b["reducer"].n_messages(b["tree1"])),
        "n_buckets": b["n_buckets"],
        "compile_s": round(compile_s, 2),
        "warm_us": round(float(np.median(per_exec)) * 1e6, 1),
        "min_us": round(min(per_exec) * 1e6, 1),
        "hlo_md5": hashlib.md5(txt.encode()).hexdigest(),
    }


def _reduction_ab(rounds: int) -> List[Row]:
    """Serial vs pipelined reduction schedule, small vs large buckets,
    on the 8-host-device mesh — one fresh subprocess per variant so the
    engines compile and run under identical conditions."""
    import subprocess
    import sys

    refuse_on_tpu("benchmarks/bench_bucketing.py")
    rows: List[Row] = []
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()

    serial_rec: Dict[str, Dict] = {}
    for cap, cap_tag in ((AB_LARGE_CAP, "@1bucket"), (AB_SMALL_CAP, "")):
        for sched in ("serial", "pipelined"):
            name = f"topk:0.05:{sched}{cap_tag}"
            r = subprocess.run(
                [sys.executable, "-m", "benchmarks.bench_bucketing",
                 "--ab-variant", sched, "--ab-cap", str(cap),
                 "--rounds", str(rounds)],
                env=env, cwd=repo, capture_output=True, text=True,
                timeout=900)
            if r.returncode != 0:
                rows.append((f"bucketing/red8/{name}", 0.0,
                             "ERROR " + r.stderr.strip()[-200:]))
                continue
            rec = json.loads(r.stdout.strip().splitlines()[-1])
            md5 = rec.pop("hlo_md5")
            rec["name"] = name
            if sched == "serial":
                serial_rec[cap_tag] = {"us": rec["us"], "md5": md5}
            else:
                base = serial_rec.get(cap_tag)
                if base:
                    rec["speedup_vs_serial"] = round(
                        base["us"] / rec["us"], 2)
                    # single-bucket layouts fall back to the serial
                    # schedule — identical programs; any timing delta in
                    # that pair is harness noise, and the record says so
                    rec["same_hlo_as_serial"] = (md5 == base["md5"])
            RECORDS.append(rec)
            derived = (f"n_buckets={rec['n_buckets']} "
                       f"hlo_all_reduces={rec['collectives']} "
                       f"compile_s={rec['compile_s']:.2f} "
                       f"warm_us={rec['warm_us']:.0f}"
                       + (f" speedup_vs_serial="
                          f"{rec.get('speedup_vs_serial', 0):.2f} "
                          f"same_hlo={rec.get('same_hlo_as_serial')}"
                          if sched == "pipelined" else ""))
            rows.append((f"bucketing/red8/{name}", rec["us"], derived))
    return rows


# codec A/B bucket cap: 24 leaves x 24 KiB -> 4 leaves per bucket -> 6
# buckets, so the per-bucket message bill is visibly below the per-leaf
# one (powersgd 48 -> 12 msgs, fused qint8 48 -> 6) while the pipeline
# still has stages to overlap
AB_CODEC_CAP = 96 << 10


def _codec_ab(rounds: int) -> List[Row]:
    """Per-codec baseline-vs-kernel-path A/B (module docstring §3):
    subprocess-per-variant like :func:`_reduction_ab`, the pipelined row
    of each pair carries ``speedup_vs_serial`` over its per-leaf
    baseline."""
    import subprocess
    import sys

    refuse_on_tpu("benchmarks/bench_bucketing.py")
    rows: List[Row] = []
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()

    pairs = (
        # (row name, child variant, reducer spec); first of each pair is
        # the baseline the second's speedup is measured against
        (("powersgd:2:perleaf", "perleaf", "powersgd:2"),
         ("powersgd:2:pipelined", "pipelined", "powersgd:2")),
        (("qint8:128:twopass:perleaf", "perleaf", "qint8:128:twopass"),
         ("qint8:128:pipelined", "pipelined", "qint8:128")),
    )
    for (base_name, base_var, base_spec), (name, var, spec) in pairs:
        base_rec = None
        for nm, v, sp in ((base_name, base_var, base_spec),
                          (name, var, spec)):
            r = subprocess.run(
                [sys.executable, "-m", "benchmarks.bench_bucketing",
                 "--ab-variant", v, "--ab-cap", str(AB_CODEC_CAP),
                 "--ab-spec", sp, "--rounds", str(rounds)],
                env=env, cwd=repo, capture_output=True, text=True,
                timeout=900)
            if r.returncode != 0:
                rows.append((f"bucketing/codec/{nm}", 0.0,
                             "ERROR " + r.stderr.strip()[-200:]))
                continue
            rec = json.loads(r.stdout.strip().splitlines()[-1])
            rec.pop("hlo_md5", None)
            rec["name"] = nm
            if v == "perleaf":
                base_rec = rec
            elif base_rec:
                rec["speedup_vs_serial"] = round(
                    base_rec["us"] / rec["us"], 2)
                rec["baseline"] = base_name
            RECORDS.append(rec)
            derived = (f"n_buckets={rec['n_buckets']} "
                       f"messages={rec['messages']} "
                       f"hlo_all_reduces={rec['collectives']} "
                       f"compile_s={rec['compile_s']:.2f}"
                       + (f" speedup_vs_serial="
                          f"{rec.get('speedup_vs_serial', 0):.2f}"
                          if v == "pipelined" else ""))
            rows.append((f"bucketing/codec/{nm}", rec["us"], derived))
    return rows


def _kernel_parity() -> List[Row]:
    """Pallas codec-kernel vs XLA-oracle parity records (interpret mode
    — the same kernel program a TPU would run, executed on CPU).  Pinned
    in BENCH_reduction.json so CI catches kernel drift without TPU
    hardware: batched QR compares projectors QQ^T (the kernel's CGS2
    sign convention differs from LAPACK's), fused qint8 must match the
    legacy two-pass quantizer bit-exactly under jit."""
    from repro.comm.quant import dequantize_block, quantize_block
    from repro.kernels import ops

    rows: List[Row] = []
    key = jax.random.PRNGKey(0)

    p = jax.random.normal(key, (8, 96, 4), dtype=jnp.float32)
    proj = lambda q: jnp.einsum("bij,bkj->bik", q, q)  # noqa: E731
    t0 = time.time()
    q_k = ops.batched_qr(p, impl="pallas_interpret")
    qr_us = (time.time() - t0) * 1e6
    qr_diff = float(jnp.max(jnp.abs(
        proj(q_k) - proj(ops.batched_qr(p, impl="xla")))))
    rec = {"name": "kernels/batched_qr", "impl": "pallas_interpret",
           "us": round(qr_us, 1), "shape": list(p.shape),
           "max_abs_diff_vs_oracle": qr_diff}
    RECORDS.append(rec)
    rows.append(("bucketing/kernels/batched_qr", round(qr_us, 1),
                 f"max_abs_diff_vs_oracle={qr_diff:.2e}"))

    x = jax.random.normal(jax.random.fold_in(key, 1), (5, 1000),
                          dtype=jnp.float32)
    roundtrip = jax.jit(lambda x: ops.qint8_unpack(
        ops.qint8_pack(x, 128, impl="pallas_interpret"), x.shape[1],
        impl="pallas_interpret"))
    legacy = jax.jit(lambda x: dequantize_block(
        *quantize_block(x, 128), x.shape[1]))
    t0 = time.time()
    got = roundtrip(x)
    q_us = (time.time() - t0) * 1e6
    q_diff = float(jnp.max(jnp.abs(got - legacy(x))))
    rec = {"name": "kernels/qint8_pack", "impl": "pallas_interpret",
           "us": round(q_us, 1), "shape": list(x.shape), "block": 128,
           "max_abs_diff_vs_oracle": q_diff}
    RECORDS.append(rec)
    rows.append(("bucketing/kernels/qint8_pack", round(q_us, 1),
                 f"max_abs_diff_vs_oracle={q_diff:.2e}"))
    return rows


def _sharded_ab(rounds: int) -> List[Row]:
    """All-reduce vs reduce-scatter+all-gather A/B at the SAME 4-learner
    topology: the fsdp=1 replicated baseline reduces full buckets with
    grouped all-reduces; the fsdp=2 rows (4 learners x 2 shards, all 8
    host devices) must show zero bucket all-reduces, reduce-scatter +
    all-gather instead, and half the wire payload (each host ships only
    the shard slice it owns).  Fresh subprocess per variant, same
    harness rationale as :func:`_reduction_ab`."""
    import subprocess
    import sys

    refuse_on_tpu("benchmarks/bench_bucketing.py")
    rows: List[Row] = []
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()

    variants = (
        # replicated baseline on the sharded rows' learner topology
        ("topk:0.05:serial@4L",
         ["--ab-variant", "serial", "--ab-topo", "1,2,2"]),
        ("topk:0.05:serial:sharded",
         ["--ab-variant", "serial", "--ab-sharded"]),
        ("topk:0.05:pipelined:sharded",
         ["--ab-variant", "pipelined", "--ab-sharded"]),
    )
    for name, extra in variants:
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_bucketing", *extra,
             "--ab-cap", str(AB_SMALL_CAP), "--rounds", str(rounds)],
            env=env, cwd=repo, capture_output=True, text=True,
            timeout=900)
        if r.returncode != 0:
            rows.append((f"bucketing/sharded/{name}", 0.0,
                         "ERROR " + r.stderr.strip()[-200:]))
            continue
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        rec.pop("hlo_md5", None)
        rec["name"] = name
        RECORDS.append(rec)
        derived = (f"n_buckets={rec['n_buckets']} "
                   f"all_reduce={rec['collectives']} "
                   f"rs={rec['reduce_scatter']} ag={rec['all_gather']} "
                   f"wire_B={rec['wire_payload_B']} "
                   f"payload_B={rec['payload_B']}")
        rows.append((f"bucketing/sharded/{name}", rec["us"], derived))
    return rows


def run(smoke: bool = False) -> List[Row]:
    RECORDS.clear()
    setup = cls_setup(hidden=HIDDEN)
    rounds = 2 if smoke else ROUNDS
    topo = HierTopology(1, 2, 2)
    rows: List[Row] = []
    for name, spec, bucket_bytes, overlap in VARIANTS:
        hier = HierAvgParams(k1=2, k2=4, reducer=spec,
                             bucket_bytes=bucket_bytes, overlap=overlap)
        sim = Simulator(setup["loss_fn"], setup["init_fn"], setup["sample"],
                        topo=topo, hier=hier, optimizer=sgd(0.1),
                        per_learner_batch=16,
                        eval_batch=setup["eval_batch"], seed=7)
        res, us = timed_run(sim, rounds)
        payload = sim.payload_bytes_per_reduction()
        global_red = resolve_plan(hier).levels[-1].reducer
        colls = _hlo_collectives(global_red, setup["init_fn"])
        derived = (f"payload_B={payload} collectives={colls} "
                   f"eval_acc={res.final_eval_acc:.4f}")
        rows.append((f"bucketing/{name}", us, derived))
        RECORDS.append({"name": name, "us": round(us, 1),
                        "payload_B": payload, "collectives": colls})
    rows.extend(_reduction_ab(rounds))
    rows.extend(_codec_ab(rounds))
    rows.extend(_kernel_parity())
    rows.extend(_sharded_ab(rounds))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ab-variant",
                    choices=("serial", "pipelined", "perleaf"),
                    default=None, help="child mode: measure ONE "
                    "reduction-schedule variant and print a json record")
    ap.add_argument("--ab-cap", type=int, default=AB_SMALL_CAP)
    ap.add_argument("--ab-spec", default="topk:0.05",
                    help="child mode: reducer spec for the variant "
                         "(the codec A/B passes powersgd/qint8 here)")
    ap.add_argument("--ab-sharded", action="store_true",
                    help="child mode: measure the fsdp=2 sharded variant "
                         "(reduce-scatter + all-gather buckets)")
    ap.add_argument("--ab-topo", default=None,
                    help="child mode: learner topology override, e.g. "
                         "'1,2,2' for the 4-learner replicated baseline")
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    args = ap.parse_args()
    if args.ab_variant:
        topo = tuple(int(x) for x in args.ab_topo.split(",")) \
            if args.ab_topo else None
        print(json.dumps(_ab_measure(args.ab_variant, args.ab_cap,
                                     args.rounds, spec=args.ab_spec,
                                     sharded=args.ab_sharded,
                                     topo_shape=topo)))
    else:
        for n, us, d in run(smoke=args.smoke):
            print(f"{n},{us:.0f},{d}")
