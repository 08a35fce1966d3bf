"""On-device measurement harness for comm-model calibration.

Times REAL grouped reductions — compress + grouped all-reduce +
finalize, the exact program ``repro.testing.build_ab_reduction`` hands
to benchmarks/bench_bucketing.py and tests/test_pipeline.py — per plan
level, payload size, reducer codec, and bucket count, on the
forced-host-device mesh.  The resulting samples feed
``autotune/calibrate.py``'s least-squares fit of
:class:`repro.core.theory.CommModel`.

CPU caveats (they shape the harness, see tests/test_pipeline.py and the
bench_bucketing subprocess-per-variant note):

* every probe point runs in a FRESH subprocess — on a small CPU box the
  wall-clock of host-device collectives is bimodal run-to-run and
  in-process measurement order perturbs XLA compile state, so no point
  may inherit another's warm LLVM/threadpool state (and the 8-device
  force must happen before jax initializes anyway);
* XLA:CPU lowers all-reduce synchronously (no ``all-reduce-start`` /
  ``-done``), so probes pin the SERIAL bucket schedule — the fit targets
  the serial cost stack, and the pipelined overlap term stays analytic;
* calibration consumes ``min_us`` (the floor is the least
  scheduler-noise-contaminated statistic on an oversubscribed box);
  ``warm_us`` (median) and ``compile_s`` are recorded for diagnostics.

Standalone:

    PYTHONPATH=src python -m repro.autotune.probe --out probe.json \
        [--smoke] [--reps N]
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

PROBE_CAP_LARGE = 4 << 20     # one bucket: isolates the wire-bytes term
PROBE_CAP_SMALL = 32 << 10    # many buckets: exposes per-message latency


@dataclass(frozen=True)
class ProbePoint:
    """One measured configuration: a ``level`` reduction on ``topo``
    with ``n_leaves`` leaves of ``leaf_shape`` fp32, reducer ``spec``,
    bucket cap ``cap`` (serial schedule)."""

    level: str = "global"
    topo: Tuple[int, int, int] = (1, 2, 4)
    spec: str = "mean"
    n_leaves: int = 8
    leaf_shape: Tuple[int, int] = (64, 64)
    cap: int = PROBE_CAP_LARGE

    def describe(self) -> str:
        p, g, s = self.topo
        return (f"{self.level}@{p}x{g}x{s}:{self.spec}:"
                f"{self.n_leaves}x{self.leaf_shape[0]}x"
                f"{self.leaf_shape[1]}:cap{self.cap}")

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "ProbePoint":
        d = json.loads(s)
        d["topo"] = tuple(d["topo"])
        d["leaf_shape"] = tuple(d["leaf_shape"])
        return cls(**d)


def default_grid(smoke: bool = False) -> List[ProbePoint]:
    """The probe grid.  Designed so every CommModel parameter is
    identifiable: two payload sizes per tier (bandwidth slope vs
    intercept), a multi-bucket point (per-message latency), mean vs
    codec reducers at matched payloads (compress_bw), and a 2-pod
    topology whose global level classifies as DCI
    (``CommModel.bw_for_level``).  The smoke grid keeps one point per
    identifiable parameter — enough for the CI fit to be determined,
    nothing more."""
    ici = (1, 2, 4)     # 8 learners, one pod: every level rides ICI
    dci = (2, 2, 2)     # 8 learners, two pods: global crosses DCI
    pts = [
        # ICI bandwidth: two sizes, one bucket each
        ProbePoint("global", ici, "mean", 8, (64, 64)),
        ProbePoint("global", ici, "mean", 8, (160, 160)),
        # per-message latency: same bytes, many buckets
        ProbePoint("global", ici, "mean", 8, (64, 64), PROBE_CAP_SMALL),
        # codec compute: matched sizes, compressing reducers
        ProbePoint("global", ici, "topk:0.05", 8, (160, 160)),
        # DCI tier: 2-pod global, two sizes
        ProbePoint("global", dci, "mean", 8, (64, 64)),
        ProbePoint("global", dci, "mean", 8, (160, 160)),
    ]
    if smoke:
        return pts
    pts += [
        # more sizes per tier for a better-conditioned slope
        ProbePoint("global", ici, "mean", 8, (96, 96)),
        ProbePoint("global", dci, "mean", 8, (96, 96)),
        # sub-global scopes (fewer participants at the same tier)
        ProbePoint("local", ici, "mean", 8, (96, 96)),
        ProbePoint("pod", ici, "mean", 8, (96, 96)),
        ProbePoint("pod", dci, "mean", 8, (96, 96)),
        # codec variety: cast halves the payload, topk ~10x
        ProbePoint("global", ici, "cast:bfloat16", 8, (160, 160)),
        ProbePoint("global", ici, "topk:0.05", 8, (64, 64)),
        ProbePoint("global", dci, "topk:0.05", 8, (96, 96)),
        # per-codec compute rates at the matched 160x160 payload: the
        # fused qint8 pack and the powersgd batched QR run very
        # different arithmetic per dense byte, so calibrate.py fits
        # each family its own compress_bw column from these labels
        ProbePoint("global", ici, "qint8:128", 8, (160, 160)),
        ProbePoint("global", ici, "powersgd:2", 8, (160, 160)),
        # a second multi-bucket latency point
        ProbePoint("global", dci, "mean", 8, (64, 64), PROBE_CAP_SMALL),
    ]
    return pts


def measure_point(point: ProbePoint, reps: int = 12) -> Dict:
    """Measure one probe point IN THIS PROCESS (the subprocess child of
    :func:`run_probe`; callable directly in tests).  Builds the shared
    A/B reduction, AOT-compiles it once, executes ``reps`` times."""
    import jax
    import numpy as np

    from repro.core.plan import LEVEL_AXES
    from repro.core.theory import tier_for
    from repro.testing import build_ab_reduction

    b = build_ab_reduction("serial", point.cap, n_leaves=point.n_leaves,
                           leaf_shape=point.leaf_shape, spec=point.spec,
                           topo_shape=point.topo, level=point.level)
    p_sh = jax.device_put(b["params"], b["shardings"][0])
    s_sh = jax.device_put(b["state"], b["shardings"][1])
    t0 = time.time()
    compiled = b["fn"].lower(p_sh, s_sh).compile()
    compile_s = time.time() - t0
    per_exec = []
    for _ in range(reps):
        t1 = time.time()
        jax.block_until_ready(compiled(p_sh, s_sh))
        per_exec.append(time.time() - t1)

    red = b["reducer"]
    tree1 = b["tree1"]
    pods = point.topo[0]
    n = 1
    for a in LEVEL_AXES[point.level]:
        n *= point.topo[a]
    dense = int(sum(leaf.size * leaf.dtype.itemsize
                    for leaf in jax.tree.leaves(tree1)))
    rec = dataclasses.asdict(point)
    rec.update({
        "n": n,
        # the same classifier CommModel.bw_for_level bills with
        "tier": tier_for(LEVEL_AXES[point.level], pods),
        "dense_bytes": dense,
        "payload_bytes": int(red.payload_bytes(tree1)),
        # per-device bytes on the wire — differs from payload_bytes only
        # for fsdp-sharded layouts (reduce-scatter/all-gather moves 1/F
        # of each sharded bucket); the default grid is fsdp=1 so the
        # calibration fit is unchanged, but the field keeps the billed
        # quantity visible in every probe artifact
        "wire_bytes": int(red.wire_payload_bytes(tree1)),
        "messages": int(red.n_messages(tree1)),
        "has_codec": bool(getattr(red, "has_codec", True)),
        # codec family label ("" for the identity mean): calibrate.py
        # fits a per-codec compress_bw column from samples sharing a
        # label, so qint8 pack and powersgd QR stop being billed at the
        # same rate as topk thresholding
        "codec": str(getattr(red, "codec_name", "")),
        "reps": reps,
        "compile_s": round(compile_s, 3),
        "warm_us": round(float(np.median(per_exec)) * 1e6, 1),
        "min_us": round(min(per_exec) * 1e6, 1),
    })
    return rec


def run_probe(points: Optional[Sequence[ProbePoint]] = None, *,
              reps: int = 12, out: Optional[str] = None,
              smoke: bool = False, timeout: float = 600.0) -> List[Dict]:
    """Measure every point in a FRESH subprocess (see module docstring)
    and optionally write the samples to ``out`` as the probe artifact
    ``autotune/calibrate.py`` consumes."""
    from repro.runtime import refuse_on_tpu
    refuse_on_tpu("repro.autotune.probe")
    points = list(points) if points is not None else default_grid(smoke)
    repo_src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    samples: List[Dict] = []
    for pt in points:
        r = subprocess.run(
            [sys.executable, "-m", "repro.autotune.probe",
             "--point", pt.to_json(), "--reps", str(reps)],
            env=env, capture_output=True, text=True, timeout=timeout)
        if r.returncode != 0:
            raise RuntimeError(
                f"probe point {pt.describe()} failed:\n"
                + r.stderr.strip()[-2000:])
        samples.append(json.loads(r.stdout.strip().splitlines()[-1]))
    if out:
        with open(out, "w") as f:
            json.dump({"meta": {"reps": reps, "smoke": smoke,
                                "n_points": len(samples),
                                "time_field": "min_us"},
                       "samples": samples}, f, indent=2)
    return samples


def load_samples(path: str) -> List[Dict]:
    with open(path) as f:
        d = json.load(f)
    return d["samples"] if isinstance(d, dict) else d


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--point", default=None,
                    help="child mode: measure ONE ProbePoint (json) and "
                         "print its sample record")
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--smoke", action="store_true",
                    help="few probe points (the CI grid)")
    ap.add_argument("--out", default="probe.json")
    args = ap.parse_args()
    if args.point:
        print(json.dumps(measure_point(ProbePoint.from_json(args.point),
                                       args.reps)))
        return
    samples = run_probe(reps=args.reps, out=args.out, smoke=args.smoke)
    for s in samples:
        print(f"{s['level']}@{s['tier']} {s['spec']:14s} "
              f"payload={s['payload_bytes']:>8d}B msgs={s['messages']:>2d} "
              f"min={s['min_us']:>9.1f}us warm={s['warm_us']:>9.1f}us "
              f"compile={s['compile_s']:.2f}s")
    print(f"# wrote {args.out} ({len(samples)} samples)", file=sys.stderr)


if __name__ == "__main__":
    # standalone / child mode: force the 8-host-device mesh.  Importing
    # jax (which `python -m` already did via the package __init__) does
    # NOT initialize its backends — XLA_FLAGS is read when the first
    # device call happens, inside measure_point — so setting it here is
    # still early enough.  Library imports never touch the environment.
    if "--xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    main()
