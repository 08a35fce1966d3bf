#!/usr/bin/env python3
"""Smoke check that the system's two entry points run on a TPU.

    python3 chip_smoke.py              # one chip: train, serve, codec kernels
    python3 chip_smoke.py --chips 4    # the learner mesh on a 2x2 host

One chip, in order, each phase through the code a user would run:

  * train — ``repro.launch.train`` on hymba-1.5b at its published widths,
    cut in depth only, 4 learners as ``HierTopology(1, 2, 2)`` stacked on
    the chip, plan ``local@2:qint8:128/global@4`` (the local level's
    codec runs the compiled qint8 pack/unpack kernels), SGD;
  * serve — ``repro.launch.serve --paged`` on qwen2-vl-2b at its
    published widths and depth with bf16 KV pages, then one decode step
    through the Pallas ``flash_decode`` kernel against the XLA oracle;
  * codec — ``qint8_pack``/``qint8_unpack`` against ``kernels/ref.py`` at
    the 4-learner x 4 MiB bucket row, and ``batched_qr`` against the
    Householder oracle at the PowerSGD panel shapes of the trained tree.

``--chips 4`` runs only the train phase twice: learners one per chip on
a ``(1, 2, 2, 1, 1)`` mesh, then stacked on the first chip, and compares
the losses.

Weights and data are random from fixed seeds.  Any failed check raises
and the exit status is non-zero.  The last line of standard output is
the JSON verdict with the device as JAX reports it; it is printed only
when every phase passed, on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

_SRC = Path(__file__).resolve().parent / "src"


@dataclasses.dataclass
class Settings:
    """Sizes and expectations of one smoke run."""
    platform: str = "tpu"
    kernel_impl: str = "pallas"      # impl the codec parity phase calls
    expect_kernels: bool = True      # compiled programs hold tpu_custom_call
    # Depth 2 of 32: 4 learners' f32 params, grads and buckets plus the
    # activations of 2 x 512 tokens per learner fit 16 GB at 2 layers;
    # the compiler needs 16.2 GB at 3.  --no-overlap: the serial bucket
    # engine packs hymba's buckets ragged, where the pipelined engine
    # pads every bucket to the largest leaf, the 32128 x 1600 embedding
    # (2.8x the parameter bytes)
    train_argv: Tuple[str, ...] = (
        "--arch", "hymba-1.5b", "--layers", "2",
        "--learners", "4", "--s", "2",
        "--plan", "local@2:qint8:128/global@4", "--no-overlap",
        "--rounds", "3", "--batch", "2", "--seq", "512", "--lr", "0.01")
    serve_argv: Tuple[str, ...] = (
        "--arch", "qwen2-vl-2b", "--paged", "--requests", "4",
        "--slots", "4", "--prompt-len", "64", "--max-new", "16",
        "--block-size", "16")
    bucket_row: int = (4 << 20) // 4  # f32 elements in a 4 MiB bucket
    qint8_block: int = 128
    powersgd_rank: int = 2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """Backend compile time and persistent-cache hits, from JAX's own
    monitoring events, so a warm run can show that it compiled less."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


class Phase:
    """Times one phase and prints its compile work and peak memory."""

    def __init__(self, name: str, log: CompileLog):
        self.name, self.log = name, log

    def __enter__(self):
        print(f"== {self.name}", flush=True)
        self.t0 = time.perf_counter()
        self.c0 = self.log.snapshot()
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        import jax
        s, n, h = (a - b for a, b in zip(self.log.snapshot(), self.c0))
        wall = time.perf_counter() - self.t0
        stats = jax.devices()[0].memory_stats() or {}
        print(f"{self.name}: wall {wall:.1f}s, backend compile {s:.1f}s "
              f"in {n} programs, {h} persistent-cache hits, "
              f"peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use', 'not reported')}",
              flush=True)
        return False


def require_platform(platform: str):
    """The devices, if the default backend is ``platform``; otherwise a
    SmokeFailure — there is no fallback to another backend."""
    import jax
    devices = jax.devices()
    check(devices[0].platform == platform,
          f"need a {platform} device, JAX found {devices[0].platform} "
          f"({devices[0].device_kind})")
    print(f"devices: {len(devices)} x {devices[0].platform} "
          f"{devices[0].device_kind}", flush=True)
    return devices


def _finite(xs: Sequence[float]) -> bool:
    import math
    return len(xs) > 0 and all(math.isfinite(x) for x in xs)


def train(s: Settings, devices):
    """One ``repro.launch.train`` run on ``devices``, its losses and its
    compiled round checked; returns ``(losses, TrainRun)``."""
    from repro.launch import train as train_cli
    run = train_cli.main(list(s.train_argv), devices=devices)
    print(f"losses: {run.losses}", flush=True)
    check(_finite(run.losses), f"non-finite training loss {run.losses}")
    hlo = run.round_fn.lower(run.state, run.batch).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    print(f"compiled round holds tpu_custom_call: {has_kernel}", flush=True)
    if s.expect_kernels:
        check(has_kernel, "the qint8 codec did not compile to a kernel")
    return run.losses, run


def serve(s: Settings) -> None:
    """``repro.launch.serve --paged``, then one decode step at the
    served shapes through the engine's kernel and through the oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve as serve_cli
    from repro.models import build

    engine, results = serve_cli.main(list(s.serve_argv))
    cfg = engine.bundle.cfg
    want_new = int(s.serve_argv[s.serve_argv.index("--max-new") + 1])
    for r in results:
        check(len(r.tokens) == want_new
              and bool(np.all((r.tokens >= 0)
                              & (r.tokens < cfg.padded_vocab))),
              f"request {r.request_id} returned {r.tokens}")

    # every slot active, reading whole sequences out of the pool the
    # requests wrote
    slots, mp = engine.slots, engine.max_pages_per_seq
    tables = (1 + np.arange(slots * mp, dtype=np.int32)).reshape(slots, mp)
    check(int(tables.max()) < engine.alloc.n_pages,
          "pool too small for the parity tables")
    lengths = np.full((slots,), engine.max_len - 1, np.int32)
    args = (engine.params, jnp.arange(slots, dtype=jnp.int32), engine.pages,
            jnp.asarray(tables), jnp.asarray(lengths),
            jnp.ones((slots,), bool))
    with jax.default_matmul_precision("highest"):
        kernel_step = jax.jit(engine.bundle.decode_step_paged).lower(
            *args).compile()
        oracle_step = jax.jit(
            build(cfg, decode_impl="xla").decode_step_paged)
        got = np.asarray(kernel_step(*args)[0], np.float64)
        want = np.asarray(oracle_step(*args)[0], np.float64)
    has_kernel = "tpu_custom_call" in kernel_step.as_text()
    print(f"compiled decode step holds tpu_custom_call: {has_kernel}",
          flush=True)
    if s.expect_kernels:
        check(has_kernel, "flash_decode did not compile to a kernel")
    diff = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    # Both steps run the same f32 model at HIGHEST matmul precision over
    # the same bf16 pages; only the attention differs: the kernel's
    # online softmax over pages against the oracle's softmax over the
    # gathered sequence.  Their reassociation error is ~len * 2^-24
    # (~5e-6) per layer; 1e-4 of the logit scale leaves room for 28
    # layers of that, while one bf16 rounding (2^-8 ~ 4e-3) fails it.
    tol = 1e-4 * scale
    print(f"flash_decode logits vs xla oracle: max abs diff {diff:.3e} "
          f"(logit scale {scale:.3e}, tolerance {tol:.3e})", flush=True)
    check(np.all(np.isfinite(got)), "non-finite decode logits")
    check(diff <= tol, f"flash_decode logit diff {diff} > {tol}")


def panel_shapes(params, rank: int) -> List[Tuple[int, int]]:
    """Distinct ``(a, r)`` PowerSGD panels of a stacked-learner param
    tree: every per-learner matrix ``[a, ...]`` with ``min(a, b) > r``."""
    import jax

    from repro.comm.lowrank import _matrix_dims
    from repro.comm.reducer import N_LEARNER_AXES

    shapes = set()
    for leaf in jax.tree.leaves(params):
        if leaf.ndim - N_LEARNER_AXES >= 2:
            a, b = _matrix_dims(leaf.shape[N_LEARNER_AXES:])
            if min(a, b) > rank:
                shapes.add((a, rank))
    return sorted(shapes)


def codec(s: Settings, panels: Sequence[Tuple[int, int]]) -> None:
    """The codec kernels against their oracles, on this device: qint8 at
    the bucket row, ``batched_qr`` at the PowerSGD ``panels``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    learners, n, block = 4, s.bucket_row, s.qint8_block
    x = jax.random.normal(jax.random.PRNGKey(1), (learners, n), jnp.float32)
    x = x * jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (learners, n)))
    # jitted, as the reducer calls them inside the round
    pack = jax.jit(ops.qint8_pack, static_argnums=1,
                   static_argnames="impl")
    unpack = jax.jit(ops.qint8_unpack, static_argnums=1,
                     static_argnames="impl")
    wire = pack(x, block, impl=s.kernel_impl)
    wire_ref = pack(x, block, impl="xla")
    back = unpack(wire, n, impl=s.kernel_impl)
    back_ref = unpack(wire_ref, n, impl="xla")
    # the codec is exact integer/bitcast work: no tolerance
    same_wire = bool(jnp.array_equal(wire, wire_ref))
    same_back = bool(jnp.array_equal(back, back_ref))
    print(f"qint8_pack [{learners}, {n}] block {block}: wire bit-identical "
          f"{same_wire}; unpack bit-identical {same_back}", flush=True)
    check(same_wire and same_back, "qint8 kernels differ from the oracle")

    for i, (a, r) in enumerate(panels):
        p = jax.random.normal(jax.random.PRNGKey(10 + i), (learners, a, r))
        q = np.asarray(ops.batched_qr(p, impl=s.kernel_impl), np.float64)
        with jax.default_matmul_precision("highest"):
            q_ref = np.asarray(ops.batched_qr(p, impl="xla"), np.float64)
        # ||Q Q^T - R R^T||_F from r x r Gram matrices (the a x a
        # projectors of a vocabulary-sized panel do not fit)
        qq = np.einsum("nar,nas->nrs", q, q)
        rr = np.einsum("nar,nas->nrs", q_ref, q_ref)
        qr = np.einsum("nar,nas->nrs", q, q_ref)
        err2 = (np.sum(qq ** 2, (1, 2)) + np.sum(rr ** 2, (1, 2))
                - 2 * np.sum(qr ** 2, (1, 2)))
        rel = float(np.sqrt(max(err2.max(), 0.0) / r))
        # both are f32 orthonormalizations of a well-conditioned Gaussian
        # panel: they agree to ~sqrt(a) * 2^-24 (< 1e-5 for a <= 32k);
        # a bf16 pass (2^-8) would miss 1e-4 by an order of magnitude
        print(f"batched_qr [{learners}, {a}, {r}]: projector rel diff "
              f"{rel:.3e} (tolerance 1e-4)", flush=True)
        check(rel <= 1e-4, f"batched_qr projector diff {rel} at a={a}")


def one_chip(s: Settings, devices) -> None:
    log = CompileLog()
    with Phase("train", log):
        _, run = train(s, devices[:1])
        panels = panel_shapes(run.state.params, s.powersgd_rank)
        del run                   # free the trained state before serving
    with Phase("serve", log):
        serve(s)
    with Phase("codec", log):
        codec(s, panels)


def mesh_vs_stacked(s: Settings, devices) -> None:
    """Learners one per chip, then stacked on one chip: same losses."""
    import jax
    import numpy as np

    log = CompileLog()
    with Phase("train, one learner per chip", log):
        mesh_losses, run = train(s, devices[:4])
        check(run.mesh is not None, "no learner mesh was built")
        for leaf in jax.tree.leaves(run.state.params):
            shards = leaf.addressable_shards
            owners = {(sh.device.id, tuple(
                sl.start or 0 for sl in sh.index[:3])) for sh in shards}
            check(len(shards) == 4 and len(owners) == 4
                  and len({d for d, _ in owners}) == 4
                  and all(sh.data.shape[:3] == (1, 1, 1)
                          for sh in shards),
                  f"param {leaf.shape} is not one learner per device: "
                  f"{sorted(owners)}")
        print("params: each device holds exactly one learner's shard",
              flush=True)
    with Phase("train, learners stacked on one chip", log):
        stacked_losses, _ = train(s, devices[:1])
    diff = np.abs(np.asarray(mesh_losses) - np.asarray(stacked_losses))
    # the mesh sums each group over chips with all-reduces, the stacked
    # run inside one reduction: the same terms in another order, so the
    # params differ by f32 rounding (~1e-7 relative) and a qint8 value
    # on a rounding boundary may flip by one quantum; over 3 rounds the
    # mean loss moves far less than 1e-4 of itself
    tol = 1e-4 * float(np.max(np.abs(stacked_losses)))
    print(f"loss agreement mesh vs stacked: max abs diff {diff.max():.3e} "
          f"(tolerance {tol:.3e})", flush=True)
    check(float(diff.max()) <= tol, f"losses disagree by {diff.max()}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the learner mesh against the stacked "
                         "run, on a 2x2 host")
    args = ap.parse_args(argv)
    if not (_SRC / "repro").is_dir():
        print(f"chip_smoke.py needs the repository's src/ next to it "
              f"({_SRC} not found)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(_SRC))
    from repro.runtime import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)

    s = Settings()
    devices = require_platform(s.platform)
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX found {len(devices)} devices")
    if args.chips == 4:
        mesh_vs_stacked(s, devices)
    else:
        one_chip(s, devices)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
