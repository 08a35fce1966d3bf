"""Drive the program's training entry, ``repro.launch.train.main``, for one
run: set-up, then a window timed on the host clock.

``main`` runs a fixed number of rounds and prints one line per round,
flushed, after its blocking ``device_get``.  While it runs, a stand-in
for ``sys.stdout`` (:class:`RoundClock`) timestamps those lines: it is
the per-round hook, and it ends the window by raising
:class:`WindowClosed` from ``write`` once the window has lasted its
seconds.  The program is driven through its own loop; nothing of it is
copied here.

The rounds before the window are set-up: round 0 compiles (or loads
from the persistent cache), and the first ``check_rounds`` rounds are
the ones the reference follows.  At the header line the hook copies
learner 0's weights (read from ``main``'s frame) to the host; after
round 0 and after the last check round it takes the norm of each leaf's
change from them on the device.  The copy is dropped before the window,
which never pays for any of it.

With a trace, the program is given ``--profile-dir`` so that its spans
become profiler annotations; the hook stops the program's own profiler
session at once and starts one of its own, without the Python tracer,
for the window's rounds alone.  At each traced round's line it keeps
the round's scalar metrics (``Window.counters``): ``main`` took them to
the host before printing, so the hook copies numbers and waits for
nothing.  An untraced run keeps none.
"""
from __future__ import annotations

import gc
import io
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np


class WindowClosed(Exception):
    """Raised through ``main`` when the window has lasted long enough."""


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.compiles, self.cache_hits = 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def learner0_shards(state) -> Dict[str, Any]:
    """The device array of each leaf that holds learner 0, keyed by leaf
    path.  Each leaf is ``[pods, G, S, ...]``; device 0 holds learner
    (0, 0, 0) at index ``[0, 0, 0]`` of its shard."""
    import jax
    return {jax.tree_util.keystr(path): leaf.addressable_shards[0].data
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(state.params)[0]}


_change_norm: Optional[Callable] = None


def change_norms(state, start: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Norm of learner 0's change from ``start`` in each leaf, on the
    device, in float32."""
    global _change_norm
    if _change_norm is None:
        import jax
        import jax.numpy as jnp
        _change_norm = jax.jit(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a[0, 0, 0] - b))))
    return {k: float(_change_norm(a, start[k]))
            for k, a in learner0_shards(state).items()}


def scalars(metrics) -> Dict[str, float]:
    """The scalar entries of a round's metrics dict, already on the
    host."""
    return {k: float(v) for k, v in metrics.items()
            if isinstance(v, (np.ndarray, np.generic)) and v.ndim == 0}


def abstract(tree):
    """Shapes, types and placements of a tree of arrays."""
    import jax
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=getattr(x, "sharding", None)), tree)


@dataclass
class Window:
    """What the hook saw."""
    losses: Dict[int, float] = field(default_factory=dict)
    tokens_printed: Optional[int] = None
    update: Dict[str, float] = field(default_factory=dict)
    change: Dict[str, float] = field(default_factory=dict)
    marks: List[Tuple[str, float]] = field(default_factory=list)
    start_t: Optional[float] = None
    end_t: Optional[float] = None
    rounds: int = 0
    longest_round: Tuple[float, int] = (0.0, -1)
    gc_s: float = 0.0
    compiles_before: int = 0
    compiles_in_window: int = 0
    round_fn: Any = None           # the program's jitted round ...
    round_args: Any = None         # ... and its arguments' shapes
    counters: Dict[int, Dict[str, float]] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end_t - self.start_t

    def mark(self, label: str) -> None:
        self.marks.append((label, time.perf_counter()))


class RoundClock(io.TextIOBase):
    """``sys.stdout`` while ``main`` runs.

    The window starts at the line of round ``check_rounds - 1`` and
    closes at the first round line at least ``seconds`` later, or after
    ``trace_rounds`` rounds when tracing to ``trace_dir``."""

    def __init__(self, out, main_code, *, check_rounds: int,
                 seconds: float, compile_log: CompileLog,
                 trace_dir: Optional[str] = None, trace_rounds: int = 0):
        self.out, self.main_code = out, main_code
        self.check_rounds, self.seconds = check_rounds, seconds
        self.log, self.trace_dir = compile_log, trace_dir
        self.trace_rounds = trace_rounds
        self.w = Window()
        self._start: Optional[Dict[str, np.ndarray]] = None
        self._last_t = 0.0
        self._gc_t = 0.0

    def _main_locals(self) -> Dict[str, Any]:
        f = sys._getframe(1)
        while f is not None and f.f_code is not self.main_code:
            f = f.f_back
        if f is None:
            raise RuntimeError("round line printed outside train.main")
        return f.f_locals

    def write(self, text: str) -> int:
        self.out.write(text)
        if text.startswith("Hier-AVG:"):
            self._header()
        elif text.startswith("round "):
            self._round(text)
        return len(text)

    def flush(self) -> None:
        self.out.flush()

    def gc_phase(self, phase: str, info) -> None:
        """``gc.callbacks`` hook: collection time inside the window."""
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self.w.start_t is not None and self.w.end_t is None:
            self.w.gc_s += time.perf_counter() - self._gc_t

    def _header(self) -> None:
        import jax
        self.w.mark("header")
        if self.trace_dir is not None:
            jax.profiler.stop_trace()      # the program's own session
        self._start = {k: np.asarray(a)[0, 0, 0] for k, a in
                       learner0_shards(self._main_locals()["state"]).items()}
        self.w.mark("weights copied")

    def _round(self, text: str) -> None:
        now = time.perf_counter()
        w = self.w
        r = int(text.split()[1])
        loc = self._main_locals()
        w.losses[r] = float(loc["m"]["loss"])
        w.tokens_printed = int(text.rsplit(",", 1)[1].split()[0])
        if r < self.check_rounds:
            w.mark(f"round {r}")
            if r == 0:
                w.update = change_norms(loc["state"], self._start)
            if r == self.check_rounds - 1:
                w.change = change_norms(loc["state"], self._start)
                self._start = None
                gc.collect()
                if self.trace_dir is not None:
                    self._start_trace()
                w.compiles_before = self.log.compiles
                w.start_t = self._last_t = time.perf_counter()
            return
        w.longest_round = max(w.longest_round, (now - self._last_t, r))
        self._last_t = now
        if self.trace_dir is not None:
            w.counters[r] = scalars(loc["m"])
        done = r - self.check_rounds + 1
        if (self.trace_dir is not None and done >= self.trace_rounds) or \
                (self.trace_dir is None and now - w.start_t >= self.seconds):
            w.end_t, w.rounds = now, done
            w.compiles_in_window = self.log.compiles - w.compiles_before
            w.round_fn = loc["round_fn"]
            w.round_args = abstract((loc["state"], loc["batch"]))
            raise WindowClosed()

    def _start_trace(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)


def drive(main, argv: List[str], devices, **clock_kw) -> Window:
    """Run ``main(argv, devices=devices)`` under a :class:`RoundClock`
    until the window closes."""
    real = sys.stdout
    clock = RoundClock(real, main.__code__, **clock_kw)
    clock.w.mark("main called")
    sys.stdout = clock
    gc.callbacks.append(clock.gc_phase)
    try:
        main(argv, devices=devices)
    except WindowClosed:
        real.write("\n")           # the round line's end, never printed
    finally:
        sys.stdout = real
        gc.callbacks.remove(clock.gc_phase)
    if clock.w.end_t is None:
        raise RuntimeError("the program ended before the window closed")
    return clock.w
