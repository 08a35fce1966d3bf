"""Host-side span timers with device fencing, Chrome-trace export, and
the names of the device-side scopes.

:class:`SpanTracer` decomposes a training round into phases the host
can honestly time:

* ``data`` — batch construction / reshaping;
* ``device`` — dispatch + device execution.  JAX dispatch is async, so
  a span that merely *calls* a jitted function measures dispatch only;
  call :meth:`SpanTracer.fence` on the results INSIDE the span to
  ``block_until_ready`` and bill the device wait where it belongs;
* ``host_sync`` — the device→host transfer (``jax.device_get``).

Every span is also a ``jax.profiler`` trace annotation, so it lands on
the host clock of whatever profiler session is active — the tracer's
own (``profile_dir``, the ``--profile-dir`` flag, a
``jax.profiler.start_trace`` session viewable in TensorBoard/Perfetto)
or one a caller started — alongside the XLA op timeline.

Inside the one fused jit program the host cannot split, each layer of
the round runs under a ``jax.named_scope`` named here (:data:`SCOPES`,
:func:`reduce_scope`): the names ride the ops' metadata through
``grad``, ``scan`` and ``vmap`` into the device trace, where each op's
``tf_op`` path says which layer it belongs to.  They change no
computation.

Export is the Chrome trace-event format (``{"traceEvents": [...]}``,
complete events, microsecond timestamps) — drop ``trace.json`` onto
https://ui.perfetto.dev to view.  Nesting is enforced by the context-
manager stack, so child spans are always contained in their parent's
[ts, ts+dur] interval (the property tests/test_telemetry.py pins).
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

# device-side scopes of a training round (models/, core/hier_avg.py)
ATTENTION = "attention"        # models/attention.gqa_attention
MLP = "mlp"                    # models/mlp.mlp_apply
SSM = "ssm"                    # models/mamba.mamba_apply
HEAD_LOSS = "head_loss"        # unembedding + loss, models/transformer.py
SCOPES = (ATTENTION, MLP, SSM, HEAD_LOSS)
REDUCE = "reduce"              # one scope per plan level: reduce.<level>


def reduce_scope(level: str) -> str:
    """The scope of one plan level's reduction."""
    return f"{REDUCE}.{level}"


class SpanTracer:
    """Collects host-side spans, each also a ``jax.profiler`` trace
    annotation; with ``profile_dir`` set, :meth:`start_profiler` opens a
    profiler session of its own to record them in."""

    def __init__(self, profile_dir: Optional[str] = None):
        self.profile_dir = profile_dir
        self.spans: List[Dict[str, Any]] = []
        self._stack: List[Dict[str, Any]] = []
        self._t0 = time.perf_counter()
        self._profiling = False     # the tracer's own profiler session

    # ------------------------------------------------------------ #

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    @contextmanager
    def span(self, name: str, cat: str = "host",
             args: Optional[Dict[str, Any]] = None):
        """Time a phase.  Yields the span record; on exit it carries
        ``ts``/``dur`` (seconds relative to tracer start).  The span is
        a trace annotation too, recorded by any active profiler
        session."""
        rec = {"name": name, "cat": cat, "ts": self._now(), "dur": 0.0,
               "depth": len(self._stack), "args": dict(args or {})}
        self._stack.append(rec)
        import jax
        try:
            with jax.profiler.TraceAnnotation(name):
                yield rec
        finally:
            self._stack.pop()
            rec["dur"] = self._now() - rec["ts"]
            self.spans.append(rec)

    def fence(self, value: Any) -> None:
        """``block_until_ready`` on ``value`` so the enclosing span is
        billed the device wait, not just the async dispatch."""
        import jax
        jax.block_until_ready(value)

    # ------------------------------------------------------------ #
    # jax.profiler bracketing (--profile-dir)

    def start_profiler(self) -> None:
        if self.profile_dir and not self._profiling:
            import jax
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True

    def stop_profiler(self) -> None:
        if self._profiling:
            import jax
            jax.profiler.stop_trace()
            self._profiling = False

    # ------------------------------------------------------------ #

    def export_chrome_trace(self, path: str) -> None:
        """Write the collected spans as a Chrome trace-event file."""
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": 0,
            "args": {"name": "repro host"}}]
        for s in self.spans:
            events.append({
                "name": s["name"], "cat": s["cat"], "ph": "X",
                "ts": round(s["ts"] * 1e6, 3),
                "dur": round(s["dur"] * 1e6, 3),
                "pid": 0, "tid": 0, "args": s["args"]})
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"},
                      f)
