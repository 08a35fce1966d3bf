"""Device time per layer of the round, from the scope names the program
gives its operations.

The program runs each layer of its round under a ``jax.named_scope``
(``repro/telemetry/spans.py``): ``attention``, ``mlp``, ``ssm``,
``head_loss`` and one ``reduce.<level>`` per plan level.  The scopes
billed are the ``reduce.<level>`` ones and those the metric files
declare (``SCOPE = "<name>"`` in ``metrics/<metric>.py``,
:func:`declared`), so a new metric file bills a new scope.  The names
ride each operation's metadata into the device trace: every operation
of a TPU plane has an entry in the plane's event metadata whose
``tf_op`` stat is its ``op_name`` path, for example
``jit(round_fn)/while/body/closed_call/transpose(jvp(attention))/dot_general``.
A scope opened directly under a transform shows inside its parentheses,
``jvp(vmap(head_loss))``.

Each operation of the ``XLA Ops`` line is billed its self time inside
the traced rounds, as ``trace.py`` computes it (:func:`trace.self_times`:
each instant to the latest-started operation running then), to the
innermost scope its ``tf_op`` names, or to ``unscoped``.  The result is
ms per chip per window round, per scope.  The scopes add up to
``trace.py``'s operation times, which is the result line's ``busy_s``.

``jax.profiler.ProfileData`` exposes an event's own stats but not its
metadata's, so the trace is read here with a short protobuf wire-format
reader of the few ``XSpace`` fields needed (field numbers of
``tensorflow/tsl/profiler/protobuf/xplane.proto``), without importing
TensorFlow into the process that holds the chip.
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import trace
from chipbench.bench import metric_module

REDUCE = r"reduce\.[\w-]+"
UNSCOPED = "unscoped"
HERE = Path(__file__).resolve().parent
TRACES = HERE.parent / ".chipbench_traces"


@functools.lru_cache(maxsize=8)
def declared(metrics_dir: Path = HERE / "metrics") -> Tuple[str, ...]:
    """The scope names the metric files in ``metrics_dir`` declare."""
    names = {getattr(metric_module(p), "SCOPE", None)
             for p in Path(metrics_dir).glob("*.py")}
    return tuple(sorted(n for n in names if n))


@functools.lru_cache(maxsize=8)
def _pattern(names: Tuple[str, ...]) -> "re.Pattern":
    alts = "|".join([re.escape(n) for n in names] + [REDUCE])
    return re.compile(r"(?:^|[/(])(" + alts + r")(?=[/)]|$)")


def scope_of(tf_op: str, names: Optional[Tuple[str, ...]] = None) -> str:
    """The innermost scope an operation's ``op_name`` path names, of
    ``names`` (default: :func:`declared`) and ``reduce.<level>``."""
    found = _pattern(declared() if names is None else names).findall(tf_op)
    return found[-1] if found else UNSCOPED


# --------------------------------------------------------------------- #
# protobuf wire format: the XSpace fields the reduction needs

# XSpace.planes
SPACE_PLANES = 1
# XPlane
PLANE_NAME, PLANE_LINES, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 3, 4, 5
# XLine
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
# XEvent
EVENT_METADATA_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
# map entries (event_metadata, stat_metadata)
ENTRY_KEY, ENTRY_VALUE = 1, 2
# XEventMetadata / XStatMetadata
META_NAME, EVENT_META_STATS = 2, 5
# XStat
STAT_METADATA_ID, STAT_STR_VALUE, STAT_REF_VALUE = 1, 5, 7


def _varint(buf: bytes, i: int):
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    out, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i + 1
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of each field of the message ``buf[i:end]``:
    an int for a varint or fixed-width field, ``(start, stop)`` for a
    length-delimited one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


@dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float
    tf_op: str


@dataclass
class Line:
    name: str
    events: List[Event] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


def _entries(buf: bytes, spans) -> Dict[int, tuple]:
    out = {}
    for a, b in spans:
        key, value = 0, (a, a)
        for f, v in _fields(buf, a, b):
            if f == ENTRY_KEY:
                key = v
            elif f == ENTRY_VALUE:
                value = v
        out[key] = value
    return out


def _plane(buf: bytes, a: int, b: int, chips: int) -> Optional[Plane]:
    """One XPlane, if it is a host plane or one of the first ``chips``
    TPU planes (of these, the ``XLA Ops`` line's events alone); its
    events carry their metadata's name and ``tf_op``."""
    name, lines, ev_meta, st_meta = "", [], [], []
    for f, v in _fields(buf, a, b):
        if f == PLANE_NAME:
            name = _text(buf, v)
        elif f == PLANE_LINES:
            lines.append(v)
        elif f == PLANE_EVENT_METADATA:
            ev_meta.append(v)
        elif f == PLANE_STAT_METADATA:
            st_meta.append(v)
    tpu = re.match(r"^/device:TPU:(\d+)$", name)
    ops_only = tpu is not None
    if not (name.startswith("/host") or ops_only and int(tpu[1]) < chips):
        return None
    stat_names = {}
    for key, (c, d) in _entries(buf, st_meta).items():
        for f, v in _fields(buf, c, d):
            if f == META_NAME:
                stat_names[key] = _text(buf, v)
    tf_op_id = next((k for k, n in stat_names.items() if n == "tf_op"), None)
    meta: Dict[int, tuple] = {}
    for key, (c, d) in _entries(buf, ev_meta).items():
        ev_name, tf_op = "", ""
        for f, v in _fields(buf, c, d):
            if f == META_NAME:
                ev_name = _text(buf, v)
            elif f == EVENT_META_STATS and tf_op_id is not None:
                sid, value = None, None
                for g, w in _fields(buf, *v):
                    if g == STAT_METADATA_ID:
                        sid = w
                    elif g == STAT_STR_VALUE:
                        value = _text(buf, w)
                    elif g == STAT_REF_VALUE:
                        value = stat_names.get(w, "")
                if sid == tf_op_id and value is not None:
                    tf_op = value
        meta[key] = (ev_name, tf_op)
    plane = Plane(name)
    for c, d in lines:
        line_name, ts, events = "", 0, []
        for f, v in _fields(buf, c, d):
            if f == LINE_NAME:
                line_name = _text(buf, v)
            elif f == LINE_TIMESTAMP_NS:
                ts = v
            elif f == LINE_EVENTS:
                events.append(v)
        line = Line(line_name)
        plane.lines.append(line)
        if ops_only and line_name != trace.OPS_LINE:
            continue
        for e0, e1 in events:
            mid, offset, dur = 0, 0, 0
            for f, v in _fields(buf, e0, e1):
                if f == EVENT_METADATA_ID:
                    mid = v
                elif f == EVENT_OFFSET_PS:
                    offset = v
                elif f == EVENT_DURATION_PS:
                    dur = v
            ev_name, tf_op = meta.get(mid, ("", ""))
            # nanoseconds as jax.profiler.ProfileData gives them
            line.events.append(Event(ev_name, float(ts + offset // 1000),
                                     float(dur // 1000), tf_op))
    return plane


def read_planes(path: Path, chips: int) -> List[Plane]:
    """The host planes and the first ``chips`` TPU planes of an
    ``.xplane.pb`` (of a TPU plane, the ``XLA Ops`` line's events
    alone)."""
    buf = Path(path).read_bytes()
    planes = []
    for f, v in _fields(buf, 0, len(buf)):
        if f == SPACE_PLANES:
            p = _plane(buf, *v, chips)
            if p is not None:
                planes.append(p)
    return planes


# --------------------------------------------------------------------- #

def ms_per_round(planes, chips: int,
                 names: Optional[Tuple[str, ...]] = None) -> Dict[str, float]:
    """Self time of the traced rounds' operations per scope (of
    ``names``, as :func:`scope_of` takes them) and ``unscoped``, in ms
    per chip (of the first ``chips`` TPU planes) and traced round."""
    rounds = trace._host_rounds(planes)
    if not rounds:
        raise ValueError("the trace holds no round[r] annotations")
    lo, hi = rounds[0].start, rounds[-1].end
    totals: Dict[str, float] = {}
    for plane in trace.device_planes(planes)[:chips]:
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            events = []
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                t1 = t0 + ev.duration_ns * 1e-9
                a, b = max(t0, lo), min(t1, hi)
                if b > a:
                    events.append((scope_of(ev.tf_op, names), a, b))
            for scope, _, _, own in trace.self_times(events):
                totals[scope] = totals.get(scope, 0.0) + own
    return {k: 1e3 * v / chips / len(rounds) for k, v in totals.items()}


@functools.lru_cache(maxsize=4)
def _cached(path: str, mtime: float, chips: int,
            names: Optional[Tuple[str, ...]]) -> Dict[str, float]:
    return ms_per_round(read_planes(Path(path), chips), chips, names)


def for_cell(cell: str, chips: int, traces: Optional[Path] = None,
             names: Optional[Tuple[str, ...]] = None) -> Dict[str, float]:
    """:func:`ms_per_round` of the newest window trace of ``cell``; empty
    where there is none."""
    traces = TRACES if traces is None else Path(traces)
    run_dir = re.compile(re.escape(cell) + r"\.-?\d+$")
    files = sorted((p for p in traces.glob("*/window/**/*.xplane.pb")
                    if run_dir.match(p.relative_to(traces).parts[0])),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return {}
    return _cached(str(files[-1]), files[-1].stat().st_mtime, chips,
                   names)


def read(ctx, scope: str) -> Optional[float]:
    """A reader's value: ms per chip and window round under ``scope``,
    billed among the scopes the cell's metric files declare; None where
    it has no operations."""
    names = declared(Path(ctx.cell.home) / "metrics")
    value = for_cell(ctx.cell.name, ctx.chips, names=names).get(scope, 0.0)
    return value if value > 0 else None
