"""Reduce a profiler trace (``.xplane.pb``) of a run's window rounds to
what the per-layer readers need.

The window is the span from the first traced round's ``round[r]``
annotation to the end of the last; the program writes those annotations
(``round[r]`` > ``data`` / ``device`` / ``host_sync``) on the host clock
the device events share.  On each TPU plane the ``XLA Ops`` line holds
one event per operation run.  From these:

* busy time per chip: the union of its operations' intervals inside the
  window, averaged over chips; the idle share is the rest;
* time per operation name, per chip, summed over the window;
* the idle gaps of the first chip, cut where a program span starts or
  ends, each piece labelled with the span the host was in; the
  breakdown sums them per span.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Tuple

ROUND = re.compile(r"^round\[(\d+)\]$")
SPANS = ("data", "device", "host_sync")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
OPS_LINE = "XLA Ops"


@dataclass
class Round:
    index: int
    start: float                      # seconds, host clock of the trace
    end: float
    spans: Dict[str, Tuple[float, float]] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def span_seconds(self, name: str) -> float:
        s = self.spans.get(name)
        return 0.0 if s is None else s[1] - s[0]


@dataclass
class Reduced:
    rounds: List[Round]
    chips: int
    busy: List[float]                 # seconds, per chip
    op_seconds: List[Dict[str, float]]  # per chip: name -> seconds
    op_text: Dict[str, str]           # name -> the operation's HLO text
    op_runs: List[Dict[str, int]]     # per chip: name -> runs
    gaps: List[Tuple[str, float]]     # (host span, seconds), chip 0

    @property
    def window_s(self) -> float:
        return self.rounds[-1].end - self.rounds[0].start

    @property
    def busy_s(self) -> float:
        return sum(self.busy) / len(self.busy)

    def mean_op_seconds(self, pred) -> float:
        """Self seconds per chip in operations whose HLO text satisfies
        ``pred``."""
        return sum(v for per in self.op_seconds for k, v in per.items()
                   if pred(self.op_text[k])) / self.chips

    def breakdown(self) -> Dict[str, List]:
        total: Dict[str, float] = {}
        for per in self.op_seconds:
            for k, v in per.items():
                total[k] = total.get(k, 0.0) + v / self.chips
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:10]
        idle: Dict[str, List[float]] = {}
        for label, sec in self.gaps:
            idle.setdefault(label, []).append(sec)
        gaps = sorted(idle.items(), key=lambda kv: -sum(kv[1]))[:10]
        return {"device_ops": [[f"{k} {short(self.op_text[k])}", v]
                               for k, v in ops],
                "idle_gaps": [[f"{k} ({len(v)} gaps, longest {max(v):.6f}s)",
                               sum(v)] for k, v in gaps]}


@dataclass
class Context:
    """What a per-layer reader is handed.  ``counters``: the scalar
    entries of the metrics dict of each traced round, keyed by its
    ``round[r]`` index, as ``train.main`` holds them on the host after
    the round's ``device_get``."""
    cell: Any
    peaks: Dict[str, Any]
    trace: Reduced
    chips: int
    counters: Dict[int, Dict[str, float]] = field(default_factory=dict)


def latest_xplane(directory: Path) -> Path:
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _host_rounds(planes) -> List[Round]:
    rounds: Dict[int, Round] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                m = ROUND.match(ev.name)
                t0 = ev.start_ns * 1e-9
                t1 = t0 + ev.duration_ns * 1e-9
                if m:
                    rounds[int(m.group(1))] = Round(int(m.group(1)), t0, t1)
                elif ev.name in SPANS:
                    spans.append((ev.name, t0, t1))
    out = [rounds[k] for k in sorted(rounds)]
    for name, t0, t1 in spans:
        for r in out:
            if r.start <= t0 and t1 <= r.end:
                r.spans[name] = (t0, t1)
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _label(t: float, rounds: List[Round]) -> str:
    for r in rounds:
        if r.start <= t < r.end:
            for name in SPANS:
                s = r.spans.get(name)
                if s is not None and s[0] <= t < s[1]:
                    return name
            return "round loop"
    return "between rounds"


def _pieces(a: float, b: float, rounds: List[Round]):
    """Split the gap [a, b) where a host span starts or ends, each piece
    labelled with what the host was doing in it."""
    cuts = {a, b}
    for r in rounds:
        for t in (r.start, r.end, *(x for s in r.spans.values() for x in s)):
            if a < t < b:
                cuts.add(t)
    edges = sorted(cuts)
    return [(_label((x + y) / 2, rounds), y - x)
            for x, y in zip(edges, edges[1:]) if y - x > 1e-9]


def device_planes(planes) -> List[Any]:
    found = [p for p in planes if re.match(r"^/device:TPU:\d+$", p.name)]
    return sorted(found, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def reduce(directory: Path, chips: int) -> Reduced:
    import jax
    data = jax.profiler.ProfileData.from_file(str(latest_xplane(directory)))
    planes = list(data.planes)
    return reduce_planes(planes, chips)


def op_parts(text: str) -> Tuple[str, str]:
    """``%fusion.4 = f32[8]{0} fusion(...)`` -> ("%fusion.4", "fusion"):
    the HLO instruction's name and its opcode."""
    name, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    return name, (m.group(1) if m else "")


def self_times(events) -> List[Tuple[str, float, float, float]]:
    """(text, start, end, self seconds) of each event inside the window
    bounds given by the caller, in order of start.  Each instant goes to
    the latest-started operation running then.  Operations nest on the
    ``XLA Ops`` line (a ``while`` holds its body's operations), and there
    an event's self time is its duration less its children's.  Some do
    not nest: an asynchronous copy's span can cover the start of the next
    loop.  Each of the two then keeps the time in which it is the latest
    started, so no self time is negative and they add up to the busy
    time (subtracting the loop's whole duration from the copy would bill
    the copy's scope minus the loop)."""
    out = sorted(([text, t0, t1, 0.0] for text, t0, t1 in events),
                 key=lambda r: (r[1], -r[2]))
    stack: List[List] = []
    now = -math.inf

    def bill(until: float) -> None:
        nonlocal now
        while stack and now < until:
            top = stack[-1]
            if top[2] <= now:
                stack.pop()
                continue
            end = min(top[2], until)
            top[3] += end - now
            now = end

    for rec in out:
        bill(rec[1])
        now = max(now, rec[1])
        stack.append(rec)
    bill(math.inf)
    return [tuple(r) for r in out]


def reduce_planes(planes, chips: int) -> Reduced:
    rounds = _host_rounds(planes)
    if not rounds:
        raise ValueError("the trace holds no round[r] annotations")
    lo, hi = rounds[0].start, rounds[-1].end
    devs = device_planes(planes)[:chips]
    if len(devs) < chips:
        raise ValueError(f"the trace holds {len(devs)} TPU planes, the run "
                         f"used {chips} chips")
    busy, op_seconds, op_runs, op_text = [], [], [], {}
    gaps: List[Tuple[str, float]] = []
    for i, plane in enumerate(devs):
        ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not ops:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line")
        events = []
        for ev in ops[0].events:
            t0 = ev.start_ns * 1e-9
            t1 = t0 + ev.duration_ns * 1e-9
            a, b = max(t0, lo), min(t1, hi)
            if b > a:
                events.append((ev.name, a, b))
        seconds: Dict[str, float] = {}
        runs: Dict[str, int] = {}
        names: Dict[str, str] = {}
        for text, a, b, own in self_times(events):
            name = names.get(text)
            if name is None:
                name = names[text] = op_parts(text)[0]
                op_text.setdefault(name, text)
            seconds[name] = seconds.get(name, 0.0) + own
            runs[name] = runs.get(name, 0) + 1
        merged = _union([(a, b) for _, a, b in events])
        busy.append(sum(b - a for a, b in merged))
        op_seconds.append(seconds)
        op_runs.append(runs)
        if i == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps += _pieces(a, b, rounds)
    return Reduced(rounds, chips, busy, op_seconds, op_text, op_runs, gaps)


def opcode(text: str) -> str:
    return op_parts(text)[1]


def is_collective(text: str) -> bool:
    return bool(COLLECTIVE.match(opcode(text)))


DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8}
SHAPE = re.compile(r"\b(f32|s32|u32|bf16|f16|s8|u8|pred|f64|s64)\[([\d,]*)\]")


def result_arrays(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of each array an operation's HLO text returns."""
    rest = text.partition(" = ")[2]
    m = OPCODE.search(rest)
    head = rest[:m.start()] if m else rest
    return [(dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in SHAPE.findall(head)]


def result_bytes(text: str) -> int:
    total = 0
    for dtype, dims in result_arrays(text):
        n = 1
        for d in dims:
            n *= d
        total += n * DTYPE_BYTES[dtype]
    return total


def short(text: str) -> str:
    """An operation's opcode and result shapes, without layouts."""
    shapes = ",".join(f"{d}[{','.join(map(str, s))}]"
                      for d, s in result_arrays(text)[:3])
    return f"{opcode(text)} {shapes}"
