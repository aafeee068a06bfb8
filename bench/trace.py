"""Reduce a profiler trace to device busy time, idle gaps and op totals.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain :class:`Trace`: per device, the intervals of the operations it ran,
and the benchmark's host spans (``bench.*`` annotations) on the same clock.
Everything else here works on that plain form, so the reduction is tested
on a small recorded trace kept with the benchmark.
"""

from __future__ import annotations

import dataclasses
import glob
from pathlib import Path

SPAN_PREFIX = "bench."
# the line of a TPU device plane that holds one event per HLO operation
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: dict[str, list[Event]]   # device plane name -> its op events
    spans: list[Event]                   # benchmark host spans, by start


def load(trace_dir: str | Path) -> Trace:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``, as a :class:`Trace`."""
    paths = sorted(glob.glob(str(Path(trace_dir) / "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_file(paths[-1])


def load_file(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        Event(op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for es in device_ops.values():
        es.sort(key=lambda e: e.start_ns)
    spans.sort(key=lambda e: e.start_ns)
    return Trace(device_ops, spans)


def op_name(hlo: str) -> str:
    """The instruction name of a device op event ("%fusion.3 = bf16[..." ->
    "fusion.3")."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_intervals(events: list[Event], lo: float, hi: float):
    return union(clip([(e.start_ns, e.end_ns) for e in events], lo, hi))


def busy_ns(events: list[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi) during which at least one operation ran."""
    return sum(e - s for s, e in busy_intervals(events, lo, hi))


def idle_gaps(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi) in which no operation ran."""
    gaps, t = [], lo
    for s, e in busy_intervals(events, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_at(spans: list[Event], t: float) -> str:
    """Name of the innermost benchmark span covering time ``t``."""
    best = None
    for s in spans:
        if s.start_ns <= t < s.end_ns and (best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else "outside_spans"


def op_totals(events: list[Event], lo: float, hi: float) -> dict[str, float]:
    """Seconds of device self time per operation name inside [lo, hi): an
    op's time less that of the ops nested in it, as the body of a ``while``
    loop runs inside the loop's own event."""
    tot: dict[str, float] = {}
    clipped = lambda e: max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))

    def close(item):
        e, child = item
        tot[e.name] = tot.get(e.name, 0.0) + (clipped(e) - child) * 1e-9

    stack: list[list] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            close(stack.pop())
        if stack:
            stack[-1][1] += clipped(e)
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return {k: v for k, v in tot.items() if v > 0}


@dataclasses.dataclass
class Summary:
    """What the per-layer readers see of one traced window."""
    window_s: float
    steps: int
    busy_s: float                    # mean over devices
    span_s: dict[str, float]         # total seconds per span name
    span_count: dict[str, int]
    op_s: dict[str, float]           # seconds per op name, mean over devices
    gaps: list[tuple[str, float]]    # (span the gap fell in, seconds), longest first
    n_devices: int


def summarize(trace: Trace, lo: float, hi: float, steps: int) -> Summary:
    """Reduce the window [lo, hi) of ``trace`` that held ``steps`` steps."""
    if not trace.device_ops:
        raise ValueError("the trace holds no device operations")
    devs = sorted(trace.device_ops)
    n = len(devs)
    busy = sum(busy_ns(trace.device_ops[d], lo, hi) for d in devs) / n
    op_s: dict[str, float] = {}
    for d in devs:
        for k, v in op_totals(trace.device_ops[d], lo, hi).items():
            op_s[k] = op_s.get(k, 0.0) + v / n
    span_s: dict[str, float] = {}
    span_count: dict[str, int] = {}
    for s in trace.spans:
        if lo <= s.start_ns < hi:
            span_s[s.name] = span_s.get(s.name, 0.0) + s.dur_ns * 1e-9
            span_count[s.name] = span_count.get(s.name, 0) + 1
    gaps = []
    for d in devs:
        for a, b in idle_gaps(trace.device_ops[d], lo, hi):
            gaps.append((span_at(trace.spans, (a + b) / 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary((hi - lo) * 1e-9, steps, busy * 1e-9, span_s, span_count,
                   op_s, gaps, n)


def window_bounds(trace: Trace) -> tuple[float, float]:
    """From the start of the first benchmark span to the end of the last."""
    if not trace.spans:
        raise ValueError("the trace holds no benchmark spans")
    return (min(s.start_ns for s in trace.spans),
            max(s.end_ns for s in trace.spans))


def collective_ms_per_step(summary: Summary, names) -> float | None:
    """Device time per step of the ops whose instruction names are in
    ``names`` (the collectives found in the compiled step), ms.  An async
    collective is timed from its ``-start`` and ``-done`` halves."""
    if not summary.steps:
        return None
    wanted = set()
    for n in names:
        op, _, num = n.partition(".")
        op = op.removesuffix("-start").removesuffix("-done")
        for half in ("", "-start", "-done"):
            wanted.add(f"{op}{half}.{num}" if num else f"{op}{half}")
    found = [v for k, v in summary.op_s.items() if k in wanted]
    if not found:
        return None
    return sum(found) / summary.steps * 1e3


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:top]]}

