"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps named by what the host was doing.

The loader reads the ``.xplane.pb`` that ``jax.profiler`` writes into
a small schema: per chip, the operations of its ``XLA Ops`` line, by
their own names; for the host, the benchmark's own spans
(``jax.profiler.TraceAnnotation`` names starting with ``bench.``).
Everything after the loader works on that schema alone, so the tests
feed it synthetic traces.

The traced window is the host span :data:`WINDOW_SPAN`, which the harness
opens around the traced part of a run.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
NO_SPAN = "outside bench spans"


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    detail: str = ""          # a program span's stats (program_spans)


@dataclass
class Trace:
    ops: dict[int, list[Event]] = field(default_factory=dict)   # chip -> ops
    spans: list[Event] = field(default_factory=list)            # host spans


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir`` in this module's
    schema.  Raises when it holds no device operation line, listing the
    planes and lines it did find."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    tr = Trace()
    seen = []
    for plane in data.planes:
        for line in plane.lines:
            seen.append(f"{plane.name}/{line.name}")
            if plane.name.startswith(DEVICE_PLANE) and line.name == OPS_LINE:
                chip = int(plane.name[len(DEVICE_PLANE):].split()[0])
                tr.ops[chip] = [
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
            elif not plane.name.startswith("/device:"):
                tr.spans += [Event(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX)]
    if not tr.ops:
        raise ValueError(f"trace has no {OPS_LINE!r} line on a "
                         f"{DEVICE_PLANE}* plane; found {sorted(set(seen))}")
    return tr


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals covering the same points."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def window(tr: Trace) -> tuple[float, float]:
    spans = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, "
                         f"found {len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def busy_ns(tr: Trace, chip: int) -> list[tuple[float, float]]:
    """Union of chip ``chip``'s operation intervals inside the window."""
    lo, hi = window(tr)
    return union(_clip([(e.start_ns, e.end_ns) for e in tr.ops[chip]], lo, hi))


def busy_seconds(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the traced chips."""
    tot = sum(sum(b - a for a, b in busy_ns(tr, c)) for c in tr.ops)
    return tot / len(tr.ops) / 1e9


def window_seconds(tr: Trace) -> float:
    lo, hi = window(tr)
    return (hi - lo) / 1e9


def kernel_events(tr: Trace, pattern: str) -> list[Event]:
    """Operations inside the window whose own name (:func:`op_name`)
    contains ``pattern``, over all chips.  An op's text and stats name its
    operands too, so an op that takes a kernel's output is not that
    kernel."""
    lo, hi = window(tr)
    return [e for c in tr.ops for e in tr.ops[c]
            if e.start_ns >= lo and e.end_ns <= hi
            and pattern in op_name(e.name)]


def idle_gaps(tr: Trace, chip: int) -> list[tuple[str, float]]:
    """Each idle stretch of ``chip`` inside the window, in seconds, named by
    the innermost benchmark span (other than the window's own) that covers
    its midpoint."""
    lo, hi = window(tr)
    gaps, cur = [], lo
    for a, b in busy_ns(tr, chip):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [s for s in tr.spans if s.name != WINDOW_SPAN]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [s for s in spans if s.start_ns <= mid <= s.end_ns]
        name = (min(cover, key=lambda s: s.end_ns - s.start_ns).name
                if cover else NO_SPAN)
        out.append((name, (b - a) / 1e9))
    return out


def op_name(name: str) -> str:
    """An HLO instruction's name without its text or numeric suffix:
    ``%copy_bitcast_fusion.20 = bf16[...] fusion(...)`` ->
    ``copy_bitcast_fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def leaf_ops(evs: list[Event]) -> list[Event]:
    """The operations that contain no other operation: a ``while`` or
    ``call`` op spans the ops it runs, which would count twice."""
    evs = sorted(evs, key=lambda e: (e.start_ns, -e.end_ns))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1].start_ns >= e.end_ns]


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time (seconds summed by
    :func:`op_name`, innermost operations only, averaged over chips) and
    the idle time summed by the host span it fell in, each the ``top``
    largest."""
    lo, hi = window(tr)
    ops: dict[str, float] = defaultdict(float)
    gaps: dict[str, float] = defaultdict(float)
    n = len(tr.ops)
    for c, evs in tr.ops.items():
        for e in leaf_ops(evs):
            if e.end_ns > lo and e.start_ns < hi:
                ops[op_name(e.name)] += (min(e.end_ns, hi)
                                         - max(e.start_ns, lo)) / 1e9 / n
        for name, s in idle_gaps(tr, c):
            gaps[name] += s / n

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:top]
    return {"device_ops": ranked(ops), "idle_gaps": ranked(gaps)}
