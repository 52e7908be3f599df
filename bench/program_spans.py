"""The program's own host spans in a traced run's profile.

The program opens spans at the boundaries of the engine's scheduler tick
and the mux's permits (``repro.core.telemetry.span``: names starting with
``engine.`` or ``mux.``), in the same ``.xplane.pb`` as the device's
operations and on the same clock.  :func:`bench.trace.load` keeps only the
benchmark's ``bench.*`` spans, so this module reads the profile again for
the program's, in :mod:`bench.trace`'s schema.  A program that opens no
such span gives an empty list, and every reader built on it then returns
nothing.
"""
from __future__ import annotations

import glob
import os
from pathlib import Path

from bench import trace
from bench.trace import Event

PREFIXES = ("engine.", "mux.")
STEP, WAIT = "engine.step", "engine.wait"


def load(path: str) -> tuple[list[Event], list[Event]]:
    """The window spans (:data:`bench.trace.WINDOW_SPAN`) and the program's
    spans on the host planes of the profile at ``path``.  A span's stats
    (``rid=``, ``job=``) go into its ``detail`` as ``key=value`` words."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    windows, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace.WINDOW_SPAN:
                    into = windows
                elif e.name.startswith(PREFIXES):
                    into = spans
                else:
                    continue
                into.append(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  " ".join(f"{k}={v}" for k, v in e.stats)))
    return windows, spans


def for_run(run, root: Path) -> list[Event]:
    """The program's spans in ``run``'s profile: the newest ``.xplane.pb``
    the harness wrote for the cell (under ``<root>/bench-out/<cell>/``)
    whose traced window is the one ``run.trace`` holds."""
    lo, hi = trace.window(run.trace)
    files = glob.glob(os.path.join(root, "bench-out", run.cell.name,
                                   "trace-*", "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        windows, spans = load(path)
        if [(w.start_ns, w.end_ns) for w in windows] == [(lo, hi)]:
            return spans
    return []


def tick_host_seconds(spans: list[Event], window: tuple[float, float]
                      ) -> list[float]:
    """For each ``engine.step`` span wholly inside ``window``: its seconds
    less those of the ``engine.wait`` spans inside it, the host's own work
    in the tick, which the device waits through.  A wait belongs to the
    step whose interval holds it, which is exact for one engine's
    spans."""
    lo, hi = window
    waits = [s for s in spans if s.name == WAIT]
    out = []
    for s in spans:
        if s.name == STEP and s.start_ns >= lo and s.end_ns <= hi:
            wait = sum(w.end_ns - w.start_ns for w in waits
                       if w.start_ns >= s.start_ns and w.end_ns <= s.end_ns)
            out.append((s.end_ns - s.start_ns - wait) / 1e9)
    return out
