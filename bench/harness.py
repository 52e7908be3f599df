"""Runs one cell of ``BENCHMARK.json`` once and reduces it to a result line.

A run: find the cell's configuration, its model family, traffic mix,
limits and per-layer readers by name; set up the program (weights from the
seed, warm-up of every shape the traffic uses); measure for
``--seconds``; free the program; check its outputs against the family's
plain reference; print.

The kind of traffic (``"kind"`` in the traffic file) names the driver in
``bench/kinds/<kind>.py``, which owns set-up, the window, the end-to-end
numbers and the output check.  Drivers report the window through a
:class:`Context`, which opens the window, places the benchmark's host
spans and, with ``--trace 1``, profiles a steady part of it.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import sys
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# finding the parts of a cell by name
# ---------------------------------------------------------------------------
def _json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    family: object                   # bench/families/<model_type>.py


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    conf = _json(root / conf_entry["file"])
    return Cell(name=name, chips=w["chips"], conf=conf,
                traffic=_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=_json(root / "bench" / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer,
                family=load_family(conf["model_type"], root))


def _module(path: Path, prefix: str):
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    return _module(root / "bench" / "metrics" / f"{name}.py",
                   "bench_metric_").read


def load_family(model_type: str, root: Path = ROOT):
    """``bench/families/<model_type>.py``, the model family of every
    configuration whose published ``model_type`` this is: its
    ``program_config(conf)``, ``shapes(conf)`` and ``NORM_GAINS`` of the
    weight tree, its plain ``Reference``, its work counts
    (``matmul_params``, ``attention_flops_per_key`` and the least work of
    its decode-attention kernel) and ``TINY``, the sizes of its cells on
    the CPU in a test."""
    path = root / "bench" / "families" / f"{model_type}.py"
    if not path.is_file():
        known = sorted(p.stem for p in path.parent.glob("*.py"))
        raise KeyError(f"no family {model_type!r}; known: {known}")
    return _module(path, "bench_family_")


def load_driver(kind: str):
    return importlib.import_module(f"bench.kinds.{kind}").Driver


# ---------------------------------------------------------------------------
# clocks, spans and the traced part of the window
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    reads included), and the number of backend compilations, summed over
    every thread of the process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration
                if event == self.EVENTS[2]:
                    self.compiles += 1


def span(name: str):
    """A host span in the profiler's trace, named ``bench.<name>``."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


class Context:
    """What a driver needs from the harness during a run."""

    def __init__(self, cell: Cell, seed: int, devices, *, trace: bool,
                 trace_dir: Path, t0: float):
        self.cell, self.conf, self.traffic = cell, cell.conf, cell.traffic
        self.seed, self.devices = seed, devices
        self.t0 = t0
        self.clock = CompileClock()
        self.trace = trace
        self.trace_dir = trace_dir
        self.t_open: Optional[float] = None
        self.setup_compile_s = 0.0
        self.setup_compiles = 0
        self._ann = None
        self._trace_state = "off"        # off -> on -> done
        self._trace_at: Optional[float] = None
        self.span = span

    def open_window(self) -> float:
        """Marks the end of set-up; returns the window's start time."""
        self.t_open = time.perf_counter()
        self.setup_compile_s = self.clock.seconds
        self.setup_compiles = self.clock.compiles
        return self.t_open

    def tracing(self) -> bool:
        return self._trace_state == "on"

    def tick(self, now: float) -> None:
        """Called by the driver between units of work: starts the profiler
        ``trace_start_s`` into the window and stops it ``trace_seconds``
        later (traffic-file parameters), so the trace holds whole units.
        Set-up's calls, before the window opens, trace nothing."""
        if not self.trace or self.t_open is None \
                or self._trace_state == "done":
            return
        t = self.traffic
        if self._trace_state == "off" and \
                now - self.t_open >= t["trace_start_s"]:
            import jax
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self._ann = span("traced")     # trace.WINDOW_SPAN
            self._ann.__enter__()
            self._trace_state, self._trace_at = "on", time.perf_counter()
        elif self._trace_state == "on" and \
                now - self._trace_at >= t["trace_seconds"]:
            self.stop_trace()

    def stop_trace(self) -> None:
        if self._trace_state != "on":
            return
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._trace_state = "done"


@dataclass
class Run:
    """Everything a per-layer reader may read."""
    cell: Cell
    conf: dict
    traffic: dict
    peaks: object
    record: dict                     # the driver's account of the window
    trace: object = None             # bench.trace.Trace of the traced part


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, devices=None, shrink=None,
             t0: Optional[float] = None,
             controls: bool = False) -> tuple[dict, list[str]]:
    """Run cell ``name`` once.  Returns the result line's object and the
    lines for standard error (the numbers compared come last).

    ``devices`` and ``shrink`` are the tests' seam: the entry point always
    asks :func:`bench.device.require_chips` for the devices and runs the
    cell as its files state it; the tests hand in CPU devices and a
    function that cuts the sizes (and the limits with them) of the loaded
    :class:`Cell` in place.
    ``controls`` adds the driver's control and planted-fault readings
    (``bench/control.py``); the benchmark's own runs do not compute them."""
    import jax

    from bench import device as dev
    from bench import trace as trace_mod
    t0 = time.perf_counter() if t0 is None else t0
    cell = load_cell(name, root)
    if shrink is not None:
        shrink(cell)
    if devices is None:
        devices = dev.require_chips(cell.chips)
        peaks = dev.peaks_for(devices[0].device_kind)
    else:
        peaks = dev.PEAKS["TPU v5 lite"]     # CPU tests: shares are not read
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out_dir = root / "bench-out" / name
    trace_dir = out_dir / f"trace-{seed}"
    ctx = Context(cell, seed, devices, trace=trace, trace_dir=trace_dir,
                  t0=t0)
    driver = load_driver(cell.traffic["kind"])(ctx)
    driver.setup()
    record = driver.window(seconds)
    ctx.stop_trace()
    window_compiles = ctx.clock.compiles - ctx.setup_compiles
    device = dev.describe(devices)
    e2e = driver.end_to_end(record)
    driver.release()
    gc.collect()
    checks = driver.check(cell.limits)
    control = driver.control() if controls else None
    correct = all(v <= lim for _, v, lim in checks) and not record["failed"]

    lines = [f"device: {device}",
             f"compile cache: {cache_dir}",
             f"setup_s={ctx.t_open - t0:.6f} of which compile_s="
             f"{ctx.setup_compile_s:.6f} ({ctx.setup_compiles} compilations)",
             f"compilations inside the window: {window_compiles}"]
    result = {"correct": bool(correct), "attempted": record["attempted"],
              "failed": record["failed"]}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        values = dict(e2e, setup_s=ctx.t_open - t0)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    else:
        tr = trace_mod.load(str(trace_dir))
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = trace_mod.window_seconds(tr)
        run = Run(cell=cell, conf=cell.conf, traffic=cell.traffic,
                  peaks=peaks, record=record, trace=tr)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        result["breakdown"] = trace_mod.breakdown(tr)
    result["metrics"] = metrics
    result["device"] = device
    if control is not None:
        result["control"] = control
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    lines += [f"check {n}: {v!r} limit {lim!r}" for n, v, lim in checks]
    return result, lines


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, t0: Optional[float] = None) -> int:
    from bench.device import NoChip
    args = parse_args(argv)
    try:
        result, lines = run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), t0=t0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


