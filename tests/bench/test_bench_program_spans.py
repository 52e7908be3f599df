"""The program's spans in a traced run (``bench/program_spans.py``) and the
readers built on them, ``tick_host_ms`` and ``paged_attn_ms``: on a
synthetic trace against values worked out by hand, and on profiles the
profiler writes on the CPU, with and without program spans."""
import json
import shutil
import time
from pathlib import Path

import jax
import pytest

from bench import harness, program_spans, trace
from bench.device import PEAKS
from bench.trace import Event, Trace
from repro.core import telemetry

ROOT = Path(__file__).resolve().parents[2]
MS = 1e6        # ns
CELL = "rollout-longtail"
EXISTING = ["decode_tick_ms", "slot_occupancy", "paged_attn_roofline",
            "mfu.rollout", "device_idle.rollout"]


def synthetic() -> tuple[Trace, list[Event]]:
    """Window 0-100 ms, two ticks of two layers.  The benchmark's
    ``bench.step`` spans 0-50 and 50-100 ms each hold one ``engine.step``:

    ====  ==========  =====  ========  =======  =======
    tick  engine.step admit  dispatch  wait     record
    ====  ==========  =====  ========  =======  =======
    1     1-49        1-2    2-8       8-44     44-49
    2     51-99       51-52  52-56     56-96    96-99
    ====  ==========  =====  ========  =======  =======

    The chip runs the paged kernel at 10-20 and 20-30 ms and a fusion at
    30-40 in tick 1, a copy at 49.5-50.5, then the kernel at 58-70 and
    70-82 and a fusion at 82-90 in tick 2.  A third step starts at 99.5 ms
    and ends past the window.
    Returns the trace as the loader gives it (benchmark spans only) and
    the program's spans."""
    kern = "%paged_decode_attention.{} = bf16[64] custom-call(x)"
    ops = [Event(kern.format(1), 10 * MS, 20 * MS, "tpu_custom_call"),
           Event(kern.format(1), 20 * MS, 30 * MS, "tpu_custom_call"),
           Event("%fusion.3 = f32[64] fusion(y)", 30 * MS, 40 * MS),
           Event("%copy.2 = s32[64,36] copy(t)", 49.5 * MS, 50.5 * MS),
           Event(kern.format(1), 58 * MS, 70 * MS, "tpu_custom_call"),
           Event(kern.format(1), 70 * MS, 82 * MS, "tpu_custom_call"),
           Event("%fusion.3 = f32[64] fusion(y)", 82 * MS, 90 * MS)]
    bench = [Event(trace.WINDOW_SPAN, 0, 100 * MS),
             Event("bench.step", 0, 50 * MS),
             Event("bench.step", 50 * MS, 100 * MS)]
    prog = []
    for step, admit, dispatch, wait, record in (
            ((1, 49), (1, 2), (2, 8), (8, 44), (44, 49)),
            ((51, 99), (51, 52), (52, 56), (56, 96), (96, 99)),
            ((99.5, 105), (99.5, 99.6), (99.6, 100), (100, 104),
             (104, 105))):
        for name, (a, b) in (("step", step), ("admit", admit),
                             ("dispatch", dispatch), ("wait", wait),
                             ("record", record)):
            prog.append(Event(f"engine.{name}", a * MS, b * MS))
    return Trace(ops={0: ops}, spans=bench), prog


def run_of(tr: Trace) -> harness.Run:
    """A run of two traced ticks of 64 live rows at 300 keys, on the cell's
    configuration cut to two layers."""
    cell = harness.load_cell(CELL)
    conf = dict(cell.conf, num_hidden_layers=2)
    tick = {"step_s": 0.05, "dt": 0.05, "tokens": 64, "firsts": 0,
            "slot_steps": 64, "live": 64, "keys": 64 * 300,
            "traced": True, "lengths": [300] * 64}
    record = {"ticks": [dict(tick), dict(tick, step_s=0.04)],
              "window_s": 0.1, "prefill_lengths": [128]}
    return harness.Run(cell=cell, conf=conf, traffic=cell.traffic,
                       peaks=PEAKS["TPU v5 lite"], record=record, trace=tr)


def with_spans(tr: Trace, spans: list[Event]) -> Trace:
    """``tr`` as a loader that kept the program's spans too would give it."""
    return Trace(ops=tr.ops, spans=tr.spans + spans)


def test_prefixes_are_the_programs():
    assert program_spans.PREFIXES == telemetry.SPAN_PREFIXES


def test_idle_gap_under_bench_step_named_by_innermost_engine_span():
    tr, prog = synthetic()
    # bench spans alone: every gap (0-10, 40-49.5, 50.5-58, 90-100 ms) is
    # the step's
    assert trace.idle_gaps(tr, 0) == [
        ("bench.step", pytest.approx(0.010)),
        ("bench.step", pytest.approx(0.0095)),
        ("bench.step", pytest.approx(0.0075)),
        ("bench.step", pytest.approx(0.010))]
    # midpoints 5, 44.75, 54.25 and 95 ms lie in dispatch, record,
    # dispatch and wait, each inside an engine.step inside a bench.step
    assert trace.idle_gaps(with_spans(tr, prog), 0) == [
        ("engine.dispatch", pytest.approx(0.010)),
        ("engine.record", pytest.approx(0.0095)),
        ("engine.dispatch", pytest.approx(0.0075)),
        ("engine.wait", pytest.approx(0.010))]
    gaps = dict(trace.breakdown(with_spans(tr, prog))
                ["idle_gaps"])
    assert gaps == {"engine.dispatch": pytest.approx(0.0175),
                    "engine.record": pytest.approx(0.0095),
                    "engine.wait": pytest.approx(0.010)}


def test_tick_host_seconds_by_hand():
    tr, prog = synthetic()
    # (49 - 1) - (44 - 8) = 12 ms and (99 - 51) - (96 - 56) = 8 ms; the
    # third step ends outside the window
    assert program_spans.tick_host_seconds(prog, trace.window(tr)) == [
        pytest.approx(0.012), pytest.approx(0.008)]
    assert program_spans.tick_host_seconds([], trace.window(tr)) == []


def test_paged_attn_ms_by_hand():
    tr, _ = synthetic()
    read = harness.load_reader("paged_attn_ms")
    # (10 + 10 + 12 + 12) ms of the kernel over 2 ticks
    assert read(run_of(tr)) == pytest.approx(22.0)
    run = run_of(tr)
    run.record["ticks"][1]["traced"] = False    # 4 events, 1 tick x 2 layers
    with pytest.raises(ValueError):
        read(run)
    run = run_of(Trace(ops={0: tr.ops[0][2:4]}, spans=tr.spans))
    assert read(run) is None                    # no such kernel in the trace


@pytest.mark.parametrize("name", EXISTING + ["paged_attn_ms", "breakdown"])
def test_existing_readings_unmoved_by_program_spans(name):
    """Program spans beside the benchmark's change no reading of the
    accepted metrics, and no device time in the breakdown; the idle time
    it names sums the same."""
    tr, prog = synthetic()
    both = with_spans(tr, prog)
    if name == "breakdown":
        plain, named = trace.breakdown(tr), trace.breakdown(both)
        assert named["device_ops"] == plain["device_ops"]
        assert sum(v for _, v in named["idle_gaps"]) == \
            pytest.approx(sum(v for _, v in plain["idle_gaps"]))
        # a program that opens no span leaves the breakdown as it was
        assert trace.breakdown(with_spans(tr, [])) == plain
    else:
        read = harness.load_reader(name)
        assert read(run_of(both)) == read(run_of(tr))


def _profile(trace_dir: Path, program: bool) -> None:
    """A CPU profile of two ticks under the benchmark's window span, the
    program's tick spans inside them when ``program`` (a parent's program
    opens none).  Each tick waits 5 ms in ``engine.wait``."""
    ann = jax.profiler.TraceAnnotation
    with jax.profiler.trace(str(trace_dir)):
        with ann(trace.WINDOW_SPAN):
            for _ in range(2):
                with ann("bench.step"):
                    if not program:
                        time.sleep(0.005)
                        continue
                    with telemetry.span("engine.step"):
                        with telemetry.span("engine.dispatch"):
                            jax.numpy.ones(4).block_until_ready()
                        with telemetry.span("engine.wait"):
                            time.sleep(0.005)
                        with telemetry.span("engine.record"):
                            sum(range(1000))


def _newest(d: Path) -> str:
    return str(max(d.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime))


@pytest.mark.parametrize("program", [True, False])
def test_tick_host_ms_reads_its_own_runs_profile(tmp_path, program):
    """The reader finds the profile of the run it is given among the cell's
    (by the traced window), and gives the mean of each step less its wait;
    nothing where the program opened no span."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = tmp_path / "bench-out" / CELL
    _profile(out / "trace-1", program)
    _profile(out / "trace-2", not program)       # another run's, newer
    windows, spans = program_spans.load(_newest(out / "trace-1"))
    assert len(windows) == 1
    tr = Trace(ops={0: []}, spans=windows)
    read = harness.load_reader("tick_host_ms", tmp_path)
    got = read(run_of(tr))
    if not program:
        assert spans == [] and got is None
        return
    steps = [s for s in spans if s.name == "engine.step"]
    waits = [s for s in spans if s.name == "engine.wait"]
    assert len(steps) == len(waits) == 2
    want = sum((s.end_ns - s.start_ns) - (w.end_ns - w.start_ns)
               for s, w in zip(steps, waits)) / 2 / 1e6
    assert got == pytest.approx(want)
    assert 0 < got < max(s.end_ns - s.start_ns for s in steps) / 1e6
    assert all(w.end_ns - w.start_ns >= 5e6 for w in waits)


def test_new_metrics_are_declared_for_the_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert per_layer["tick_host_ms"]["source"] == "program_span"
    assert per_layer["paged_attn_ms"]["source"] == "device_trace"
    names = {m["name"] for m in harness.load_cell(CELL).per_layer}
    assert {"tick_host_ms", "paged_attn_ms"} <= names
