"""Trace reduction on a synthetic trace of the loader's schema, against
values worked out by hand."""
import pytest

from bench import trace
from bench.trace import Event, Trace

MS = 1e6        # ns


def synthetic() -> Trace:
    """Window 0-100 ms.  Chip 0 runs a ``while`` op (10-60 ms) holding two
    kernel calls (10-30, 30-50) and a copy (50-60), then a fusion at
    70-90 ms.  The host was in ``bench.step`` from 0 to 65 ms and in
    ``bench.harvest`` from 65 to 100 ms, with ``bench.submit`` nested in
    the step from 0 to 5 ms."""
    ops = [
        Event("%while.16 = (s32[]) while(%tuple.48)", 10 * MS, 60 * MS),
        Event("%paged_decode_attention.5 = bf16[64] custom-call(x)",
              10 * MS, 30 * MS, "tpu_custom_call"),
        Event("%paged_decode_attention.7 = bf16[64] custom-call(x)",
              30 * MS, 50 * MS, "tpu_custom_call"),
        Event("%copy-done = bf16[2049] copy-done(%copy-start)",
              50 * MS, 60 * MS),
        Event("%fusion.3 = f32[64] fusion(y)", 70 * MS, 90 * MS),
        Event("%fusion.9 = f32[64] fusion(y)", 120 * MS, 130 * MS),  # outside
    ]
    spans = [Event(trace.WINDOW_SPAN, 0, 100 * MS),
             Event("bench.step", 0, 65 * MS),
             Event("bench.submit", 0, 5 * MS),
             Event("bench.harvest", 65 * MS, 100 * MS)]
    return Trace(ops={0: ops}, spans=spans)


def test_busy_and_idle_share():
    tr = synthetic()
    assert trace.window_seconds(tr) == pytest.approx(0.100)
    # busy: 10-60 and 70-90 ms = 70 ms of the 100 ms window
    assert trace.busy_seconds(tr) == pytest.approx(0.070)


def test_kernel_time_by_name():
    evs = trace.kernel_events(synthetic(), "paged_dec")
    assert len(evs) == 2
    assert sum(e.end_ns - e.start_ns for e in evs) / 1e9 == pytest.approx(0.040)


def test_op_naming_the_kernels_output_is_not_counted():
    """An op that takes a kernel's output names it in its text and in its
    stats, as its operand; it is another op, not a call of the kernel."""
    tr = synthetic()
    tr.ops[0].append(Event(
        "%bitcast_fusion.4 = bf16[64,16,128] fusion(%paged_decode_attention.7)",
        50 * MS, 55 * MS, "%paged_decode_attention.7 = bf16[64,8,2,128]"))
    evs = trace.kernel_events(tr, "paged_dec")
    assert [e.start_ns for e in evs] == [10 * MS, 30 * MS]


def test_idle_gaps_named_by_innermost_host_span():
    gaps = trace.idle_gaps(synthetic(), 0)
    # 0-10 ms: midpoint 5 ms lies in step and (at its edge) submit, whose
    # span is shorter; 60-70 ms: midpoint 65 in step and harvest, the
    # shorter of which is harvest (35 ms against 65); 90-100: harvest
    assert gaps == [("bench.submit", pytest.approx(0.010)),
                    ("bench.harvest", pytest.approx(0.010)),
                    ("bench.harvest", pytest.approx(0.010))]


def test_breakdown_counts_innermost_ops_by_name():
    b = trace.breakdown(synthetic())
    ops = dict((k, v) for k, v in b["device_ops"])
    assert "while" not in ops                      # holds the ops it runs
    assert ops["paged_decode_attention"] == pytest.approx(0.040)
    assert ops["fusion"] == pytest.approx(0.020)   # the one inside the window
    assert ops["copy-done"] == pytest.approx(0.010)
    assert [k for k, _ in b["device_ops"]][0] == "paged_decode_attention"
    gaps = dict((k, v) for k, v in b["idle_gaps"])
    assert gaps == {"bench.harvest": pytest.approx(0.020),
                    "bench.submit": pytest.approx(0.010)}


def test_op_name_strips_text_and_suffix():
    assert trace.op_name("%copy_bitcast_fusion.20 = bf16[8] fusion(x)") == \
        "copy_bitcast_fusion"
    assert trace.op_name("%copy-done = bf16[8] copy-done(x)") == "copy-done"


def test_window_span_is_required():
    tr = synthetic()
    tr.spans = [s for s in tr.spans if s.name != trace.WINDOW_SPAN]
    with pytest.raises(ValueError):
        trace.busy_seconds(tr)
