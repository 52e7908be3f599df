"""Share of its roofline that ``paged_decode_attention`` reaches in the
traced ticks: the least time the chip's peaks allow for the work the
live rows need (the family's ``paged_attention_work``: live KV blocks,
q and out; q.k and p.v FLOPs), over the kernel's device time in the
trace.  The work is memory-bound at every length this traffic sends."""
from bench import counts, trace

KERNEL = "paged_decode_attention"      # the Pallas kernel's name


def read(run):
    evs = trace.kernel_events(run.trace, KERNEL)
    if not evs:
        return None
    layers = run.conf["num_hidden_layers"]
    traced = [t for t in run.record["ticks"] if t["traced"]]
    if len(evs) != layers * len(traced):
        raise ValueError(f"{len(evs)} {KERNEL} events for {len(traced)} "
                         f"traced ticks of {layers} layers")
    t_min = 0.0
    for t in traced:
        f, b = run.cell.family.paged_attention_work(
            run.conf, t["lengths"], block_size=run.traffic["kv_block_size"])
        t_min += layers * counts.roofline_seconds(f, b, run.peaks)[0]
    busy = sum(e.end_ns - e.start_ns for e in evs) / 1e9
    return 100.0 * t_min / busy
