"""Device time of ``paged_decode_attention`` per traced tick: the summed
durations of its operations in the traced window over the traced ticks,
with one call per layer and tick.  The operations are found by the
Pallas kernel's stable name, after which XLA names its operation."""
from bench import trace

KERNEL = "paged_decode_attention"


def read(run):
    evs = trace.kernel_events(run.trace, KERNEL)
    if not evs:
        return None
    layers = run.conf["num_hidden_layers"]
    traced = [t for t in run.record["ticks"] if t["traced"]]
    if len(evs) != layers * len(traced):
        raise ValueError(f"{len(evs)} {KERNEL} events for {len(traced)} "
                         f"traced ticks of {layers} layers")
    return sum(e.end_ns - e.start_ns for e in evs) / 1e6 / len(traced)
