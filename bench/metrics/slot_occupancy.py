"""Share of the slot-steps the engine offered in the window that produced
a token: ``generated_tokens / slot_steps`` from ``Engine.metrics()``,
as deltas over the window's ticks."""


def read(run):
    ticks = run.record["ticks"]
    steps = sum(t["slot_steps"] for t in ticks)
    return 100.0 * sum(t["tokens"] for t in ticks) / steps if steps else None
