"""Mean wall time of the window's ``Engine.step`` calls (one admit + one
decode step of every live slot each), from the benchmark's host clock
around each call."""


def read(run):
    ticks = run.record["ticks"]
    return 1e3 * sum(t["step_s"] for t in ticks) / len(ticks)
