"""Share of the traced window in which no operation ran on the chip:
1 - (union of device operation intervals) / traced window."""
from bench import trace


def read(run):
    return 100.0 * (1.0 - trace.busy_seconds(run.trace)
                    / trace.window_seconds(run.trace))
