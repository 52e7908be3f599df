"""Mean host time of a scheduler tick in the traced window: each
``engine.step`` span the program opened, less its ``engine.wait`` child
(the host's one wait on the device), from the program's own spans in the
profile.  The device waits through this time; it is what is left of a
tick once the decode block's device time is gone.  Nothing where the
program opens no such span."""
from pathlib import Path

from bench import program_spans, trace

ROOT = Path(__file__).resolve().parents[2]     # the checkout the run is in


def read(run):
    spans = program_spans.for_run(run, ROOT)
    host = program_spans.tick_host_seconds(spans, trace.window(run.trace))
    return 1e3 * sum(host) / len(host) if host else None
