"""Readings that set the limits of a cell's output check.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51 \\
        --out chiprun_out/control-<cell>.jsonl

For each seed, in one process: one whole run of the cell (set-up, window,
output check), then the control (the reference in a lower precision in
the program's place) and the planted faults the cell can have, each read
with the same comparison.  One JSON line per seed: the program's numbers
(``checks``) and the control's and faults' (``control``).  The lower end
of each limit is the largest program reading over the seeds, the upper
the smallest control or fault reading.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]    # the benchmark, the program
# JAX's compile cache lives inside the checkout, at a fixed path (part of
# the cache key), whatever the environment says; the program's
# enable_compile_cache() takes it from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

from bench.harness import run_cell  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    a = p.parse_args()
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in a.seeds.split(",")):
        res, _ = run_cell(a.workload, seed, a.seconds, False,
                          t0=time.perf_counter(), controls=True)
        line = {"seed": seed, "correct": res["correct"],
                "checks": res["checks"], "control": res["control"],
                "metrics": res["metrics"]}
        with open(a.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
