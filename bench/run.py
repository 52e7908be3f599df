"""Entry point: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds
and prints one JSON result as the last line of standard output.  Exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T0 = time.perf_counter()    # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]    # the benchmark, the program
# JAX's compile cache lives inside the checkout, at a fixed path (part of
# the cache key), whatever the environment says; the program's
# enable_compile_cache() takes it from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
