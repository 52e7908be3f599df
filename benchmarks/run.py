"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig13,...] [--fast]

Prints ``name,value,derived`` CSV rows (value is the paper-metric unit noted
in each row's `derived` column; latency rows are milliseconds).
"""
from __future__ import annotations

import argparse
import time

from benchmarks import (at_scale, decision_latency, interference, longtail,
                        model_sync, mux_micro, scheduler_quality, sensitivity,
                        warm_start)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "fig10_mux_micro": mux_micro.run,
    "table4_interference": interference.run,
    "fig11_longtail": longtail.run,
    "fig12_model_sync": model_sync.run,
    "fig13_at_scale": at_scale.run,
    "fig14_sensitivity": sensitivity.run,
    "fig15_scheduler_quality": scheduler_quality.run,
    "table5_decision_latency": decision_latency.run,
    "fig4_warm_start": warm_start.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true",
                    help="shrink trace sizes for CI-speed runs")
    args = ap.parse_args()
    enable_compile_cache()
    picked = {k.strip() for k in args.only.split(",") if k.strip()}
    print("name,value,derived")
    for name, fn in SUITES.items():
        if picked and not any(p in name for p in picked):
            continue
        t0 = time.time()
        kwargs = {}
        if args.fast:
            if name == "fig13_at_scale":
                kwargs = {"n_jobs": 60, "seeds": (1,)}
            elif name == "fig14_sensitivity":
                kwargs = {"n_jobs": 50}
            elif name == "fig15_scheduler_quality":
                kwargs = {"n_instances": 3, "jobs_per_instance": 6}
            elif name == "table5_decision_latency":
                kwargs = {"targets": (5, 13, 100, 500)}
        fn(**kwargs)
        print(f"# {name} done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
