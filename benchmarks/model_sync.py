"""Paper Fig 12: topology-aware model sync vs flat collectives.

Analytic bandwidth model (20 Gbps cross / 400 Gbps intra, paper §7.1) plus —
when enough host devices are available — HLO collective-byte attribution of
the real shard_map lowerings (ppermute bytes = slow link, all-gather = fast
fabric)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit
from repro.sync import ClusterTopology

MODEL_BYTES = {"7B": 15.4e9, "14B": 29.6e9, "32B": 65.5e9}  # bf16 weights


def run():
    topo = ClusterTopology()
    for name, b in MODEL_BYTES.items():
        flat = topo.flat_fetch_time_s(b, 8)
        hier = topo.hierarchical_time_s(b, 8, 8)
        emit(f"fig12_single_{name}_flat_s", flat, "veRL 8xH800->8xH20")
        emit(f"fig12_single_{name}_rollmux_s", hier, "hierarchical 2-stage")
        emit(f"fig12_single_{name}_speedup", flat / hier,
             "paper: 7.87-8.33x")
        ring = topo.ring_allgather_time_s(b, 32)
        hier16 = topo.hierarchical_time_s(b, 16, 16)
        emit(f"fig12_multi_{name}_speedup", ring / hier16,
             "paper: 2.62-2.75x (our ring baseline is conservative)")

    # real collective structure, lowered on 16 virtual CPU devices in a
    # child process; the child pins itself to the CPU so it never contends
    # for an accelerator this process may hold
    code = r"""
import json, sys
sys.path.insert(0, 'src')
from repro.sync import lower_sync
from repro.launch.hlo_cost import analyze_hlo
out = {}
for mode in ('hierarchical','flat'):
    txt = lower_sync(8, 2*8*1000, mode=mode).compile().as_text()
    c = analyze_hlo(txt)
    out[mode] = {k: v for k, v in c.coll.items()}
print(json.dumps(out))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=16")
    res = subprocess.run([sys.executable, "-c", code], cwd=os.getcwd(),
                         env=env, capture_output=True, text=True,
                         timeout=600)
    if res.returncode != 0:
        raise RuntimeError(f"collective lowering child failed "
                           f"(rc {res.returncode}):\n{res.stderr[-4000:]}")
    data = json.loads(res.stdout.strip().splitlines()[-1])
    emit("fig12_hlo_slowlink_bytes_hier",
         data["hierarchical"]["collective-permute"],
         "ppermute bytes crossing the cluster axis (one copy)")
    emit("fig12_hlo_alllink_bytes_flat", data["flat"]["all-gather"],
         "flat all-gather bytes spanning both pools")


if __name__ == "__main__":
    run()
