"""Model FLOPs of the window's rollout over the window's seconds times the
chip's peak: one forward per generated token over its live context, and
one prefill per distinct prompt (group members share it)."""
from bench import counts


def read(run):
    rec, conf = run.record, run.conf
    flops = sum(counts.forward_flops(conf, t["live"], t["keys"])
                for t in rec["ticks"])
    flops += sum(counts.forward_flops(conf, p, counts.prefill_keys(p))
                 for p in rec["prefill_lengths"])
    return 100.0 * flops / (rec["window_s"] * run.peaks.flops_bf16)
