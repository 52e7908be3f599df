"""Model FLOPs of the window's rollout over the window's seconds times the
chip's peak: one forward per generated token over its live context, and
one prefill per distinct prompt (group members share it)."""
from bench import counts


def read(run):
    rec, conf, fam = run.record, run.conf, run.cell.family
    flops = sum(counts.forward_flops(fam, conf, t["live"], t["keys"])
                for t in rec["ticks"])
    flops += sum(counts.forward_flops(fam, conf, p, counts.prefill_keys(p))
                 for p in rec["prefill_lengths"])
    return 100.0 * flops / (rec["window_s"] * run.peaks.flops_bf16)
