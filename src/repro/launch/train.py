"""End-to-end RL post-training driver (single host, real execution).

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 50 --batch 8 --group 4          # --no-reduced: full widths

Runs the full synchronous on-policy loop the paper schedules:
rollout (generation) -> reward (verifiable) -> GRPO advantages ->
training step -> weight sync into the rollout actor.

``--rollout engine`` routes the rollout phase through the
continuous-batching serving engine (``rl.generate_continuous``) instead of
the static-batch ``generate`` scan — the same engine the serving drivers
and benchmarks exercise, so training traffic measures real serving
behaviour (``--kv paged`` serves it from the block-pool KV layout).
Greedy rollouts are token-identical across the two backends; sampled
rollouts draw from a different (equally valid) key stream.

``--mux`` picks the phase-multiplexing executor (``rl.coexec``), the
paper's answer to the rollout<->train dependency bubble:

* ``off`` (default) — rollout and training back-to-back, the
  standard-disaggregation baseline.
* ``pipeline`` — overlap the rollout of GRPO iteration ``k+1`` with the
  training step of iteration ``k``.  The on-policy staleness guard
  ``--mux-staleness`` bounds how many optimizer steps the rollout weights
  may lag: ``0`` forces full sync (bit-exact to ``off``, no overlap),
  ``1`` (default) overlaps adjacent iterations, correcting the bounded
  off-policy drift with the clipped importance ratio (the per-step lag is
  recorded as ``rollout_staleness`` in the history).
* ``coexec`` — ``--jobs`` independent GRPO jobs time-multiplex the shared
  rollout/train pools round-robin with warm-start context switches from
  the host-DRAM actor cache: while one job trains, another's rollout
  drains through the engine.  Job ``i`` uses ``seed + i``; per-job losses
  are bit-exact to running that job alone.
* ``stream`` — group-level pipelining *inside* the job (``rl.stream``):
  the engine streams each completed GRPO prompt group to a reward permit
  pool (``--reward-workers`` verifiers running off the critical path —
  see ``--reward`` / ``--reward-latency``) while it keeps decoding the
  stragglers, and the trainer consumes rewarded groups as micro-batches
  (``--micro-groups``; default = one bit-exact full-batch step per
  iteration) behind the same staleness guard, extended past 1 with
  clipped importance-ratio diagnostics in the history.

All modes print/return per-step history; the mux modes additionally
report the measured phase timelines (reclaimed dependency bubble) — see
``benchmarks/train_mux.py`` for the tracked numbers.
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.rl.coexec import (GRPOJob, MuxConfig, build_train_batch,
                             run_coexec, run_pipelined, run_sequential)
from repro.rl.rewards import RewardFn, make_reward
from repro.rl.stream import run_streaming

__all__ = ["build_train_batch", "run_training"]


def run_training(arch: str = "internlm2-1.8b", *, reduced: bool = True,
                 steps: int = 50, batch: int = 8, group: int = 4,
                 max_new: int = 8, lr: float = 3e-4, seed: int = 0,
                 log_every: int = 5, model=None, rollout: str = "static",
                 temperature: float = 1.0, num_slots: int | None = None,
                 engine_block_size: int = 1, kv: str = "contiguous",
                 kv_block_size: int = 16, sched: str = "fifo",
                 prefix_share: bool = False,
                 kernel_backend: str = "jnp", kv_dtype: str | None = None,
                 slo_bound: float = 2.0,
                 mux: str = "off", mux_staleness: int = 1, jobs: int = 2,
                 reward: str | RewardFn = "arith",
                 reward_latency: float = 0.0,
                 reward_workers: int = 2, micro_groups: int | None = None,
                 elastic: bool = False,
                 spec=None, carry: bool = False,
                 return_report: bool = False):
    """GRPO post-training through the phase-multiplexed executors.

    ``rollout`` picks the generation backend (``"static"`` scan or the
    continuous-batching serving ``"engine"``); ``mux`` picks the executor
    (see module docstring); ``reward``/``reward_latency`` pick the
    verifier (``rl.rewards.make_reward`` — a nonzero latency wraps it in
    the slow external-verifier stub, the workload ``--mux stream``'s
    reward pool hides off the critical path); ``reward`` may also be a
    row-wise verifier callable itself.  Returns ``(state,
    history)`` — or, for ``mux="coexec"``, ``(states, histories)`` dicts
    keyed by job id — plus the :class:`~repro.rl.coexec.MuxReport` when
    ``return_report``.
    """
    from repro.serve import RolloutSpec

    cfg = MuxConfig(mode=mux, max_staleness=mux_staleness,
                    reward_workers=reward_workers, micro_groups=micro_groups)
    reward_fn = (make_reward(reward, latency_s=reward_latency, seed=seed)
                 if isinstance(reward, str) else reward)
    if spec is None:
        spec = RolloutSpec(num_slots=num_slots,
                           block_size=engine_block_size, kv_layout=kv,
                           kv_block_size=kv_block_size, sched=sched,
                           prefix_share=prefix_share,
                           kernel_backend=kernel_backend, kv_dtype=kv_dtype,
                           carry=carry)

    def make_job(jid: str, job_seed: int) -> GRPOJob:
        return GRPOJob(
            jid, model=model or build_model(arch, reduced=reduced),
            seed=job_seed, steps=steps, batch=batch, group=group,
            max_new=max_new, lr=lr, temperature=temperature, rollout=rollout,
            spec=spec, carry=carry or spec.carry, slo_bound=slo_bound,
            reward_fn=reward_fn)

    if cfg.mode == "off":
        state, hist, report = run_sequential(make_job("job0", seed),
                                             log_every=log_every)
    elif cfg.mode == "pipeline":
        state, hist, report = run_pipelined(make_job("job0", seed),
                                            max_staleness=cfg.max_staleness,
                                            log_every=log_every)
    elif cfg.mode == "stream":
        state, hist, report = run_streaming(
            make_job("job0", seed), max_staleness=cfg.max_staleness,
            reward_workers=cfg.reward_workers,
            micro_groups=cfg.micro_groups, elastic=elastic,
            log_every=log_every)
    else:                                   # "coexec"
        if jobs < 1:
            raise ValueError("coexec needs >= 1 jobs")
        group_jobs = [make_job(f"job{i}", seed + i) for i in range(jobs)]
        state, hist, report = run_coexec(group_jobs,
                                         host_cache_gb=cfg.host_cache_gb,
                                         log_every=log_every)
    if return_report:
        return state, hist, report
    return state, hist


def _main():
    ap = argparse.ArgumentParser(
        description="GRPO post-training with phase-multiplexed execution",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the 2-layer float32 smoke variant; "
                         "--no-reduced trains the published widths in the "
                         "config's dtype")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--group", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rollout", choices=("static", "engine"),
                    default="static",
                    help="rollout backend: static generate scan or the "
                         "continuous-batching serving engine")
    ap.add_argument("--slots", type=int, default=None,
                    help="engine KV slots (--rollout engine; default = "
                         "batch * group)")
    ap.add_argument("--kv", choices=("contiguous", "paged"),
                    default="contiguous",
                    help="engine KV layout (--rollout engine)")
    ap.add_argument("--kv-block-size", type=int, default=16)
    ap.add_argument("--sched", choices=("fifo", "deadline", "slo"),
                    default="fifo",
                    help="engine admission policy (--rollout engine): "
                         "fifo = strict arrival order; deadline = EDF with "
                         "bounded head skipping + per-job token budgets; "
                         "slo = deadlines from the job's slowdown bound "
                         "(--slo-bound), the inter-group SLO contract")
    ap.add_argument("--slo-bound", type=float, default=2.0,
                    help="admitted slowdown bound the slo policy enforces "
                         "(core.InterGroupScheduler.slo_contract exports "
                         "this per job in a planned cluster)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="radix prompt-prefix KV sharing (--kv paged): the "
                         "GRPO group's duplicated prompt prefills once and "
                         "its full blocks are pinned under all members")
    ap.add_argument("--kernel-backend", choices=("jnp", "pallas"),
                    default="jnp",
                    help="engine decode backend (--rollout engine): jnp = "
                         "vmapped model step; pallas = batched "
                         "decode-attention kernels + fused greedy sampling "
                         "(token-identical; recurrent archs fall back)")
    ap.add_argument("--kv-dtype", choices=("auto", "int8"), default=None,
                    help="engine paged KV storage dtype (--kv paged): int8 "
                         "quantizes blocks with per-position scales, "
                         "~halving rollout KV memory per request")
    ap.add_argument("--mux", choices=("off", "pipeline", "coexec", "stream"),
                    default="off",
                    help="phase multiplexing: 'off' runs rollout and "
                         "training back-to-back (baseline); 'pipeline' "
                         "overlaps next-iteration rollout with the current "
                         "training step behind the --mux-staleness guard; "
                         "'coexec' round-robins --jobs jobs over the shared "
                         "rollout/train pools with warm-start switches; "
                         "'stream' pipelines at prompt-group granularity — "
                         "finished groups flow to the --reward-workers "
                         "reward pool and to train micro-batches while the "
                         "engine still decodes the stragglers")
    ap.add_argument("--mux-staleness", type=int, default=1,
                    help="pipeline/stream modes: max optimizer iterations "
                         "the rollout weights may lag (0 = force sync; "
                         "bit-exact to --mux off but with no overlap)")
    ap.add_argument("--carry", action="store_true",
                    help="stream mode, --rollout engine: partial-rollout "
                         "continuation — a mid-rollout weight sync suspends "
                         "live generations, swaps weights and resumes them "
                         "(Engine.reset(carry_live=True)) instead of "
                         "finishing the iteration on stale weights; "
                         "per-token weight versions feed the clip-fraction "
                         "diagnostics")
    ap.add_argument("--jobs", type=int, default=2,
                    help="coexec mode: number of co-executing jobs "
                         "(job i uses seed+i)")
    ap.add_argument("--reward", default="arith",
                    choices=("arith", "length", "format", "composite"),
                    help="verifier (rl.rewards): exact numeric match, "
                         "match + length penalty, regex format check, or "
                         "a weighted composite")
    ap.add_argument("--reward-latency", type=float, default=0.0,
                    help="wrap the verifier in the slow external-verifier "
                         "stub with this mean verdict latency (seconds); "
                         "--mux stream hides it on the reward pool")
    ap.add_argument("--reward-workers", type=int, default=2,
                    help="stream mode: reward permit-pool capacity "
                         "(concurrent verifier calls)")
    ap.add_argument("--micro-groups", type=int, default=None,
                    help="stream mode: rewarded groups per train "
                         "micro-step (default: all groups of an iteration "
                         "in one bit-exact full-batch step)")
    ap.add_argument("--elastic", action="store_true",
                    help="stream mode: close the loop on the reward "
                         "permit pool — each iteration reads the runtime's "
                         "MetricsSnapshot and grows the pool toward "
                         "--reward-workers when verifiers queue, shrinks "
                         "it when the pool idles (held permits are never "
                         "revoked)")
    args = ap.parse_args()
    enable_compile_cache()
    from repro.serve import RolloutSpec
    spec = RolloutSpec.from_args(args)
    t0 = time.time()
    out = run_training(args.arch, reduced=args.reduced, steps=args.steps,
                       batch=args.batch, group=args.group,
                       max_new=args.max_new, lr=args.lr, seed=args.seed,
                       rollout=args.rollout, spec=spec, carry=args.carry,
                       slo_bound=args.slo_bound,
                       mux=args.mux, mux_staleness=args.mux_staleness,
                       jobs=args.jobs, reward=args.reward,
                       reward_latency=args.reward_latency,
                       reward_workers=args.reward_workers,
                       micro_groups=args.micro_groups, elastic=args.elastic,
                       return_report=True)
    _, hist, report = out
    wall = time.time() - t0
    if args.mux == "coexec":
        finals = {jid: h[-1]["reward"] for jid, h in hist.items() if h}
        print(f"done in {wall:.1f}s; final rewards "
              + ", ".join(f"{j}={r:.3f}" for j, r in sorted(finals.items())))
    else:
        print(f"done in {wall:.1f}s; final reward {hist[-1]['reward']:.3f}")
    s = report.summary()
    reward_part = (f"reward busy {s['total_reward_s']:.2f}s, "
                   if s["total_reward_s"] else "")
    print(f"mux={report.mode}: rollout busy {s['total_rollout_s']:.2f}s, "
          f"train busy {s['total_train_s']:.2f}s, {reward_part}"
          f"overlap {s['overlap_s']:.2f}s "
          f"({s['reclaimed_bubble_frac']:.0%} of the back-to-back bubble "
          f"reclaimed)")


if __name__ == "__main__":
    _main()
