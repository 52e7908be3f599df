"""Phase-multiplexed GRPO benchmark: back-to-back vs pipelined vs co-executed.

Runs the *same* GRPO workload (engine-served rollouts, real train steps)
through the three ``rl.coexec`` executors and measures what the paper's
phase multiplexing is for — the dependency bubble between rollout and
training, and how much of it each schedule reclaims:

  * **off** — rollout and training back-to-back (the standard-
    disaggregation baseline RollMux beats); by construction overlap = 0.
  * **pipeline** — rollout of iteration ``k+1`` overlaps training on
    iteration ``k`` behind the ``--staleness`` on-policy guard.
  * **coexec** — ``--jobs`` independent jobs round-robin the shared
    rollout/train permit pools with warm-start context switches (this is
    the two-job co-execution of paper Fig 1-bottom, running for real).
  * **stream** — group-level pipelining inside the job (``rl.stream``):
    finished GRPO prompt groups flow to the reward permit pool and to
    train micro-batches while the engine still decodes stragglers.  Run
    twice: with instant rewards (comparable to pipeline) and in a
    slow-verifier pair — ``off_slow_reward`` verifies each group inline
    through an external-verifier stub whose per-group latency is
    calibrated to ``--reward-latency-frac`` of the measured rollout
    phase, ``stream_slow_reward`` hides the same verification work on
    ``--reward-workers`` reward-pool workers.

A seventh scenario benchmarks the *multi-turn agentic* bubble: episodes
that alternate generation with tool calls (``rl.agentic.run_episodes``)
run once with the engine's suspend/resume lifecycle (a tool-waiting
episode's slot is reclaimed the moment the boundary token is sampled)
and once with the hold-the-slot baseline (what an engine without suspend
support does).  Tokens are identical by construction; the cost is
measured in deterministic virtual scheduler ticks, so the reclaimed
fraction of the tool-latency bubble is machine-independent and CI holds
it to an absolute floor.

Reported per mode: wall time, per-step time, useful completion tokens/s,
measured rollout/train busy time, rollout×train overlap, and the fraction
of the back-to-back bubble (``min(Σroll, Σtrain)``) reclaimed.  The
engine-measured :class:`PhaseProfile` records are also pushed through the
co-execution simulator (``core.simulate_profiles``) so modeled-vs-served
iteration times appear side by side.  Writes ``BENCH_train_mux.json``
(``--quick`` shrinks the workload and writes ``BENCH_train_mux_quick.json``
— the same-config baseline the CI bench guard diffs against).

    PYTHONPATH=src python benchmarks/train_mux.py
    PYTHONPATH=src python benchmarks/train_mux.py --quick
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core.simulator import simulate_profiles
from repro.data import tokenizer as tok
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.rl.agentic import CountdownToolEnv, run_episodes
from repro.rl.coexec import (GRPOJob, run_coexec, run_pipelined,
                             run_sequential)
from repro.rl.rewards import ExternalVerifier, arithmetic_reward
from repro.rl.stream import run_streaming
from repro.serve import Engine, EngineConfig, Request


def serial_group_verifier(fn, group: int):
    """Inline-baseline shape of external verification: the driver submits
    one verification call per GRPO group, serially, on the critical path —
    the same per-group work the streaming executor hides on the reward
    pool."""
    def wrapped(completions, mask, answers):
        outs = [fn(completions[i:i + group], mask[i:i + group],
                   answers[i:i + group])
                for i in range(0, len(answers), group)]
        return np.concatenate(outs)
    return wrapped


def run_agentic_scenario(model, *, episodes: int, max_new: int,
                         slots: int, tool_latency_ticks: int, turns: int,
                         tool_len: int, seed: int) -> dict:
    """Multi-turn episodes, suspend vs hold-the-slot, in virtual ticks.

    Three deterministic runs of the *same* token work: ``suspend`` (slot
    reclaimed at every tool boundary), ``hold`` (tool-waiting episodes
    keep their slot — admission stalls behind the tool latency) and
    ``ideal`` (zero-latency tools: the floor no schedule can beat).  The
    reclaimed-bubble fraction is ``(hold - suspend) / (hold - ideal)``;
    because ticks count engine scheduler steps, not seconds, the number
    is identical on every runner and is guarded by an absolute CI floor.
    """
    import jax

    params = model.init(jax.random.PRNGKey(seed))
    max_seq = 8 + max_new + turns * tool_len

    def engine():
        return Engine(model, params, EngineConfig(
            num_slots=slots, max_seq_len=max_seq, temperature=0.0))

    # probe the greedy path for a boundary token that fires early — same
    # trick the engine tests use, deterministic for a given seed
    probe = engine()
    probe.submit(Request(
        rid=0, prompt=np.asarray(tok.encode("1+2=", bos=True), np.int32),
        max_new_tokens=max_new))
    [ref] = probe.run()
    env = CountdownToolEnv((ref.tokens[2],), vocab=model.cfg.vocab_size,
                           turns=turns, tool_len=tool_len)
    # long-tail prompt mix: most episodes hit the tool boundary, the rest
    # decode straight through and keep the pool busy
    texts = ["1+2=", "0+1=", "1+2=", "3+4=", "1+2=", "2+3="]
    prompts = [np.asarray(tok.encode(texts[i % len(texts)], bos=True),
                          np.int32) for i in range(episodes)]

    runs = {}
    for name, latency, hold in (("suspend", tool_latency_ticks, False),
                                ("hold", tool_latency_ticks, True),
                                ("ideal", 0, False)):
        eps, stats = run_episodes(engine(), env, prompts,
                                  max_new_tokens=max_new,
                                  tool_latency_ticks=latency,
                                  hold_slots=hold)
        runs[name] = (eps, stats)
    sus, hol, ide = (runs[k][1]["ticks"] for k in ("suspend", "hold",
                                                   "ideal"))
    # identical tokens across schedules — the bench only re-times them
    for a, b in zip(runs["suspend"][0], runs["hold"][0]):
        assert a.full_completion == b.full_completion
    gen_tokens = sum(len(e.gen_tokens) for e in runs["suspend"][0])
    bubble = max(hol - ide, 1)
    return {
        "episodes": episodes,
        "turns": runs["suspend"][1]["turns"],
        "tool_calls": runs["suspend"][1]["tool_calls"],
        "gen_tokens": gen_tokens,
        "ticks_suspend": sus,
        "ticks_hold": hol,
        "ticks_ideal": ide,
        "speedup_suspend_vs_hold": hol / max(sus, 1),
        "reclaimed_bubble_frac": (hol - sus) / bubble,
    }


def _mode_summary(histories, report) -> dict:
    """Collapse one executor run into the tracked numbers."""
    if isinstance(histories, dict):                 # coexec: per-job
        steps = sum(len(h) for h in histories.values())
        tokens = sum(r["tokens"] for h in histories.values() for r in h)
    else:
        steps = len(histories)
        tokens = sum(r["tokens"] for r in histories)
    s = report.summary()
    return {
        "steps": steps,
        "tokens": tokens,
        "wall_s": s["wall_s"],
        "step_time_s": s["wall_s"] / max(steps, 1),
        "tok_per_s": tokens / max(s["wall_s"], 1e-9),
        "total_rollout_s": s["total_rollout_s"],
        "total_train_s": s["total_train_s"],
        "total_reward_s": s["total_reward_s"],
        "overlap_s": s["overlap_s"],
        "bubble_back_to_back_s": s["bubble_back_to_back_s"],
        "reclaimed_bubble_frac": s["reclaimed_bubble_frac"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--group", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=None,
                    help="engine KV slots (default batch*group)")
    ap.add_argument("--block-size", type=int, default=4,
                    help="engine decode steps fused per scheduler tick")
    ap.add_argument("--kv", choices=("contiguous", "paged"),
                    default="contiguous")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--staleness", type=int, default=1,
                    help="pipeline/stream on-policy staleness guard")
    ap.add_argument("--jobs", type=int, default=2,
                    help="co-executing jobs in coexec mode")
    ap.add_argument("--reward-workers", type=int, default=2,
                    help="stream mode: reward permit-pool capacity")
    ap.add_argument("--reward-latency-frac", type=float, default=0.25,
                    help="slow-verifier scenario: per-group verification "
                         "latency as a fraction of the measured rollout "
                         "phase (calibrated from the warmup run)")
    ap.add_argument("--tool-latency-ticks", type=int, default=16,
                    help="agentic scenario: engine ticks each tool call "
                         "takes (the bubble suspend/resume reclaims)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=1,
                    help="run each mode this many times, keep its best "
                         "(min-wall) run and the max reclaimed-bubble "
                         "fraction — wall-clock noise rejection on shared "
                         "CI runners")
    ap.add_argument("--quick", action="store_true",
                    help="small CI workload (best-of-2 repeats); writes the "
                         "*_quick.json the bench guard diffs (same config "
                         "every run)")
    ap.add_argument("--json", default=None,
                    help="report path ('' disables; default "
                         "BENCH_train_mux[_quick].json at the repo root)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.quick:
        # batch 4 = four GRPO groups per iteration: enough sub-phase
        # granularity for the streaming scenarios to show their overlap
        args.steps, args.batch, args.group, args.max_new = 6, 4, 2, 8
        args.repeats = max(args.repeats, 2)
        args.tool_latency_ticks = 10
    # agentic scenario shape: more episodes than slots (so reclaimed slots
    # actually admit waiting work) and 2 tool turns per episode
    agentic_cfg = dict(
        episodes=6 if args.quick else 8,
        max_new=10 if args.quick else 16,
        slots=2, turns=2, tool_len=3,
        tool_latency_ticks=args.tool_latency_ticks)
    # micro-batched trainer size for the slow-verifier streaming scenario:
    # half the groups per iteration, so the trainer overlaps the decode and
    # verification of the other half (derived from config => deterministic)
    stream_micro = max(1, args.batch // 2)
    if args.json is None:
        name = "BENCH_train_mux_quick.json" if args.quick \
            else "BENCH_train_mux.json"
        args.json = os.path.join(os.path.dirname(__file__), "..", name)

    model = build_model(args.arch, reduced=True)

    def make_job(jid: str, seed: int, reward_fn=None) -> GRPOJob:
        return GRPOJob(jid, model=model, seed=seed, steps=args.steps,
                       batch=args.batch, group=args.group,
                       max_new=args.max_new, temperature=args.temperature,
                       rollout="engine", num_slots=args.slots,
                       engine_block_size=args.block_size, kv=args.kv,
                       reward_fn=reward_fn)

    # warmup: compile prefill/decode/train for this shape once, off the clock
    # (the jitted train step and engine fns are shared across jobs); the
    # post-compile rollout duration also calibrates the slow-verifier
    # latency below
    _, _, r_warm = run_sequential(make_job("warmup", args.seed), steps=2,
                                  log_every=0)
    t_roll = r_warm.profiles["warmup"].rollout_s[-1]
    reward_latency = args.reward_latency_frac * t_roll
    # ... and the micro-batch train shape the streaming scenario uses
    wj = make_job("warmup", args.seed)
    wj.steps = 1
    run_streaming(wj, max_staleness=1, micro_groups=stream_micro)

    print(f"# {args.arch}: {args.steps} steps x batch {args.batch} x group "
          f"{args.group}, {args.max_new} new tokens, engine rollout "
          f"(block {args.block_size}, kv {args.kv}), best of "
          f"{args.repeats} repeat(s)")

    def best_of(run_mode):
        """Best (min-wall) summary across repeats; the reclaimed-bubble
        fraction is a property of the schedule, not of timing noise, so
        report the max across repeats (like serve's capacity numbers)."""
        runs = [run_mode() for _ in range(max(args.repeats, 1))]
        best = min(runs, key=lambda m: m["wall_s"])
        best["reclaimed_bubble_frac"] = max(r["reclaimed_bubble_frac"]
                                            for r in runs)
        return best

    modes: dict[str, dict] = {}

    def run_off():
        _, h, r = run_sequential(make_job("job0", args.seed))
        return _mode_summary(h, r)

    def run_pipe():
        _, h, r = run_pipelined(make_job("job0", args.seed),
                                max_staleness=args.staleness)
        m = _mode_summary(h, r)
        m["staleness"] = max((rec["rollout_staleness"] for rec in h),
                             default=0)
        return m

    co_reports = []

    def run_co():
        jobs = [make_job(f"job{i}", args.seed + i) for i in range(args.jobs)]
        _, h, r = run_coexec(jobs)
        co_reports.append(r)
        return _mode_summary(h, r)

    def run_stream():
        _, h, r = run_streaming(make_job("job0", args.seed),
                                max_staleness=args.staleness,
                                reward_workers=args.reward_workers)
        m = _mode_summary(h, r)
        m["staleness"] = max((rec["rollout_staleness"] for rec in h),
                             default=0)
        return m

    def run_off_slow():
        # inline baseline: the driver verifies each group through the slow
        # external verifier serially, on the critical path (run_sequential
        # calls the reward inside its train permit)
        job = make_job("job0", args.seed, reward_fn=serial_group_verifier(
            ExternalVerifier(arithmetic_reward, latency_s=reward_latency,
                             jitter=0.1, seed=args.seed), args.group))
        _, h, r = run_sequential(job)
        return _mode_summary(h, r)

    def run_stream_slow():
        # same per-group verification work, hidden on the reward pool
        # while the engine decodes stragglers and the micro-batched
        # trainer steps on already-verified groups
        job = make_job("job0", args.seed, reward_fn=ExternalVerifier(
            arithmetic_reward, latency_s=reward_latency, jitter=0.1,
            seed=args.seed))
        _, h, r = run_streaming(job, max_staleness=args.staleness,
                                reward_workers=args.reward_workers,
                                micro_groups=stream_micro)
        return _mode_summary(h, r)

    modes["off"] = best_of(run_off)
    modes["pipeline"] = best_of(run_pipe)
    modes["coexec"] = best_of(run_co)
    modes["stream"] = best_of(run_stream)
    modes["off_slow_reward"] = best_of(run_off_slow)
    modes["stream_slow_reward"] = best_of(run_stream_slow)
    r_co = co_reports[-1]

    # multi-turn agentic bubble: suspend/resume vs hold-the-slot (virtual
    # ticks — deterministic, no repeats needed)
    agentic = run_agentic_scenario(model, seed=args.seed, **agentic_cfg)
    print(f"agentic multi-turn ({agentic_cfg['episodes']} episodes x "
          f"{agentic_cfg['turns']} tool turns, "
          f"{agentic_cfg['tool_latency_ticks']}-tick tools, "
          f"{agentic_cfg['slots']} slots): "
          f"hold {agentic['ticks_hold']} ticks -> suspend "
          f"{agentic['ticks_suspend']} ticks (ideal "
          f"{agentic['ticks_ideal']}), "
          f"{agentic['speedup_suspend_vs_hold']:.2f}x, "
          f"{agentic['reclaimed_bubble_frac']:.0%} of the tool bubble "
          f"reclaimed")

    for name, m in modes.items():
        print(f"{name:18s}: {m['wall_s']:6.2f}s wall "
              f"({m['step_time_s']*1e3:6.1f} ms/step), "
              f"{m['tok_per_s']:7.1f} tok/s | roll {m['total_rollout_s']:.2f}s "
              f"train {m['total_train_s']:.2f}s "
              f"reward {m['total_reward_s']:.2f}s "
              f"overlap {m['overlap_s']:.2f}s "
              f"-> {m['reclaimed_bubble_frac']:.0%} of bubble reclaimed")

    # feed the engine-measured phase profiles back into the co-execution
    # simulator: served durations in, predicted group iteration times out
    profiles = [p for jid, p in sorted(r_co.profiles.items())]
    sim = simulate_profiles(profiles)
    measured_iter = modes["coexec"]["wall_s"] / max(args.steps, 1)
    print(f"simulator on measured profiles: iter_time "
          f"{ {j: round(t, 3) for j, t in sim.iter_time.items()} } "
          f"(measured coexec {measured_iter:.3f}s/iter), "
          f"rollout bubble {sim.rollout_bubble:.0%}, "
          f"train bubble {sim.train_bubble:.0%}")

    speed_pipe = modes["off"]["wall_s"] / max(modes["pipeline"]["wall_s"], 1e-9)
    reclaimed = modes["pipeline"]["reclaimed_bubble_frac"]
    print(f"pipeline vs back-to-back: {speed_pipe:.2f}x wall, "
          f"{reclaimed:.0%} of the dependency bubble reclaimed")
    speed_stream_slow = (modes["off_slow_reward"]["wall_s"]
                         / max(modes["stream_slow_reward"]["wall_s"], 1e-9))
    print(f"stream vs inline under slow rewards "
          f"({reward_latency * 1e3:.0f} ms/group = "
          f"{args.reward_latency_frac:.0%} of rollout): "
          f"{speed_stream_slow:.2f}x wall, "
          f"{modes['stream_slow_reward']['reclaimed_bubble_frac']:.0%} of "
          f"the three-pool bubble reclaimed")

    if args.json:
        report = {
            "arch": args.arch,
            "config": {
                "steps": args.steps, "batch": args.batch,
                "group": args.group, "max_new": args.max_new,
                "slots": args.slots, "block_size": args.block_size,
                "kv": args.kv, "temperature": args.temperature,
                "staleness": args.staleness, "jobs": args.jobs,
                "reward_workers": args.reward_workers,
                "stream_micro_groups": stream_micro,
                # the *rule*, not the machine-calibrated seconds — the
                # config block must stay runner-independent for the CI
                # baseline equality check
                "reward_latency_frac": args.reward_latency_frac,
                "seed": args.seed, "repeats": args.repeats,
                "quick": args.quick,
                "agentic": agentic_cfg,
            },
            "calibration": {"rollout_phase_s": t_roll,
                            "reward_latency_s": reward_latency},
            "modes": modes,
            "speedup_pipeline_vs_off": speed_pipe,
            "speedup_coexec_vs_off": (
                # per-step time ratio: coexec runs --jobs x the work
                modes["off"]["step_time_s"]
                / max(modes["coexec"]["step_time_s"], 1e-9)),
            "speedup_stream_vs_off_slow_reward": speed_stream_slow,
            "reclaimed_bubble_frac_pipeline": reclaimed,
            "reclaimed_bubble_frac_coexec":
                modes["coexec"]["reclaimed_bubble_frac"],
            "reclaimed_bubble_frac_stream":
                modes["stream"]["reclaimed_bubble_frac"],
            "reclaimed_bubble_frac_stream_slow":
                modes["stream_slow_reward"]["reclaimed_bubble_frac"],
            "agentic": agentic,
            "simulator_on_measured_profiles": {
                "iter_time_s": dict(sim.iter_time),
                "rollout_bubble": sim.rollout_bubble,
                "train_bubble": sim.train_bubble,
            },
        }
        path = os.path.abspath(args.json)
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {path}")
    return modes


if __name__ == "__main__":
    main()
