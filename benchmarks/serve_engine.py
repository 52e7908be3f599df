"""Trace-driven rollout-serving benchmark: continuous batching vs static,
contiguous vs paged KV.

Replays a Poisson-arrival trace with heavy-tailed per-request decode
budgets (the paper's long-tail response-length model, ``core.distributions``)
through three servers sharing one model + weights:

  * **engine** — ``repro.serve.Engine``: FIFO queue over a fixed slot pool
    of contiguous ``max_seq_len`` KV stripes, prefill-into-free-slot
    admission, slot recycle on EOS/budget, decode batched across live
    slots (``--block-size`` fused steps per tick);
  * **paged** — the same engine on the block-pool KV layout at **equal KV
    memory**: the pool holds exactly as many ``--kv-block-size``-token
    blocks as ``--slots`` contiguous stripes, but requests reserve only
    their own budget's worth of blocks, so the long-tail trace packs more
    live requests into the same bytes (``--paged-slots-factor`` × more
    decode slots are offered; blocks are the binding constraint);
  * **static** — the legacy ``serve_batch`` path: requests are grouped
    FIFO into fixed batches of ``--slots``; each batch waits for its last
    member to arrive, then runs prefill + a fixed ``--max-new``-step decode
    scan end-to-end (no early exit, no refill).

Two scheduler-path scenarios ride along (the SLO-admission / prefix-
sharing tentpole's tracked numbers): **mixed-priority** replays a
two-class trace (interactive: short + tight self-calibrated deadlines;
batch: long-tail bulk) under FIFO vs deadline admission and reports
per-class p95 latency and deadline-attainment %; **prefix sharing**
replays a GRPO-group trace (each prompt submitted ``group`` times) through
the paged engine at one fixed pool size with and without radix sharing and
reports peak concurrency at equal KV memory plus blocks saved.

The **chat trace** scenario exercises the content-addressed radix tree
beyond the GRPO shape: a multi-tenant conversation workload (shared
system prompt, per-tenant preambles, growing multi-turn histories,
fan-out retries) runs unshared, tree-shared, and tree-shared through the
KV-aware disagg router (two prefill engines), and reports the
blocks-saved ratio against both the unshared run and the best a flat
exact-match index could do (``radix.saved_over_flat`` — the tree's
cross-request partial-prefix margin), TTFT speedup, requests KV-routed,
and a greedy token-equality bit (``radix.tokens_match``).

Both timelines start at the first arrival; useful tokens are counted
identically (per-request budget).  Response lengths are modeled entirely
by the budgets — the EOS channel is disabled in both servers (random
weights emit EOS at random, which would make the two servers decode
different useful-token totals and add noise to the comparison; EOS-driven
slot recycling is covered by tests/test_serve_engine.py).  Reports token
throughput, request latency (mean / p95), time-to-first-token, slot/block
utilization and peak concurrency, and writes the whole report to
``BENCH_serve.json`` at the repo root so the trajectory is tracked per PR.

    PYTHONPATH=src python benchmarks/serve_engine.py
    PYTHONPATH=src python benchmarks/serve_engine.py --arch rwkv6-7b
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributions import sample_response_fractions
from repro.data import tokenizer as tok
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.rl import SamplerConfig, generate
from repro.serve import (DisaggConfig, DisaggRouter, ElasticConfig,
                         ElasticController, Engine, EngineConfig, Request,
                         blocks_for, run_trace)

PROMPT_BUCKETS = (8, 16)
NO_EOS = -1           # lengths come from budgets; see module docstring


def make_trace(rng: np.random.Generator, n: int, rate: float, cap: int):
    """Poisson arrivals + lognormal (long-tail) decode budgets + bucketed
    prompts. Returns a list of Requests (prompts are PAD-left-padded to a
    bucket so both servers compile O(#buckets) prefill variants)."""
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    arrivals -= arrivals[0]                       # timeline starts at t=0
    budgets = np.maximum(
        1, (sample_response_fractions(rng, n) * cap).astype(int))
    reqs = []
    for i in range(n):
        # operand width 2..6 digits so both prompt buckets really occur
        hi = 10 ** int(rng.integers(2, 7))
        text = f"{int(rng.integers(10, hi))}+{int(rng.integers(10, hi))}="
        ids = tok.encode(text, bos=True)
        bucket = next(b for b in PROMPT_BUCKETS if b >= len(ids))
        prompt = tok.pad_batch([ids], bucket)[0]
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(budgets[i]),
                            arrival_time=float(arrivals[i])))
    return reqs


def run_static(model, params, reqs, batch_size: int, max_new: int,
               seed: int = 0):
    """Static-batch timeline: FIFO batches of ``batch_size``; batch i starts
    at max(prev batch end, its last member's arrival) and costs one
    measured ``generate`` wall time (fixed ``max_new`` decode steps)."""
    key = jax.random.PRNGKey(seed)
    sampler = SamplerConfig(max_new_tokens=max_new, temperature=0.0,
                            eos_id=NO_EOS)
    t_free = 0.0
    latencies, ttfts, useful = [], [], 0
    for i in range(0, len(reqs), batch_size):
        batch = reqs[i:i + batch_size]
        plen = max(r.prompt_len for r in batch)
        prompts = jnp.asarray(np.stack([
            tok.pad_batch([r.prompt.tolist()], plen)[0] for r in batch]))
        t0 = time.perf_counter()
        out = generate(model, params, prompts, key, sampler)
        jax.block_until_ready(out["completions"])
        wall = time.perf_counter() - t0
        start = max(t_free, max(r.arrival_time for r in batch))
        end = start + wall
        t_free = end
        mask = np.asarray(out["mask"])
        for j, r in enumerate(batch):
            n_eos = int(mask[j].sum())
            useful += min(n_eos, r.max_new_tokens)
            latencies.append(end - r.arrival_time)
            # the one-shot generate only materialises tokens at batch end
            ttfts.append(end - r.arrival_time)
    lat = np.array(latencies)
    return {
        "makespan_s": t_free,
        "tokens": useful,
        "tok_per_s": useful / max(t_free, 1e-9),
        "latency_mean_s": float(lat.mean()),
        "latency_p95_s": float(np.quantile(lat, 0.95)),
        "ttft_mean_s": float(np.mean(ttfts)),
    }


def _strip_outputs(report: dict) -> dict:
    return {k: v for k, v in report.items() if k != "outputs"}


# ---------------------------------------------------------------------------
# Scenario: mixed-priority traffic under deadline-aware admission
# ---------------------------------------------------------------------------
def _class_stats(outputs, cls):
    outs = [o for o in outputs if o.priority == cls]
    lat = np.array([o.finish_time - o.arrival_time for o in outs])
    met = sum(o.finish_time <= o.deadline for o in outs
              if o.deadline is not None)
    n_dl = sum(o.deadline is not None for o in outs)
    return {
        "n": len(outs),
        "latency_p95_s": float(np.quantile(lat, 0.95)) if len(lat) else 0.0,
        "latency_mean_s": float(lat.mean()) if len(lat) else 0.0,
        "deadline_attainment": met / n_dl if n_dl else 1.0,
    }


def run_priority_scenario(model, params, rng, *, n: int, rate: float,
                          cap: int, slots: int, block_size: int):
    """Two traffic classes through one engine, FIFO vs deadline admission.

    *Interactive* requests (priority 1, ~1/3 of traffic) have short decode
    budgets and tight deadlines; *batch* requests (priority 0) are the
    long-tail bulk with loose deadlines.  Deadlines are self-calibrated
    from a FIFO dry run (per-token service latency measured on this
    machine, so attainment is meaningful on any runner), then the same
    deadline-tagged trace replays under ``--sched fifo`` and ``--sched
    deadline``.  The deadline policy's head skipping should buy the
    interactive class p95/attainment at bounded cost to batch traffic —
    the per-class numbers below are the tracked evidence.
    """
    reqs = make_trace(rng, n, rate, cap)
    interactive = rng.random(n) < (1 / 3)
    for r, it in zip(reqs, interactive):
        if it:                              # short, urgent
            r.priority = 1
            r.max_new_tokens = max(1, r.max_new_tokens // 4)
        r.job_id = "interactive" if it else "batch"
    max_len = max(PROMPT_BUCKETS) + cap

    def fresh(sched):
        return Engine(model, params, EngineConfig(
            num_slots=slots, max_seq_len=max_len, temperature=0.0,
            eos_id=NO_EOS, block_size=block_size, sched=sched))

    # calibration: measure this machine's per-token service latency, then
    # rescale arrivals AND deadlines by it — offered load and slack are
    # expressed in service-time units, so queueing depth (and hence the
    # fifo-vs-deadline contrast) is comparable across runner speeds
    calib = run_trace(fresh("fifo"), reqs)
    # per-request per-token wall latency when the pool is busy: one decode
    # step serves all slots at once, so a single request sees roughly
    # slots / aggregate-throughput per token.  (Per-request timestamps are
    # too coarse here: fused decode blocks deliver a short request's whole
    # budget in one host-visible step.)
    per_tok = slots / max(calib["tok_per_s"], 1e-9)
    mean_budget = float(np.mean([r.max_new_tokens for r in reqs]))
    overload = 1.3                          # offered load vs service capacity
    gap = mean_budget * per_tok / (slots * overload)
    arrivals = np.cumsum(rng.exponential(gap, size=n))
    arrivals -= arrivals[0]
    for r, t in zip(reqs, arrivals):
        r.arrival_time = float(t)
        slack = 4.0 if r.priority else 10.0
        r.deadline = (r.arrival_time
                      + slack * per_tok * (r.max_new_tokens + r.prompt_len))

    out = {"config": {"n": n, "interactive_frac": float(interactive.mean()),
                      "overload": overload},
           "per_token_calib_s": per_tok}
    for sched in ("fifo", "deadline"):
        res = run_trace(fresh(sched), reqs)
        out[sched] = {
            "tok_per_s": res["tok_per_s"],
            "deadline_attainment": res.get("deadline_attainment", 1.0),
            "interactive": _class_stats(res["outputs"], 1),
            "batch": _class_stats(res["outputs"], 0),
        }
    out["attainment_gain_interactive"] = (
        out["deadline"]["interactive"]["deadline_attainment"]
        - out["fifo"]["interactive"]["deadline_attainment"])
    return out


# ---------------------------------------------------------------------------
# Scenario: GRPO-group traffic with radix prefix sharing at equal KV memory
# ---------------------------------------------------------------------------
def run_prefix_scenario(model, params, rng, *, n_groups: int, group: int,
                        rate: float, block_size: int):
    """GRPO-shaped trace (every prompt submitted ``group`` times, members
    arriving together) through the paged engine at one fixed KV pool size,
    with and without radix prefix sharing.

    Sharing turns each group's ``group`` prompt copies into one prefill
    plus pinned blocks, so paged admission — which gates on *net new*
    blocks — packs strictly more live requests into the same KV bytes.
    Tracked: peak concurrency both ways (the admitted-at-equal-memory
    claim), blocks saved and the saved fraction of all prompt-block
    traffic, prefill hit counts, and throughput.
    """
    bs = block_size
    prompt_bucket = 16                      # 2 full KV blocks per prompt
    cap = 16
    max_len = prompt_bucket + cap
    stripes = 3                             # pool = 3 contiguous stripes
    num_blocks = stripes * blocks_for(max_len, bs)
    slots = 2 * stripes + 2                 # slots non-binding; blocks bind
    arrivals = np.cumsum(rng.exponential(group / rate, size=n_groups))
    arrivals -= arrivals[0]
    reqs, rid = [], 0
    prompt_blocks_total = 0
    for gi in range(n_groups):
        hi = 10 ** int(rng.integers(4, 7))  # wide operands: bucket-16 prompt
        text = f"{int(rng.integers(1000, hi))}+{int(rng.integers(1000, hi))}="
        ids = tok.encode(text, bos=True)
        prompt = tok.pad_batch([ids], prompt_bucket)[0]
        budgets = np.maximum(1, (sample_response_fractions(rng, group)
                                 * cap).astype(int))
        for m in range(group):
            reqs.append(Request(
                rid=rid, prompt=prompt, max_new_tokens=int(budgets[m]),
                arrival_time=float(arrivals[gi]), prefix_key=("g", gi)))
            prompt_blocks_total += prompt_bucket // bs
            rid += 1

    def fresh(share: bool):
        return Engine(model, params, EngineConfig(
            num_slots=slots, max_seq_len=max_len, temperature=0.0,
            eos_id=NO_EOS, block_size=1, kv_layout="paged",
            kv_block_size=bs, num_kv_blocks=num_blocks,
            prefix_share=share))

    for share in (False, True):             # warmup: compile both paths
        warm = fresh(share)
        for j in range(2):
            warm.submit(Request(rid=-1 - j,
                                prompt=np.full(prompt_bucket, tok.PAD,
                                               np.int32),
                                max_new_tokens=1, prefix_key=("w", 0)))
        warm.run()

    runs = {}
    for name, share in (("unshared", False), ("shared", True)):
        res = run_trace(fresh(share), reqs)
        runs[name] = {
            "tok_per_s": res["tok_per_s"],
            "latency_p95_s": res["latency_p95_s"],
            "peak_active": res["peak_active"],
            "peak_kv_blocks": res["peak_kv_blocks"],
        }
        if "prefix" in res:
            runs[name]["prefix"] = res["prefix"]
    saved = runs["shared"]["prefix"]["blocks_saved"]
    return {
        "config": {"n_groups": n_groups, "group": group,
                   "kv_block_size": bs, "num_kv_blocks": num_blocks,
                   "slots": slots, "prompt_bucket": prompt_bucket,
                   "cap": cap},
        "unshared": runs["unshared"],
        "shared": runs["shared"],
        "blocks_saved": saved,
        "blocks_saved_ratio": saved / max(prompt_blocks_total, 1),
        "extra_concurrency_at_equal_memory": (
            runs["shared"]["peak_active"] - runs["unshared"]["peak_active"]),
    }


# ---------------------------------------------------------------------------
# Scenario: multi-tenant chat trace through the content-addressed radix tree
# ---------------------------------------------------------------------------
def run_chat_scenario(model, params, rng, *, n_tenants: int = 3,
                      turns: int = 3, fanout: int = 3, block_size: int = 4,
                      max_new: int = 6, turn_gap_s: float = 0.25,
                      repeats: int = 2):
    """Multi-tenant multi-turn chat replay: the radix tree's cross-request /
    cross-tenant / cross-turn sharing against two baselines.

    The trace is built from block-aligned content chunks: a **system**
    preamble (2 blocks) shared by every tenant, one **tenant** chunk, and
    per turn a **user** chunk plus an **assistant** chunk appended to the
    history — so turn ``k``'s prompt extends turn ``k-1``'s registered
    path, and each turn is submitted ``fanout`` times (parallel
    candidates over the same history, the chat analogue of a GRPO
    group).  Nothing carries a ``prefix_key``: all sharing is by content.

    Three arms at identical pool sizes: **unshared** paged engine,
    **shared** (radix tree), and **disagg** — two prefill engines behind
    KV-aware routing, each with its own tree, so repeats steer to their
    prefix holder (``kv_routed``).  Tracked (CI-floored as ``radix.*``):

    * ``blocks_saved_ratio`` — shared prompt blocks / all prompt-block
      traffic.  Must beat ``flat_index_ceiling``, the analytic best a
      flat per-group exact-duplicate index (the pre-radix design) could
      reach on this trace — only the ``fanout`` copies of one prompt can
      share there, never cross-turn or cross-tenant prefixes.
    * ``ttft_speedup`` — unshared/shared mean TTFT: exact repeats admit
      with zero prefill compute, extensions prefill only their new
      blocks.
    * ``tokens_match`` — greedy outputs bit-identical across all arms.
    """
    bs = block_size

    def chunk(n_blocks):
        # byte-range ids only: no PAD/BOS/EOS in synthetic chat content
        return rng.integers(1, 256, size=n_blocks * bs).astype(np.int32)

    # heavy system preamble + multi-block chat turns: prefill is the
    # dominant per-request cost, which is exactly what exact hits skip
    sys_c = chunk(12)
    hist = [np.concatenate([sys_c, chunk(2)]) for _ in range(n_tenants)]
    reqs, rid, total_blocks, flat_dup, unique = [], 0, 0, 0, set()
    for k in range(turns):
        for t in range(n_tenants):
            prompt = np.concatenate([hist[t], chunk(2)])
            n_blocks = len(prompt) // bs
            for _ in range(fanout):
                # turns arrive in waves: turn k routes (and matches)
                # against the trees turn k-1 registered
                reqs.append(Request(rid=rid, prompt=prompt.copy(),
                                    max_new_tokens=max_new,
                                    arrival_time=k * turn_gap_s))
                total_blocks += n_blocks
                rid += 1
            # a flat exact-duplicate index shares only the non-donor copies
            flat_dup += (fanout - 1) * n_blocks
            for d in range(n_blocks):
                unique.add(prompt[d * bs:(d + 1) * bs].tobytes())
            hist[t] = np.concatenate([prompt, chunk(2)])
    max_len = max(r.total_budget for r in reqs)
    slots = n_tenants * fanout
    # generous pool: tree pins + every wave live, no eviction noise
    num_blocks = slots * blocks_for(max_len, bs) + 2 * len(unique)

    def mono(share: bool):
        return Engine(model, params, EngineConfig(
            num_slots=slots, max_seq_len=max_len, temperature=0.0,
            eos_id=NO_EOS, block_size=1, kv_layout="paged",
            kv_block_size=bs, num_kv_blocks=num_blocks,
            prefix_share=share))

    def disagg():
        return DisaggRouter(model, params, DisaggConfig(
            prefill_slots=2, decode_slots=slots, max_seq_len=max_len,
            temperature=0.0, eos_id=NO_EOS, kv_layout="paged",
            kv_block_size=bs, decode_kv_blocks=num_blocks,
            prefix_share=True, prefill_engines=2, kv_routing="kv_aware"))

    arms, toks, kv_routed, shared_stats = {}, {}, 0, None
    for name, fresh in (("unshared", lambda: mono(False)),
                        ("shared", lambda: mono(True)),
                        ("disagg_kv_aware", disagg)):
        runs = []
        for i in range(repeats + 1):        # first run is compile warmup
            srv = fresh()
            res = run_trace(srv, reqs)
            if i:
                runs.append(res)
        best = min(runs, key=lambda r: r["makespan_s"])
        arms[name] = {"tok_per_s": best["tok_per_s"],
                      "ttft_mean_s": best["ttft_mean_s"],
                      "latency_p95_s": best["latency_p95_s"]}
        toks[name] = {o.rid: list(map(int, o.tokens))
                      for o in best["outputs"]}
        if name == "shared":
            shared_stats = {"hits": srv.radix.hits,
                            "partial_hits": srv.radix.partial_hits,
                            "misses": srv.radix.misses,
                            "blocks_saved": srv.metrics().blocks_saved}
            arms[name]["prefix"] = shared_stats
        elif name == "disagg_kv_aware":
            snap = srv.metrics()
            kv_routed = snap.kv_routed
            arms[name]["prefix"] = {
                "hits": snap.prefix_hits,
                "partial_hits": snap.prefix_partial_hits,
                "blocks_saved": snap.blocks_saved}
            arms[name]["kv_routed"] = kv_routed

    saved = shared_stats["blocks_saved"]
    return {
        "config": {"n_tenants": n_tenants, "turns": turns, "fanout": fanout,
                   "kv_block_size": bs, "num_kv_blocks": num_blocks,
                   "slots": slots, "requests": len(reqs),
                   "prompt_blocks_total": total_blocks,
                   "unique_content_blocks": len(unique)},
        "unshared": arms["unshared"],
        "shared": arms["shared"],
        "disagg_kv_aware": arms["disagg_kv_aware"],
        "blocks_saved": saved,
        "blocks_saved_ratio": saved / max(total_blocks, 1),
        # analytic ceilings on this trace: a flat per-group index can only
        # dedupe exact prompt copies; the tree's own bound is every block
        # re-prefilled at most never (unique content prefills once)
        "flat_index_ceiling": flat_dup / max(total_blocks, 1),
        "radix_ideal_ratio": (total_blocks - len(unique))
        / max(total_blocks, 1),
        "saved_over_flat": (saved - flat_dup) / max(total_blocks, 1),
        "ttft_speedup": (arms["unshared"]["ttft_mean_s"]
                         / max(arms["shared"]["ttft_mean_s"], 1e-9)),
        "kv_routed": kv_routed,
        "tokens_match": int(toks["unshared"] == toks["shared"]
                            == toks["disagg_kv_aware"]),
    }


# ---------------------------------------------------------------------------
# Scenario: disaggregated prefill/decode router, pool-ratio sweep
# ---------------------------------------------------------------------------
def run_disagg_scenario(model, params, rng, *, n: int, rate: float,
                        cap: int, slots: int, block_size: int,
                        kv_block_size: int):
    """The same trace through a monolithic paged engine and through the
    prefill/decode router at *equal total pools*: every split keeps
    ``prefill_slots + decode_slots == slots`` and splits the block pool in
    the same proportion, so any throughput difference is pure routing +
    KV-handle transfer cost, and the ratio sweep shows the independent
    pool-sizing knob doing its job (decode-heavy splits win this decode-
    dominated trace).  Deadlines are self-calibrated from the monolithic
    run so attainment is comparable across runners.  Tracked:
    ``tok_per_s_ratio_vs_monolithic`` (the CI floor: disaggregation must
    keep >= 0.9x monolithic throughput at equal resources) and
    ``transfer_efficiency`` (1 - transfer-time share of serving time).
    """
    reqs = make_trace(rng, n, rate, cap)
    max_len = max(PROMPT_BUCKETS) + cap
    total_blocks = slots * blocks_for(max_len, kv_block_size)
    prompt_blocks = blocks_for(max(PROMPT_BUCKETS), kv_block_size)

    def mono():
        return Engine(model, params, EngineConfig(
            num_slots=slots, max_seq_len=max_len, temperature=0.0,
            eos_id=NO_EOS, block_size=block_size, kv_layout="paged",
            kv_block_size=kv_block_size, num_kv_blocks=total_blocks))

    def router(pf_slots: int):
        # split the block pool in slot proportion, but keep each side
        # large enough to make progress: prefill holds a whole prompt
        # (plus one pinned handle), decode a whole worst-case request
        pf_blocks = max(round(total_blocks * pf_slots / slots),
                        2 * prompt_blocks)
        pf_blocks = min(pf_blocks,
                        total_blocks - blocks_for(max_len, kv_block_size))
        return DisaggRouter(model, params, DisaggConfig(
            prefill_slots=pf_slots, decode_slots=slots - pf_slots,
            max_seq_len=max_len, temperature=0.0, eos_id=NO_EOS,
            block_size=block_size, kv_layout="paged",
            kv_block_size=kv_block_size, prefill_kv_blocks=pf_blocks,
            decode_kv_blocks=total_blocks - pf_blocks))

    ratios = sorted({1, slots // 2, slots - 1})
    # calibrate deadlines off the monolithic engine (also its warmup)
    calib = run_trace(mono(), [Request(rid=r.rid, prompt=r.prompt,
                                       max_new_tokens=r.max_new_tokens,
                                       arrival_time=r.arrival_time)
                               for r in reqs])
    per_tok = slots / max(calib["tok_per_s"], 1e-9)
    for r in reqs:
        r.deadline = (r.arrival_time
                      + 6.0 * per_tok * (r.max_new_tokens + r.prompt_len))
    for pf in ratios:                      # warmup: each decode-pool shape
        warm = router(pf)
        for b in PROMPT_BUCKETS:
            warm.submit(Request(rid=-b, prompt=np.full(b, tok.PAD, np.int32),
                                max_new_tokens=1))
        warm.run()

    mono_res = run_trace(mono(), reqs)
    out = {"config": {"n": n, "slots": slots, "total_kv_blocks": total_blocks,
                      "kv_block_size": kv_block_size, "ratios": ratios},
           "monolithic": {
               "tok_per_s": mono_res["tok_per_s"],
               "ttft_mean_s": mono_res["ttft_mean_s"],
               "latency_p95_s": mono_res["latency_p95_s"],
               "deadline_attainment": mono_res.get("deadline_attainment",
                                                   1.0)},
           "splits": []}
    best = None
    for pf in ratios:
        rt = router(pf)
        res = run_trace(rt, reqs)
        split = {
            "ratio": f"{pf}:{slots - pf}",
            "prefill_slots": pf, "decode_slots": slots - pf,
            "prefill_kv_blocks": rt.prefill.slots.alloc.num_blocks,
            "decode_kv_blocks": rt.decode.slots.alloc.num_blocks,
            "tok_per_s": res["tok_per_s"],
            "ttft_mean_s": res["ttft_mean_s"],
            "latency_p95_s": res["latency_p95_s"],
            "deadline_attainment": res.get("deadline_attainment", 1.0),
            "transfers": rt.metrics().transfers,
            "transfer_time_s": rt.metrics().transfer_time_s,
            "transfer_overhead_frac": rt.metrics().transfer_overhead_frac,
            "peak_kv_blocks_decode": res["peak_kv_blocks"],
        }
        out["splits"].append(split)
        if best is None or split["tok_per_s"] > best["tok_per_s"]:
            best = split
    out["best_ratio"] = best["ratio"]
    out["tok_per_s_ratio_vs_monolithic"] = (
        best["tok_per_s"] / max(mono_res["tok_per_s"], 1e-9))
    out["transfer_efficiency"] = 1.0 - best["transfer_overhead_frac"]
    return out


# ---------------------------------------------------------------------------
# Scenario: kernel decode path (pallas backend) + int8 KV at equal bytes
# ---------------------------------------------------------------------------
def _paged_block_bytes(model, max_len: int, bs: int, kv_dtype):
    """Bytes one KV block costs in the pool (scales included for int8),
    from the abstract cache shapes — no allocation."""
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        1, max_len, block_size=bs, num_blocks=1, kv_dtype=kv_dtype))
    paged = set(model.paged_cache_names())
    scales = set(model.scale_cache_names()) if kv_dtype == "int8" else set()
    total = 0
    for name, leaf in cache.items():
        if name in paged or name in scales:
            # (L, NB+1, bs, *rest): per-block cost excludes the null block
            per_block = int(np.prod(leaf.shape)) // leaf.shape[1]
            total += per_block * jnp.dtype(leaf.dtype).itemsize
    return total


def run_kernel_scenario(model, params, rng, *, n: int, rate: float,
                        cap: int, slots: int, block_size: int,
                        kv_block_size: int):
    """Kernel-path scenarios for the live Pallas decode path.

    **fp32 vs int8 at equal KV bytes** (the tracked floor): the int8 pool
    holds as many blocks as the fp32 pool's byte budget buys once blocks
    are quantized (~1/4 the bytes incl. per-position scales), so paged
    admission — which gates on blocks — packs strictly more live requests
    into the same memory.  ``int8_admit_ratio`` = peak concurrent int8 /
    fp32 requests on the same long-tail trace; the CI floor demands
    >= 1.5x.  Both runs use the jnp backend (the admission math is
    backend-blind), and slot counts scale with the block budget so blocks
    stay the binding resource.

    **jnp vs pallas** (informational): the same short paged trace through
    both decode backends.  On CPU the pallas kernels run in interpret
    mode — a correctness path, not a speed path — so the tok/s ratio is
    recorded but not guarded; ``tokens_match`` is the hard claim (greedy
    bit-exactness under serving conditions, budget-truncated trace).
    """
    max_len = max(PROMPT_BUCKETS) + cap
    bs = kv_block_size
    f32_blocks = slots * blocks_for(max_len, bs)
    bytes_f32 = _paged_block_bytes(model, max_len, bs, None)
    bytes_i8 = _paged_block_bytes(model, max_len, bs, "int8")
    i8_blocks = (f32_blocks * bytes_f32) // bytes_i8
    byte_budget = f32_blocks * bytes_f32

    reqs = make_trace(rng, n, rate, cap)

    def fresh(kv_dtype, num_blocks, num_slots, backend="jnp"):
        return Engine(model, params, EngineConfig(
            num_slots=num_slots, max_seq_len=max_len, temperature=0.0,
            eos_id=NO_EOS, block_size=block_size, kv_layout="paged",
            kv_block_size=bs, num_kv_blocks=int(num_blocks),
            kv_dtype=kv_dtype, kernel_backend=backend))

    # slots scale with the block budget (extra paged slots are nearly
    # free — no contiguous stripe), so blocks bind admission on both sides
    f32_slots, i8_slots = 2 * slots, 4 * slots
    admit = {}
    for name, kv_dtype, nb, ns in (("fp32", None, f32_blocks, f32_slots),
                                   ("int8", "int8", i8_blocks, i8_slots)):
        runs = [run_trace(fresh(kv_dtype, nb, ns), reqs) for _ in range(2)]
        best = min(runs, key=lambda r: r["makespan_s"])
        admit[name] = {
            "num_kv_blocks": int(nb), "num_slots": ns,
            "pool_bytes": int(nb * (bytes_i8 if kv_dtype else bytes_f32)),
            "tok_per_s": best["tok_per_s"],
            "peak_active": max(r["peak_active"] for r in runs),
            "peak_kv_blocks": max(r["peak_kv_blocks"] for r in runs),
        }
    ratio = admit["int8"]["peak_active"] / max(admit["fp32"]["peak_active"],
                                               1)

    # jnp vs pallas on a short trace (interpret mode is slow on CPU)
    short = [Request(rid=r.rid, prompt=r.prompt,
                     max_new_tokens=min(r.max_new_tokens, 16),
                     arrival_time=r.arrival_time)
             for r in reqs[:max(8, n // 6)]]
    backends = {}
    toks = {}
    for backend in ("jnp", "pallas"):
        res = run_trace(fresh(None, f32_blocks, slots, backend), short)
        backends[backend] = {"tok_per_s": res["tok_per_s"],
                             "ttft_mean_s": res["ttft_mean_s"]}
        toks[backend] = {o.rid: list(map(int, o.tokens))
                         for o in res["outputs"]}
    return {
        "config": {"n": n, "slots": slots, "kv_block_size": bs,
                   "byte_budget": int(byte_budget),
                   "block_bytes_fp32": bytes_f32,
                   "block_bytes_int8": bytes_i8,
                   "pallas_trace_n": len(short)},
        "fp32": admit["fp32"],
        "int8": admit["int8"],
        "int8_blocks_per_fp32_block": bytes_f32 / bytes_i8,
        "int8_admit_ratio": ratio,
        "jnp": backends["jnp"],
        "pallas": backends["pallas"],
        "pallas_vs_jnp_tok_per_s_ratio": (
            backends["pallas"]["tok_per_s"]
            / max(backends["jnp"]["tok_per_s"], 1e-9)),
        "tokens_match": toks["jnp"] == toks["pallas"],
    }


# ---------------------------------------------------------------------------
# Scenario: elastic capacity under diurnal / bursty load
# ---------------------------------------------------------------------------
def run_elastic_scenario(model, params, rng, *, n: int, cap: int,
                         slots: int, block_size: int):
    """Closed-loop autoscaling (``serve.elastic``) vs a statically
    peak-provisioned engine on a diurnal trace, plus the two admission-
    control guarantees.

    The trace alternates **burst waves** (a wave's worth of requests
    arriving together — the diurnal peak) with **trickle valleys**
    (near-serial arrivals at roughly one request per solo service time).
    Gaps and deadlines are expressed in service-time units measured by a
    calibration run at peak capacity, so the diurnal shape — and hence
    the controller's grow/shrink behaviour — survives runner-speed
    differences.  The same deadline-stamped trace replays through a
    static engine pinned at the peak rung and through the elastic
    controller starting at the peak rung; shrinking through the valleys
    is where the capacity-seconds saving comes from.  (A full diurnal
    replay — the paper's million-request day — is this same code at
    higher ``n``; the CI trace keeps the wave structure at bench scale.)

    Tracked (CI-guarded as ``elastic.*``):

    * ``capacity_seconds_ratio`` — elastic capacity-seconds over the
      peak-provisioned static baseline (CI ceiling: <= 0.9 — elasticity
      must actually return capacity);
    * ``attainment_delta`` — elastic minus static deadline attainment on
      the identical trace (floor: >= 0 — returned capacity must not cost
      attainment);
    * ``subsat_shed_free`` — with admission control *armed*, a
      sub-saturation trace sheds exactly nothing (the predictor is
      conservative by construction);
    * ``tokens_match`` — greedy token equality: elastic output is
      bit-identical to static per request; in the overload leg, admitted
      non-degraded requests are bit-identical and degraded requests are
      an exact prefix of their unclamped static tokens;
    * ``overload_accounted`` — under genuine overload with tight
      deadlines every arrival is finished or recorded-shed (sheds are
      never silent).
    """
    max_len = max(PROMPT_BUCKETS) + cap
    ladder = tuple(sorted({max(1, slots // 4), max(1, slots // 2), slots}))

    def fresh(ns):
        return Engine(model, params, EngineConfig(
            num_slots=ns, max_seq_len=max_len, temperature=0.0,
            eos_id=NO_EOS, block_size=block_size))

    for rung in ladder:                 # compile every rung off-trace
        warm = fresh(rung)
        for b in PROMPT_BUCKETS:
            warm.submit(Request(rid=-b, prompt=np.full(b, tok.PAD, np.int32),
                                max_new_tokens=1))
        warm.run()

    prompts = []
    for _ in range(n):
        hi = 10 ** int(rng.integers(2, 7))
        text = f"{int(rng.integers(10, hi))}+{int(rng.integers(10, hi))}="
        ids = tok.encode(text, bos=True)
        bucket = next(b for b in PROMPT_BUCKETS if b >= len(ids))
        prompts.append(tok.pad_batch([ids], bucket)[0])
    budgets = np.maximum(
        1, (sample_response_fractions(rng, n) * cap).astype(int))

    calib = run_trace(fresh(slots),
                      [Request(rid=i, prompt=prompts[i],
                               max_new_tokens=int(budgets[i]))
                       for i in range(n)], realtime=False)
    per_tok = slots / max(calib["tok_per_s"], 1e-9)  # solo per-token service
    mean_budget = float(budgets.mean())

    # diurnal arrivals: two "days" of burst -> valley
    segs = ("burst", "valley", "burst", "valley")
    counts = [round(n * f) for f in (0.3, 0.2, 0.3, 0.0)]
    counts[3] = n - sum(counts[:3])
    serial_gap = 1.3 * cap * per_tok    # one request per solo service time
    arr, t = [], 0.0
    for kind, count in zip(segs, counts):
        if kind == "burst":
            arr.extend([t] * count)
            t += 1.2 * count * mean_budget * per_tok / slots
        else:
            for _ in range(count):
                arr.append(t)
                t += serial_gap

    def mk(slack):
        return [Request(rid=i, prompt=prompts[i],
                        max_new_tokens=int(budgets[i]), arrival_time=arr[i],
                        deadline=arr[i] + slack * per_tok
                        * (int(budgets[i]) + len(prompts[i])))
                for i in range(n)]

    def ctrl(**over):
        kw = dict(ladder=ladder, interval_s=0.05, cooldown_s=0.15)
        kw.update(over)
        return ElasticController(ElasticConfig(**kw))

    static_res = run_trace(fresh(slots), mk(12.0), realtime=False)
    c_main = ctrl()
    ela_res = run_trace(fresh(slots), mk(12.0), realtime=False,
                        controller=c_main)
    e = ela_res["elastic"]
    ref = {o.rid: list(map(int, o.tokens)) for o in static_res["outputs"]}
    got = {o.rid: list(map(int, o.tokens)) for o in ela_res["outputs"]}
    main_exact = got == ref
    att_static = static_res.get("deadline_attainment", 1.0)
    att_elastic = ela_res.get("deadline_attainment", 1.0)

    # sub-saturation, admission control ARMED: sheds must be exactly zero
    n_sub = min(max(n // 3, 8), 16)
    sub_reqs = [Request(rid=i, prompt=prompts[i],
                        max_new_tokens=int(budgets[i]),
                        arrival_time=i * serial_gap,
                        deadline=i * serial_gap + 12.0 * per_tok
                        * (int(budgets[i]) + len(prompts[i])))
                for i in range(n_sub)]
    sub_res = run_trace(fresh(slots), sub_reqs, realtime=False,
                        controller=ctrl(shed=True))
    subsat_shed_free = int(sub_res["elastic"]["sheds"] == 0)

    # overload: the whole trace as one dense wave with tight deadlines —
    # admission degrades (budget clamps) before it sheds, sheds are
    # recorded, and nothing silently vanishes.  Arrivals are staggered by
    # one service step so the predictor has a measured time-per-token
    # before the queue gets deep (a cold engine admits everything).
    over_gap = per_tok / 3.0
    over_reqs = [Request(rid=i, prompt=prompts[i],
                         max_new_tokens=int(budgets[i]),
                         arrival_time=i * over_gap,
                         deadline=i * over_gap + 1.25 * per_tok
                         * (int(budgets[i]) + len(prompts[i])))
                 for i in range(n)]
    c_over = ctrl(shed=True, min_degrade_tokens=4)
    over_res = run_trace(fresh(slots), over_reqs, realtime=False,
                         controller=c_over)
    oe = over_res["elastic"]
    degraded_to = {d["rid"]: d["to"] for d in oe["degrade_records"]}
    shed_rids = {s["rid"] for s in oe["shed_records"]}
    prefix_ok, exact_ok = True, True
    for o in over_res["outputs"]:
        if o.rid in degraded_to:
            want = ref[o.rid][:degraded_to[o.rid]]
            prefix_ok &= list(map(int, o.tokens)) == want
        else:
            exact_ok &= list(map(int, o.tokens)) == ref[o.rid]
    overload_accounted = int(
        len(over_res["outputs"]) + oe["sheds"] == n
        and oe["sheds"] == len(oe["shed_records"])
        and not shed_rids & {o.rid for o in over_res["outputs"]})
    tokens_match = int(main_exact and prefix_ok and exact_ok)

    return {
        "config": {"n": n, "slots": slots, "ladder": list(ladder),
                   "cap": cap, "per_token_calib_s": per_tok,
                   "segments": list(zip(segs, counts)),
                   "n_subsat": n_sub},
        "static": {"tok_per_s": static_res["tok_per_s"],
                   "latency_p95_s": static_res["latency_p95_s"],
                   "deadline_attainment": att_static},
        "elastic": {"tok_per_s": ela_res["tok_per_s"],
                    "latency_p95_s": ela_res["latency_p95_s"],
                    "deadline_attainment": att_elastic,
                    "resizes": e["resizes"],
                    "resize_log": e["resize_log"],
                    "capacity_log": e["capacity_log"]},
        "capacity_seconds": e["capacity_seconds"],
        "static_capacity_seconds": e["static_capacity_seconds"],
        "capacity_seconds_ratio": e["capacity_seconds_ratio"],
        "attainment_delta": att_elastic - att_static,
        "tokens_match": tokens_match,
        "subsat_sheds": sub_res["elastic"]["sheds"],
        "subsat_shed_free": subsat_shed_free,
        "overload": {"sheds": oe["sheds"], "degrades": oe["degrades"],
                     "shed_frac": oe["sheds"] / n,
                     "class_counts": oe["class_counts"],
                     "degraded_prefix_ok": int(prefix_ok),
                     "admitted_exact_ok": int(exact_ok)},
        "overload_accounted": overload_accounted,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--n-requests", type=int, default=64)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate (req/s); high rate = the "
                         "compute-bound heavy-traffic regime (low rates are "
                         "arrival-limited: the engine then wins on latency/"
                         "TTFT rather than throughput)")
    ap.add_argument("--max-new", type=int, default=48,
                    help="static decode budget / engine per-request cap")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV block for the paged server")
    ap.add_argument("--paged-slots-factor", type=int, default=2,
                    help="paged server offers factor * --slots decode slots "
                         "over the SAME KV memory (blocks bind admission)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="run each server this many times and keep its best "
                         "(min-makespan) run — wall-clock noise rejection on "
                         "shared/throttled CPUs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="small CI workload (fewer requests; best-of-N "
                         "repeats kept for noise rejection); writes "
                         "BENCH_serve_quick.json — the same-config baseline "
                         "the CI bench guard diffs against")
    ap.add_argument("--json", default=None,
                    help="report path ('' disables; default "
                         "BENCH_serve[_quick].json at the repo root)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.quick:
        # the CI bench guard diffs this report's speedup ratios at 15%
        # tolerance, so the quick trace stays large enough (and best-of-5)
        # to keep run-to-run ratio noise well inside that band
        args.n_requests = 48
        args.repeats = max(args.repeats, 5)
    if args.json is None:
        name = "BENCH_serve_quick.json" if args.quick else "BENCH_serve.json"
        args.json = os.path.join(os.path.dirname(__file__), "..", name)

    model = build_model(args.arch, reduced=True)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    reqs = make_trace(rng, args.n_requests, args.rate, args.max_new)
    max_len = max(PROMPT_BUCKETS) + args.max_new

    # equal KV memory: the paged pool holds exactly --slots contiguous
    # stripes' worth of block capacity; extra decode slots are nearly free
    # (no per-slot stripe), so blocks are the binding admission resource.
    # Architectures with no cache_seq leaves (rwkv6: pure recurrent state)
    # have nothing to page — extra slots there would just be extra state
    # memory, so the equal-memory comparison is skipped.
    num_kv_blocks = args.slots * blocks_for(max_len, args.kv_block_size)
    paged_slots = args.paged_slots_factor * args.slots
    has_paged_kv = bool(model.paged_cache_names())

    def fresh_engine(kv: str):
        if kv == "paged":
            return Engine(model, params, EngineConfig(
                num_slots=paged_slots, max_seq_len=max_len, temperature=0.0,
                eos_id=NO_EOS, block_size=args.block_size, kv_layout="paged",
                kv_block_size=args.kv_block_size,
                num_kv_blocks=num_kv_blocks))
        return Engine(model, params, EngineConfig(
            num_slots=args.slots, max_seq_len=max_len, temperature=0.0,
            eos_id=NO_EOS, block_size=args.block_size))

    # ---- warmup: compile both prompt buckets for engine prefill (both KV
    # layouts) AND the static generate path, plus the decode blocks
    layouts = ("contiguous", "paged") if has_paged_kv else ("contiguous",)
    for kv in layouts:
        warm = fresh_engine(kv)
        for b in PROMPT_BUCKETS:
            warm.submit(Request(rid=-b, prompt=np.full(b, tok.PAD, np.int32),
                                max_new_tokens=1))
        warm.run()
    for b in PROMPT_BUCKETS:
        fake = [Request(rid=-100 - b - j, prompt=np.full(b, tok.PAD, np.int32),
                        max_new_tokens=1, arrival_time=0.0)
                for j in range(args.slots)]
        run_static(model, params, fake, args.slots, args.max_new)

    # ---- timed runs (best-of-N per server; interleaved for fairness)
    eng_runs, pag_runs, sta_runs = [], [], []
    for _ in range(max(args.repeats, 1)):
        eng_runs.append(run_trace(fresh_engine("contiguous"), reqs))
        if has_paged_kv:
            pag_runs.append(run_trace(fresh_engine("paged"), reqs))
        sta_runs.append(run_static(model, params, reqs, args.slots,
                                   args.max_new, seed=args.seed))
    eng_res = min(eng_runs, key=lambda r: r["makespan_s"])
    sta_res = min(sta_runs, key=lambda r: r["makespan_s"])
    # capacity numbers are properties of the trace, not of timing: report
    # the max across repeats so a lucky fast run can't under-state them
    eng_res["peak_active"] = max(r["peak_active"] for r in eng_runs)
    pag_res = None
    if has_paged_kv:
        pag_res = min(pag_runs, key=lambda r: r["makespan_s"])
        pag_res["peak_active"] = max(r["peak_active"] for r in pag_runs)
        pag_res["peak_kv_blocks"] = max(r["peak_kv_blocks"]
                                        for r in pag_runs)
        pag_res["kv_block_utilization"] = (
            pag_res["peak_kv_blocks"] / max(pag_res["kv_blocks_total"], 1))

    # ---- scheduler-path scenarios (tentpole metrics) ----------------------
    pri_res = run_priority_scenario(
        model, params, np.random.default_rng(args.seed + 1),
        n=args.n_requests, rate=args.rate, cap=args.max_new,
        slots=args.slots, block_size=args.block_size)
    pfx_res = None
    if has_paged_kv:
        pfx_res = run_prefix_scenario(
            model, params, np.random.default_rng(args.seed + 2),
            n_groups=max(args.n_requests // 4, 4), group=4, rate=args.rate,
            block_size=max(args.kv_block_size // 2, 4))
    dis_res = None
    if has_paged_kv:
        dis_res = run_disagg_scenario(
            model, params, np.random.default_rng(args.seed + 3),
            n=args.n_requests, rate=args.rate, cap=args.max_new,
            slots=args.slots, block_size=args.block_size,
            kv_block_size=args.kv_block_size)
    ker_res = None
    if has_paged_kv and model.kernel_supported():
        ker_res = run_kernel_scenario(
            model, params, np.random.default_rng(args.seed + 4),
            n=args.n_requests, rate=args.rate, cap=args.max_new,
            slots=args.slots, block_size=args.block_size,
            kv_block_size=args.kv_block_size)
    chat_res = None
    if has_paged_kv:
        chat_res = run_chat_scenario(
            model, params, np.random.default_rng(args.seed + 5))
    ela_res = run_elastic_scenario(
        model, params, np.random.default_rng(args.seed + 6),
        n=args.n_requests, cap=args.max_new,
        slots=args.slots, block_size=args.block_size)

    speedup = eng_res["tok_per_s"] / max(sta_res["tok_per_s"], 1e-9)
    print(f"# {args.arch}: {args.n_requests} reqs, {args.slots} slots, "
          f"rate {args.rate}/s, cap {args.max_new}, block {args.block_size}, "
          f"kv-block {args.kv_block_size} ({num_kv_blocks} blocks = equal "
          f"memory, paged offers {paged_slots} slots)")
    servers = [("engine", eng_res), ("static", sta_res)]
    if pag_res is not None:
        servers.insert(1, ("paged ", pag_res))
    for name, r in servers:
        print(f"{name}: {r['tokens']} tokens in {r['makespan_s']:.2f}s = "
              f"{r['tok_per_s']:.1f} tok/s | latency mean "
              f"{r['latency_mean_s']:.2f}s p95 {r['latency_p95_s']:.2f}s | "
              f"ttft {r['ttft_mean_s']:.2f}s")
    print(f"engine slot utilization: {eng_res['slot_utilization']:.1%}")
    if pag_res is not None:
        print(f"concurrency at equal KV memory: contiguous peaks at "
              f"{eng_res['peak_active']} live requests (slot-capped at "
              f"{args.slots}), paged at {pag_res['peak_active']} "
              f"(block util {pag_res['kv_block_utilization']:.0%})")
    else:
        print(f"{args.arch} has no cache_seq leaves — nothing to page, "
              f"equal-memory paged comparison skipped")
    print(f"throughput speedup (engine/static): {speedup:.2f}x")

    f_i = pri_res["fifo"]["interactive"]
    d_i = pri_res["deadline"]["interactive"]
    print(f"mixed-priority: interactive p95 fifo {f_i['latency_p95_s']:.2f}s"
          f" -> deadline {d_i['latency_p95_s']:.2f}s | attainment "
          f"{f_i['deadline_attainment']:.0%} -> "
          f"{d_i['deadline_attainment']:.0%} (batch "
          f"{pri_res['deadline']['batch']['deadline_attainment']:.0%})")
    if pfx_res is not None:
        print(f"prefix sharing at equal KV memory: peak live "
              f"{pfx_res['unshared']['peak_active']} -> "
              f"{pfx_res['shared']['peak_active']} requests, "
              f"{pfx_res['blocks_saved']} blocks saved "
              f"({pfx_res['blocks_saved_ratio']:.0%} of prompt-block "
              f"traffic), {pfx_res['shared']['prefix']['hits']} prefills "
              f"skipped")
    if dis_res is not None:
        print(f"disagg at equal total pools: best split "
              f"{dis_res['best_ratio']} = "
              f"{dis_res['tok_per_s_ratio_vs_monolithic']:.2f}x monolithic "
              f"tok/s, transfer efficiency "
              f"{dis_res['transfer_efficiency']:.0%} | per-ratio tok/s: "
              + ", ".join(f"{s['ratio']}={s['tok_per_s']:.0f}"
                          for s in dis_res["splits"]))
    if ker_res is not None:
        match = ("tokens identical" if ker_res["tokens_match"]
                 else "TOKEN MISMATCH")
        print(f"kernel path: int8 KV admits {ker_res['int8']['peak_active']} "
              f"vs fp32 {ker_res['fp32']['peak_active']} live requests at "
              f"equal KV bytes ({ker_res['int8_admit_ratio']:.2f}x admit, "
              f"{ker_res['int8_blocks_per_fp32_block']:.1f} blocks per fp32 "
              f"block) | pallas decode "
              f"{ker_res['pallas_vs_jnp_tok_per_s_ratio']:.2f}x jnp tok/s "
              f"({match}; interpret mode off-TPU)")
    if chat_res is not None:
        match = ("tokens identical" if chat_res["tokens_match"]
                 else "TOKEN MISMATCH")
        print(f"chat trace (radix): {chat_res['blocks_saved_ratio']:.0%} of "
              f"prompt blocks shared (flat-index ceiling "
              f"{chat_res['flat_index_ceiling']:.0%}, tree ideal "
              f"{chat_res['radix_ideal_ratio']:.0%}) | ttft "
              f"{chat_res['ttft_speedup']:.2f}x unshared | "
              f"{chat_res['kv_routed']} requests KV-routed across 2 prefill "
              f"engines ({match})")
    match = ("tokens identical" if ela_res["tokens_match"]
             else "TOKEN MISMATCH")
    print(f"elastic (diurnal trace): {ela_res['capacity_seconds_ratio']:.0%} "
          f"capacity-seconds vs peak-provisioned static at attainment delta "
          f"{ela_res['attainment_delta']:+.0%} "
          f"({ela_res['elastic']['resizes']} resizes over ladder "
          f"{ela_res['config']['ladder']}) | sub-saturation sheds "
          f"{ela_res['subsat_sheds']} | overload: "
          f"{ela_res['overload']['degrades']} degraded, "
          f"{ela_res['overload']['sheds']} shed "
          f"({ela_res['overload']['shed_frac']:.0%}), accounted="
          f"{ela_res['overload_accounted']} ({match})")

    if args.json:
        report = {
            "arch": args.arch,
            "config": {
                "n_requests": args.n_requests, "slots": args.slots,
                "rate": args.rate, "max_new": args.max_new,
                "block_size": args.block_size,
                "kv_block_size": args.kv_block_size,
                "num_kv_blocks": num_kv_blocks, "paged_slots": paged_slots,
                "repeats": args.repeats, "seed": args.seed,
                "quick": args.quick,
            },
            "engine": _strip_outputs(eng_res),
            "static": _strip_outputs(sta_res),
            "speedup_engine_vs_static": speedup,
        }
        if pag_res is not None:
            report["paged"] = _strip_outputs(pag_res)
            report["speedup_paged_vs_static"] = (
                pag_res["tok_per_s"] / max(sta_res["tok_per_s"], 1e-9))
            report["paged_extra_concurrency_at_equal_memory"] = (
                pag_res["peak_active"] - eng_res["peak_active"])
        report["priority"] = pri_res
        if pfx_res is not None:
            report["prefix"] = pfx_res
        if dis_res is not None:
            report["disagg"] = dis_res
        if ker_res is not None:
            report["kernel"] = ker_res
        if chat_res is not None:
            report["radix"] = chat_res
        report["elastic"] = ela_res
        path = os.path.abspath(args.json)
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {path}")
    return speedup


if __name__ == "__main__":
    main()
