"""Serving drivers: continuous-batching engine (default) + static batch.

The continuous path feeds prompts through ``repro.serve.Engine`` —
policy-driven admission (``--sched fifo|deadline|slo``) into a fixed pool
of KV-cache slots, slot recycle on EOS, decode batched across all live
slots, optional radix prompt-prefix KV sharing (``--prefix-share``, paged
layout).  The static path is the legacy one-batch-end-to-end ``generate``
call, kept as the benchmark baseline.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b \
        --batch 8 --slots 4 --max-new 32              # continuous (default)
    PYTHONPATH=src python -m repro.launch.serve --engine static ...
    PYTHONPATH=src python -m repro.launch.serve --kv paged --prefix-share \
        --group 4                                     # GRPO-shaped sharing
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.data import tokenizer as tok
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.rl import (SamplerConfig, completions_to_text, generate,
                      generate_continuous)
from repro.serve import RolloutSpec


def _encode_prompts(model, prompts_text):
    plen = max(len(tok.encode(t, bos=True)) for t in prompts_text)
    prompts = jnp.asarray(tok.pad_batch(
        [tok.encode(t, bos=True) for t in prompts_text], plen))
    fr = None
    if model.cfg.frontend == "vision":
        fr = jnp.zeros((prompts.shape[0], model.cfg.num_frontend_tokens,
                        model.cfg.d_model))
    elif model.cfg.frontend == "audio":
        fr = jnp.zeros((prompts.shape[0], model.cfg.max_source_len,
                        model.cfg.d_model))
    return prompts, fr


def serve_batch(arch: str, prompts_text: list[str], *, reduced: bool = True,
                max_new: int = 32, temperature: float = 0.8, seed: int = 0,
                model=None, params=None):
    """Static batch: one prefill + fixed-length decode scan for the whole
    batch (every request pays ``max_new`` steps regardless of EOS)."""
    if model is None:
        model = build_model(arch, reduced=reduced)
    key = jax.random.PRNGKey(seed)
    if params is None:
        params = model.init(key)
    prompts, fr = _encode_prompts(model, prompts_text)
    sampler = SamplerConfig(max_new_tokens=max_new, temperature=temperature)
    t0 = time.perf_counter()
    out = generate(model, params, prompts, key, sampler, frontend=fr)
    jax.block_until_ready(out["completions"])
    dt = time.perf_counter() - t0
    n_tok = int(out["mask"].sum())
    return {"texts": completions_to_text(out["completions"], out["mask"]),
            "wall_s": dt, "tokens": n_tok,
            "tok_per_s": n_tok / max(dt, 1e-9)}


def serve_continuous(arch: str, prompts_text: list[str], *,
                     reduced: bool = True, max_new: int = 32,
                     temperature: float = 0.8, seed: int = 0,
                     spec: RolloutSpec | None = None,
                     num_slots: int | None = None, block_size: int = 1,
                     kv: str = "contiguous", kv_block_size: int = 16,
                     num_kv_blocks: int | None = None,
                     sched: str = "fifo", policy=None,
                     prefix_share: bool = False, group: int | None = None,
                     disagg=None, kernel_backend: str = "jnp",
                     kv_dtype: str | None = None, model=None, params=None):
    """Continuous batching: requests stream through the slot-pool engine
    (``kv="paged"`` serves from the shared block-pool KV layout;
    ``sched`` picks the admission policy and ``prefix_share`` enables
    radix prompt-prefix sharing — with ``group``, every ``group``
    consecutive prompts are treated as one shared-prefix group).
    ``disagg`` routes through split prefill/decode pools instead of one
    engine — ``True`` or a dict of ``DisaggConfig`` overrides (see
    ``rl.generate_continuous``); output is identical under greedy.
    ``spec`` supplies the whole engine shape as one
    :class:`~repro.serve.RolloutSpec` instead of the loose kwargs."""
    if spec is None:
        spec = RolloutSpec(num_slots=num_slots, block_size=block_size,
                           kv_layout=kv, kv_block_size=kv_block_size,
                           num_kv_blocks=num_kv_blocks, sched=sched,
                           prefix_share=prefix_share, disagg=disagg,
                           kernel_backend=kernel_backend, kv_dtype=kv_dtype,
                           group=group)
    elif group is not None:
        spec = spec.replace(group=group)
    if model is None:
        model = build_model(arch, reduced=reduced)
    key = jax.random.PRNGKey(seed)
    if params is None:
        params = model.init(key)
    prompts, fr = _encode_prompts(model, prompts_text)
    sampler = SamplerConfig(max_new_tokens=max_new, temperature=temperature)
    t0 = time.perf_counter()
    out = generate_continuous(model, params, prompts, key, sampler,
                              frontend=fr, spec=spec, policy=policy)
    dt = time.perf_counter() - t0
    n_tok = int(out["mask"].sum())
    stats = out["engine_stats"]
    report = {"texts": completions_to_text(out["completions"], out["mask"]),
              "wall_s": dt, "tokens": n_tok,
              "tok_per_s": n_tok / max(dt, 1e-9),
              "slot_utilization": stats.slot_utilization,
              "prefills": stats.prefills, "decode_steps": stats.steps,
              "peak_active": stats.peak_active,
              "peak_kv_blocks": stats.peak_kv_blocks,
              "prefix_hits": stats.prefix_hits,
              "blocks_saved": stats.blocks_saved}
    if spec.disagg:
        report["transfers"] = stats.transfers
        report["transfer_time_s"] = stats.transfer_time_s
        report["transferred_blocks"] = stats.transferred_blocks
        report["transfer_overhead_frac"] = stats.transfer_overhead_frac
    return report


def serve_elastic(arch: str, prompts_text: list[str], *,
                  reduced: bool = True, max_new: int = 32, seed: int = 0,
                  spec: RolloutSpec | None = None,
                  ladder: tuple = (2, 4, 8), shed: bool = False,
                  deadline_s: float = 2.0, arrival_gap_s: float = 0.05,
                  warmup: bool = True, model=None, params=None):
    """Closed-loop elastic serving: replay a staggered arrival trace
    through ``serve.run_trace`` with an ``ElasticController`` in the loop.

    The engine starts on the smallest rung of ``ladder`` and the
    controller grows/shrinks it between steps by suspend/resume (live KV
    carried, greedy tokens identical to a static run).  ``shed=True``
    stamps every request with an ``arrival + deadline_s`` deadline and
    arms the admission gate: requests that cannot meet their deadline are
    degraded (decode budget clamped) before being shed, and every shed is
    recorded in the report — never silently dropped.  Returns the
    ``run_trace`` report; its ``"elastic"`` section carries
    capacity-seconds, sheds/degrades and the resize history.

    ``warmup`` (default on) pre-compiles every ladder rung's decode shape
    on a throwaway engine before the trace starts — otherwise the first
    step's jit compile lands in ``decode_time_s``, the admission
    predictor reads a wildly inflated time-per-token, and an unloaded
    system sheds like a saturated one."""
    import numpy as np

    from repro.serve import ElasticConfig, ElasticController, Request
    from repro.serve.engine import run_trace

    if model is None:
        model = build_model(arch, reduced=reduced)
    key = jax.random.PRNGKey(seed)
    if params is None:
        params = model.init(key)
    ladder = tuple(sorted({int(x) for x in ladder}))
    if spec is None:
        spec = RolloutSpec()
    spec = spec.replace(num_slots=ladder[0])
    toks = [np.asarray(tok.encode(t, bos=True), np.int32)
            for t in prompts_text]
    plen = max(len(t) for t in toks)
    if warmup:
        for rung in ladder:
            warm = spec.replace(num_slots=rung).build_engine(
                model, params, batch=len(toks),
                max_seq_len=plen + max_new, eos_id=tok.EOS,
                temperature=0.0, rng=key)
            warm.submit(Request(rid=0, prompt=toks[0], max_new_tokens=2))
            while not warm.idle:
                warm.step()
    engine = spec.build_engine(model, params, batch=len(toks),
                               max_seq_len=plen + max_new, eos_id=tok.EOS,
                               temperature=0.0, rng=key)
    reqs = []
    for i, t in enumerate(toks):
        fr = None
        if model.cfg.frontend == "vision":
            fr = jnp.zeros((1, model.cfg.num_frontend_tokens,
                            model.cfg.d_model))
        elif model.cfg.frontend == "audio":
            fr = jnp.zeros((1, model.cfg.max_source_len, model.cfg.d_model))
        arrival = i * arrival_gap_s
        reqs.append(Request(rid=i, prompt=t, max_new_tokens=max_new,
                            arrival_time=arrival, frontend=fr,
                            deadline=arrival + deadline_s if shed else None))
    controller = ElasticController(ElasticConfig(
        ladder=ladder, shed=shed, interval_s=0.05, cooldown_s=0.15))
    report = run_trace(engine, reqs, realtime=False, controller=controller)
    report["texts"] = [
        tok.decode([int(x) for x in o.tokens if int(x) != tok.EOS])
        for o in report["outputs"]]
    return report


def _main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the 2-layer float32 smoke variant; "
                         "--no-reduced serves the published widths in "
                         "the config's dtype")
    ap.add_argument("--engine", choices=("continuous", "static", "elastic"),
                    default="continuous",
                    help="continuous = fixed-capacity slot-pool engine; "
                         "static = legacy one-batch generate; elastic = "
                         "continuous engine under the closed-loop capacity "
                         "controller (serve.elastic) replaying a staggered "
                         "arrival trace")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--slots", type=int, default=None,
                    help="KV-cache slots (continuous only; default = batch)")
    ap.add_argument("--block-size", type=int, default=1,
                    help="decode steps fused per scheduler tick")
    ap.add_argument("--kv", choices=("contiguous", "paged"),
                    default="contiguous",
                    help="KV-cache layout (continuous engine only)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV block (--kv paged)")
    ap.add_argument("--num-kv-blocks", type=int, default=None,
                    help="paged pool size in blocks (default: same memory "
                         "as the contiguous slot pool)")
    ap.add_argument("--sched", choices=("fifo", "deadline", "slo"),
                    default="fifo",
                    help="admission policy: fifo = strict arrival order; "
                         "deadline = EDF with bounded head skipping; slo = "
                         "deadlines derived from a slowdown bound (the "
                         "inter-group SLO contract)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="content-addressed radix-tree KV sharing (--kv "
                         "paged): requests agreeing on a block-aligned "
                         "token prefix share those blocks, exact repeats "
                         "skip prefill entirely (no tag needed)")
    ap.add_argument("--group", type=int, default=None,
                    help="shared-prefix group size for --prefix-share "
                         "(each prompt is duplicated group times, the "
                         "GRPO rollout shape)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: route prompts through a "
                         "dedicated prefill engine, hand the finished KV "
                         "over to the decode engine by block-granular "
                         "transfer handle (output identical under greedy)")
    ap.add_argument("--prefill-slots", type=int, default=None,
                    help="prefill-side slot pool (--disagg; default: "
                         "slots/4, min 1)")
    ap.add_argument("--decode-slots", type=int, default=None,
                    help="decode-side slot pool (--disagg; default: "
                         "slots - prefill slots)")
    ap.add_argument("--prefill-kv-blocks", type=int, default=None,
                    help="prefill-side paged pool size (--disagg --kv "
                         "paged; default: sized to its slot pool)")
    ap.add_argument("--decode-kv-blocks", type=int, default=None,
                    help="decode-side paged pool size (--disagg --kv "
                         "paged; default: --num-kv-blocks)")
    ap.add_argument("--prefill-engines", type=int, default=None,
                    help="parallel prefill engines (--disagg; each gets "
                         "its own full-size pools and radix tree)")
    ap.add_argument("--kv-routing", choices=("kv_aware", "queue"),
                    default=None,
                    help="request steering across --prefill-engines: "
                         "kv_aware sends each request to the engine "
                         "holding its longest registered prefix; queue "
                         "balances on load alone")
    ap.add_argument("--kernel-backend", choices=("jnp", "pallas"),
                    default="jnp",
                    help="decode-step backend (continuous engine only): "
                         "jnp = vmapped model step; pallas = batched "
                         "decode-attention kernels + fused greedy sampling "
                         "(token-identical; recurrent archs fall back)")
    ap.add_argument("--kv-dtype", choices=("auto", "int8"), default=None,
                    help="paged KV storage dtype (--kv paged): int8 "
                         "quantizes blocks with per-position scales, "
                         "~halving KV memory per request")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--ladder", default="2,4,8",
                    help="--engine elastic: comma-separated slot-count "
                         "rungs the controller may resize between (each "
                         "rung compiles its own decode shape once)")
    ap.add_argument("--shed", action="store_true",
                    help="--engine elastic: stamp deadlines on every "
                         "request and arm overload admission control — "
                         "degrade (clamp decode budget) before shedding, "
                         "report every shed")
    ap.add_argument("--deadline-s", type=float, default=2.0,
                    help="--engine elastic --shed: per-request deadline, "
                         "seconds after arrival")
    args = ap.parse_args()
    enable_compile_cache()
    spec = RolloutSpec.from_args(args)
    prompts = [f"{i}+{i+1}=" for i in range(args.batch)]
    if args.group:
        prompts = [p for p in prompts for _ in range(args.group)]
    if args.engine == "elastic":
        ladder = tuple(int(x) for x in args.ladder.split(","))
        res = serve_elastic(args.arch, prompts, reduced=args.reduced,
                            max_new=args.max_new, spec=spec, ladder=ladder,
                            shed=args.shed, deadline_s=args.deadline_s)
        e = res["elastic"]
        print(f"[elastic] served {len(res['texts'])}/{len(prompts)} "
              f"requests, {res['tokens']} tokens in {res['makespan_s']:.2f}s "
              f"({res['tok_per_s']:.1f} tok/s)")
        print(f"  resizes {len(e['resize_log'])} "
              + "".join(f"{a}->{b} " for _, a, b in e["resize_log"])
              + f"| capacity {e['capacity_seconds']:.2f} slot-s "
              f"(static {e['static_capacity_seconds']:.2f}, "
              f"ratio {e['capacity_seconds_ratio']:.2f})")
        print(f"  sheds {e['sheds']}, degrades {e['degrades']}, "
              f"classes {e['class_counts']}")
        for o, t in zip(res["outputs"], res["texts"]):
            print(f"  rid={o.rid} [{o.finish_reason}] -> {t!r}")
        return
    if args.engine == "continuous":
        res = serve_continuous(args.arch, prompts, reduced=args.reduced,
                               max_new=args.max_new, spec=spec)
        extra = (f", slot util {res['slot_utilization']:.0%}, "
                 f"{res['decode_steps']} decode steps")
        if args.prefix_share:
            extra += (f", {res['prefix_hits']} prefix hits "
                      f"({res['blocks_saved']} blocks saved)")
        if args.disagg:
            extra += (f", {res['transfers']} KV transfers "
                      f"({res['transfer_overhead_frac']:.1%} overhead)")
    else:
        res = serve_batch(args.arch, prompts, reduced=args.reduced,
                          max_new=args.max_new)
        extra = ""
    print(f"[{args.engine}] served {len(prompts)} requests, {res['tokens']} "
          f"tokens in {res['wall_s']:.2f}s ({res['tok_per_s']:.1f} tok/s"
          f"{extra})")
    for p, t in zip(prompts, res["texts"]):
        print(f"  {p!r} -> {t!r}")


if __name__ == "__main__":
    _main()
