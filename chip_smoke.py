"""Chip smoke test: the system's main path on one TPU at the published
widths of internlm2-1.8b (24 layers, d_model 2048, 16 query and 8 KV heads,
head_dim 128, d_ff 8192, vocab 92,544, bf16), with random weights from a
seed.

    python chip_smoke.py            # from the checkout root, on a TPU host

Two phases run in order, in this one process (no child touches the chip):

* serve: ``RolloutSpec`` -> ``Engine`` with paged KV and the pallas
  backend, greedy, over ``N_REQUESTS`` prompts of ``PROMPT_LEN`` tokens;
  once with KV in the model dtype and once with int8 KV.  Every request
  must produce ``MAX_NEW`` tokens, the kernels must run compiled (resolved
  interpret flag False, ``tpu_custom_call`` in the decode block's compiled
  HLO), and each request's first token and its logprob must agree with the
  jnp backend on the same requests.
* rl: ``repro.launch.train.run_training`` with engine rollouts (paged,
  pallas) under ``mux="stream"`` for ``RL_STEPS`` GRPO steps, at published
  widths cut to ``RL_LAYERS`` layers.  Losses must be finite and the
  params must move.

Per-phase wall and compile seconds go to earlier lines; the last line is
one JSON object naming the device.  Any failed check raises, and the
script exits non-zero when JAX finds no TPU.  The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_config  # noqa: E402
from repro.kernels.ops import resolve_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import run_training  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import Request, RolloutSpec  # noqa: E402

ARCH = "internlm2-1.8b"
SEED = 0
N_REQUESTS = 8
PROMPT_LEN = 256        # one prompt length: one prefill compilation
MAX_NEW = 32
# Random weights may emit EOS at any step; the engine ignores it here so
# that every request's length is known in advance.
NO_EOS = -1
# First-token logprob tolerance: bf16 keeps 8 significant bits, and at
# |logprob| ~ ln(92,544) ~ 11.4 one bf16 ulp is 2^-8 * 11.4 ~ 0.045.
LOGPROB_ATOL = 0.05
# Depth cut for the GRPO phase.  Compiled for a described v5e, the train
# step's memory_analysis (arguments + outputs + temps: bf16 params, fp32
# Adam moments, grads) is 12.61 GB at 4 layers, 13.87 GB at 5, 15.14 GB at
# 6 and 16.39 GB at 7.  The stream mux can keep the previous weights alive
# for the rollout engine while the next step trains (+2 bytes per param).
# 5 layers is the deepest cut for which both, plus 1 GiB for the KV pool
# and runtime, stay within the chip's 16 GiB.
RL_LAYERS = 5
RL_STEPS = 3
RL_BATCH, RL_GROUP, RL_MAX_NEW = 4, 4, 16


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (cache reads
    included), summed over every thread of the process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration


def parity_reward(completions, mask, answers) -> np.ndarray:
    """Share of each row's generated tokens whose id is even.

    Random weights practically never emit the digits the arithmetic
    verifier checks, so its rewards, GRPO's advantages and the update
    would all be zero.  This row-wise reward varies across a group's
    samples and keeps the step's gradient nonzero."""
    comp, m = np.asarray(completions), np.asarray(mask) > 0
    even = (comp % 2 == 0) & m
    return (even.sum(1) / np.maximum(m.sum(1), 1)).astype(np.float32)


def _serve(model, params, prompts, max_new, *, kernel_backend,
           kv_dtype=None):
    spec = RolloutSpec(kv_layout="paged", kernel_backend=kernel_backend,
                       kv_dtype=kv_dtype)
    eng = spec.build_engine(model, params, batch=len(prompts),
                            max_seq_len=prompts.shape[1] + max_new,
                            eos_id=NO_EOS, temperature=0.0,
                            rng=jax.random.PRNGKey(SEED))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new))
    outs = eng.run()
    check(len(outs) == len(prompts)
          and all(len(o.tokens) == max_new for o in outs),
          f"{kernel_backend}/{kv_dtype}: expected {len(prompts)} requests "
          f"of {max_new} tokens, got {[len(o.tokens) for o in outs]}")
    return eng, outs


def serve_phase(model, params, *, n_requests: int = N_REQUESTS,
                prompt_len: int = PROMPT_LEN, max_new: int = MAX_NEW) -> dict:
    """Greedy paged serving on the pallas backend, KV in the model dtype
    and in int8, each compared with the jnp backend on the same requests.
    Returns one report per KV dtype."""
    prompts = np.random.default_rng(SEED).integers(
        0, model.cfg.vocab_size, (n_requests, prompt_len), dtype=np.int32)
    _, ref = _serve(model, params, prompts, max_new, kernel_backend="jnp")
    reports = {}
    for kv_dtype in (None, "int8"):
        eng, outs = _serve(model, params, prompts, max_new,
                           kernel_backend="pallas", kv_dtype=kv_dtype)
        check(eng.kernel_backend == "pallas",
              f"resolved backend is {eng.kernel_backend!r}, not 'pallas'")
        # the first token comes from the prefill logits, which the KV
        # storage dtype does not touch, so one jnp run serves both
        lp_diff = max(abs(o.logprobs[0] - r.logprobs[0])
                      for o, r in zip(outs, ref))
        check(all(o.tokens[0] == r.tokens[0] for o, r in zip(outs, ref)),
              f"first tokens differ from jnp: "
              f"{[o.tokens[0] for o in outs]} vs {[r.tokens[0] for r in ref]}")
        check(lp_diff <= LOGPROB_ATOL,
              f"first-token logprob differs from jnp by {lp_diff}")
        # information only: greedy decode may flip at a near-tie, after
        # which the sequences part; before it they should agree closely
        prefix = [next((j for j, (a, b) in enumerate(zip(o.tokens, r.tokens))
                        if a != b), max_new) for o, r in zip(outs, ref)]
        reports[kv_dtype or model.cfg.dtype] = {
            "interpret": eng.interpret,
            "tpu_custom_call": "tpu_custom_call" in eng.decode_hlo(),
            "max_first_logprob_diff": lp_diff,
            "identical_sequences": prefix.count(max_new) / len(ref),
            "mean_common_prefix": sum(prefix) / len(ref),
            "max_logprob_diff_common_prefix": max(
                abs(a - b) for o, r, n in zip(outs, ref, prefix)
                for a, b in zip(o.logprobs[:n], r.logprobs[:n])),
        }
        del eng, outs
    return reports


def rl_phase(model, *, steps: int = RL_STEPS, batch: int = RL_BATCH,
             group: int = RL_GROUP, max_new: int = RL_MAX_NEW) -> dict:
    """GRPO through the stream mux with paged pallas engine rollouts."""
    state, hist, report = run_training(
        model=model, steps=steps, batch=batch, group=group, max_new=max_new,
        seed=SEED, rollout="engine", kv="paged", kernel_backend="pallas",
        mux="stream", reward=parity_reward, log_every=0, return_report=True)
    losses = [h["loss"] for h in hist]
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"losses {losses}")
    # the job's initial params are model.init(PRNGKey(seed)), drawn eagerly
    init = jax.tree.leaves(model.init(jax.random.PRNGKey(SEED)))
    final = jax.tree.leaves(state["params"])
    moved = sum(int(jnp.sum(a != b)) for a, b in zip(init, final))
    total = sum(x.size for x in final)
    check(moved > 0, "no parameter changed after the GRPO steps")
    return {"losses": losses, "moved_share": moved / total,
            "mux": report.summary()}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    count = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    check(not resolve_interpret(), "pallas kernels would run interpreted")
    cfg = get_config(ARCH)
    print(f"rl depth cut: {RL_LAYERS} of {cfg.num_layers} layers at "
          f"published widths (deepest whose GRPO train step fits one chip)",
          flush=True)
    clock = CompileClock()

    def phase(name, fn):
        t0, c0 = time.perf_counter(), clock.seconds
        out = fn()
        print(f"phase {name}: wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={clock.seconds - c0:.3f}", flush=True)
        print(f"phase {name}: {json.dumps(out)}", flush=True)

    def serve():
        model = build_model(ARCH)
        params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
        out = serve_phase(model, params)
        for kv, r in out.items():
            check(r["interpret"] is False, f"{kv}: kernels interpreted")
            check(r["tpu_custom_call"], f"{kv}: no tpu_custom_call in the "
                  f"decode block's compiled HLO")
        return out

    phase("serve", serve)
    gc.collect()
    phase("rl", lambda: rl_phase(build_model(
        dataclasses.replace(cfg, num_layers=RL_LAYERS))))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
