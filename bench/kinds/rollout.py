"""Closed-loop batched rollout through the serving engine.

Batches of prompt groups go to one ``Engine`` (built by ``RolloutSpec``)
back to back: the next batch is submitted when the last sequence of the
previous one has finished, as a synchronous on-policy rollout does.  The
window opens at the first batch's submission and closes at the end of
the first batch that ends ``--seconds`` or more after it, so that it
holds whole batches, straggler tails included, whatever the speed.
Every scheduler tick is timed on the host; the engine's counters give
the tokens each tick delivered.

Tokens are sampled at the traffic's temperature; each served token's
reported log-probability is checked against the float32 reference of the
cell's model family (``bench/check.py``).
"""
from __future__ import annotations

import time

import numpy as np

from bench import check, traffic as gen, weights


def _check_tree(params, model) -> None:
    import jax
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        model.init_abstract())
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter tree:\n{got}\nvs\n{want}")


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.conf, self.t = ctx.conf, ctx.traffic
        self.family = ctx.cell.family
        self.seed = ctx.seed

    # ---- set-up -----------------------------------------------------------
    def setup(self) -> None:
        from bench import model as bm
        from repro.serve import Request, RolloutSpec

        t = self.t
        self.model = bm.build(self.family, self.conf)
        params = weights.make(self.family, self.conf, self.seed,
                              device=self.ctx.devices[0])
        _check_tree(params, self.model)
        spec = RolloutSpec(num_slots=t["slots"], block_size=1,
                           kv_layout="paged", kv_block_size=t["kv_block_size"],
                           num_kv_blocks=t["num_kv_blocks"],
                           prefix_share=t["prefix_share"],
                           kernel_backend=t["kernel_backend"])
        self.engine = spec.build_engine(
            self.model, params, batch=t["slots"],
            max_seq_len=t["max_seq_len"], eos_id=t["eos_id"],
            temperature=t["temperature"], rng=weights.key_for(self.seed, 1))
        if self.ctx.devices[0].platform == "tpu" and self.engine.interpret:
            raise RuntimeError("pallas kernels would run interpreted")
        # warm-up: every prompt length the traffic sends, once as a prefill
        # donor and once as a prefix-sharing hit, over the same slot pool
        rng = gen.rng_for(self.seed, 1 << 20)
        rid = 0
        for length in sorted(set(t["prompt_lengths"])):
            p = rng.integers(0, self.conf["vocab_size"], length,
                             dtype=np.int32)
            for _ in range(2):
                self.engine.submit(Request(rid=rid, prompt=p,
                                           max_new_tokens=2))
                rid += 1
        self.engine.run()
        self.engine.harvest()
        self.engine.reset(rng=weights.key_for(self.seed, 2))

    # ---- the window -------------------------------------------------------
    def window(self, seconds: float) -> dict:
        from repro.serve import Request

        ctx, eng, t = self.ctx, self.engine, self.t
        vocab = self.conf["vocab_size"]
        span = ctx.span
        ticks: list[dict] = []
        outputs, plans = [], {}
        attempted, prefills = 0, []
        m_prev = eng.metrics()
        t_open = ctx.open_window()
        close, prev_end = t_open + seconds, t_open
        done, b = False, 0
        while not done:
            plan = gen.rollout_batch(t, self.seed, b, vocab)
            with span("submit"):
                for j, (g, prompt, budget) in enumerate(plan):
                    rid = b * len(plan) + j
                    plans[rid] = (b, g, len(prompt), budget)
                    eng.submit(Request(rid=rid, prompt=prompt,
                                       max_new_tokens=budget))
            attempted += len(plan)
            rows = [(len(p), n) for _, p, n in plan]
            prefills += [len(plan[j][1]) for j in range(0, len(plan),
                                                          t["group"])]
            k = 0                                   # tick within the batch
            while not eng.idle:
                traced = ctx.tracing()
                t_step = time.perf_counter()
                with span("step"):
                    eng.step()
                t_step = time.perf_counter() - t_step
                with span("harvest"):
                    outputs += eng.harvest()
                now = time.perf_counter()
                m = eng.metrics()
                # kv length of each live sequence in this tick's decode
                lengths = [P + k + 1 for P, n in rows if k < n]
                ticks.append({
                    "end": now - t_open, "dt": now - prev_end,
                    "step_s": t_step,
                    "tokens": m.generated_tokens - m_prev.generated_tokens,
                    "firsts": m.prefills - m_prev.prefills,
                    "slot_steps": m.slot_steps - m_prev.slot_steps,
                    "live": len(lengths), "keys": sum(lengths),
                    "traced": traced,
                    "lengths": lengths if traced else None})
                m_prev, prev_end, k = m, now, k + 1
                ctx.tick(now)
            done = prev_end >= close
            b += 1
        self.outputs, self.plans = outputs, plans
        # every request of the window has finished: it ends with a batch
        failed = attempted - len(outputs) + sum(
            len(o.tokens) != plans[o.rid][3] for o in outputs)
        return {"ticks": ticks, "window_s": prev_end - t_open,
                "prefill_lengths": prefills,   # one prefill per prompt group
                "attempted": attempted, "failed": int(failed),
                "finished": len(outputs)}

    def end_to_end(self, rec: dict) -> dict:
        ticks = rec["ticks"]
        tokens = sum(x["tokens"] for x in ticks)
        gaps = np.repeat([x["dt"] for x in ticks],
                         [x["tokens"] - x["firsts"] for x in ticks])
        return {"rollout_tok_s": tokens / rec["window_s"],
                "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3}

    def release(self) -> None:
        self.engine = None
        self.model = None

    # ---- the output check -------------------------------------------------
    def sample(self) -> list:
        """The finished request with the most tokens and, drawn from the
        seed, requests of other prompts up to ``check_requests``."""
        outs = sorted(self.outputs, key=lambda o: (-len(o.tokens), o.rid))
        if not outs:
            return []
        picked, seen = [outs[0]], {self.plans[outs[0].rid][:2]}
        rng = gen.rng_for(self.seed, 1 << 21)
        for i in rng.permutation(len(outs)):
            o = outs[i]
            key = self.plans[o.rid][:2]
            if len(picked) < self.t["check_requests"] and key not in seen:
                picked.append(o)
                seen.add(key)
        return picked

    def check(self, limits: dict) -> list:
        ref = self.family.Reference(self.conf)
        w = weights.make(self.family, self.conf, self.seed,
                         device=self.ctx.devices[0])
        L = self.t["max_seq_len"]
        got = [check.served(ref, w, o.prompt, o.tokens, o.logprobs, L)
               for o in self.sample()]
        wrong = sum(len(o.tokens) != self.plans[o.rid][3]
                    for o in self.outputs)
        # no finished request to compare reads as a failed comparison
        worst = float(max(g.max() for g in got)) if got else float("inf")
        return [("wrong_lengths", int(wrong), limits["wrong_lengths"]),
                ("logp_gap", worst, limits["logp_gap"])]

    def control(self) -> dict:
        """Readings that set the upper end of the limits: the float8
        reference in the program's place (its log-probability of each
        served token), and each served token altered where it is produced
        (moved to the next id, its reported log-probability kept)."""
        Reference = self.family.Reference
        ref, low = Reference(self.conf), Reference(self.conf, quant="fp8")
        w = weights.make(self.family, self.conf, self.seed,
                         device=self.ctx.devices[0])
        L, V = self.t["max_seq_len"], self.conf["vocab_size"]
        picked = self.sample()
        ctl = [check.control(ref, low, w, o.prompt, o.tokens, L)
               for o in picked]
        alt = [check.served(ref, w, o.prompt,
                            (np.asarray(o.tokens) + 1) % V, o.logprobs, L)
               for o in picked]
        return {f"logp_gap.{tag}": float(max(g.max() for g in got))
                for tag, got in (("fp8", ctl), ("altered_token", alt))}
