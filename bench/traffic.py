"""The one traffic generator: turns a traffic file's parameters and a seed
into the inputs of a run.

Every seed gets the same set of sizes (prompt lengths, response budgets)
in another order, so that seeds change the tokens and the order, never
the amount of work.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def lognormal_quantiles(n: int, mu: float, sigma: float, lo: float,
                        hi: float) -> np.ndarray:
    """The n quantiles at (j + 1/2)/n of a lognormal clipped to [lo, hi]:
    the paper's long-tail response lengths (its Fig. 11; mu -1.2, sigma
    0.9 in ``core/distributions.sample_response_fractions``), with no draw
    left to chance."""
    z = [NormalDist().inv_cdf((j + 0.5) / n) for j in range(n)]
    return np.clip(np.exp(mu + sigma * np.asarray(z)), lo, hi)


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def rollout_batch(traffic: dict, seed: int, index: int, vocab: int):
    """Batch ``index`` of a closed-loop rollout mix: ``prompts`` distinct
    prompts, each sent ``group`` times, as a list of
    ``(group_index, prompt, budget)``.

    Prompt lengths cycle through ``prompt_lengths`` over a batch's
    prompts, so every batch holds the same lengths; response budgets are
    the lognormal quantiles times ``max_new``, dealt to the sequences in
    an order drawn from the seed and the batch's index."""
    n_p, g = traffic["prompts"], traffic["group"]
    r = traffic["response"]
    n = n_p * g
    fr = lognormal_quantiles(n, r["mu"], r["sigma"], r["min_frac"],
                             r["max_frac"])
    budgets = np.maximum(1, np.floor(fr * r["max_new"])).astype(int)
    rng = rng_for(seed, index)
    budgets = budgets[rng.permutation(n)]
    lens = traffic["prompt_lengths"]
    out = []
    for p in range(n_p):
        length = lens[p % len(lens)]
        prompt = rng.integers(0, vocab, length, dtype=np.int32)
        for m in range(g):
            out.append((p, prompt, int(budgets[p * g + m])))
    return out

