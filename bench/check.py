"""The comparison that decides ``correct`` for served tokens.

For each served token, the gap between the log-probability the engine
reported for it and the float32 reference's log-probability of the same
token after the same prompt and served tokens.  It holds for sampled
tokens as for greedy ones: the engine reports ``log_softmax(logits)`` of
the token it served, whatever the draw.  A token altered after it was
drawn reads the distance between two tokens' log-probabilities; a model
computed wrong reads the error of its logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _token_logp(logits, targets):
    return jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               targets[:, None], -1)[:, 0]


@jax.jit
def _logp_gap(logits, targets, valid, logprobs):
    """|log p(target) - logprobs| at the positions that predict
    ``targets``."""
    return jnp.where(valid, jnp.abs(_token_logp(logits, targets) - logprobs),
                     0.0)


def padded(prompt, tokens, length: int):
    """prompt + served tokens, zero-padded to ``length``; the targets of
    positions ``P-1 .. P+n-2`` are the served tokens."""
    seq = np.zeros(length, np.int32)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(tokens, np.int32)])
    seq[:len(full)] = full
    P, n = len(prompt), len(tokens)
    targets = np.zeros(length, np.int32)
    valid = np.zeros(length, bool)
    targets[P - 1:P - 1 + n] = tokens
    valid[P - 1:P - 1 + n] = True
    return seq, targets, valid


def _at(values, P, n, length):
    out = np.zeros(length, np.float32)
    out[P - 1:P - 1 + n] = values
    return jnp.asarray(out)


def served(ref, w, prompt, tokens, logprobs, length: int) -> np.ndarray:
    """Per served token: the gap between the served log-probability and
    the float32 reference's."""
    seq, targets, valid = padded(prompt, tokens, length)
    lp = _at(logprobs, len(prompt), len(tokens), length)
    gap = _logp_gap(ref.logits(w, seq[None])[0], jnp.asarray(targets),
                    jnp.asarray(valid), lp)
    return np.asarray(gap)[valid]


def control(ref, low, w, prompt, tokens, length: int) -> np.ndarray:
    """The lower-precision ``low`` in the program's place, at the same
    positions: the gap between ``low``'s log-probability of each served
    token and the reference's."""
    seq, targets, valid = padded(prompt, tokens, length)
    targets, valid = jnp.asarray(targets), jnp.asarray(valid)
    low_lp = _token_logp(low.logits(w, seq[None])[0], targets)
    gap = _logp_gap(ref.logits(w, seq[None])[0], targets, valid, low_lp)
    return np.asarray(gap)[valid]
