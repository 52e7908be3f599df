"""Fused sampling epilogue Pallas kernels (logits -> token, no host hop).

:func:`greedy_sample` fuses the argmax + logprob epilogue of a decode step
into one pass over the vocabulary: the logits stream through VMEM in
``block_v`` chunks while running max / argmax / logsumexp scratch carries
the online reduction, so the (B, V) logits never round-trip through a
separate ``log_softmax`` materialization.  The greedy token's logprob is
``logit[argmax] - logsumexp = -log(sum exp(x - max))`` — free once the
online sum is in hand.

:func:`topk_values` keeps a running top-k scratch per row (k static and
small, the extraction loop is unrolled); :func:`topk_mask` turns that into
threshold-masked logits for ``jax.random.categorical`` — the sampled path's
epilogue.  Ties **at** the k-th value all survive the mask (may keep more
than k candidates); jnp oracles in :mod:`repro.kernels.ref` mirror that
choice.

Pure-jnp oracles: ``ref.greedy_sample_ref`` / ``ref.topk_mask_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _greedy_kernel(x_ref, tok_ref, lp_ref, m_s, l_s, idx_s, *,
                   bv: int, nv: int):
    vi = pl.program_id(0)

    @pl.when(vi == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        idx_s[...] = jnp.zeros_like(idx_s)

    x = x_ref[...].astype(jnp.float32)                # (B, bv)
    bm = x.max(axis=1, keepdims=True)                 # (B, 1)
    # first in-block occurrence of the max, as a min over matching lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    bi = jnp.min(jnp.where(x == bm, lane, bv), axis=1, keepdims=True)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, bm)
    # strict > keeps the first occurrence across blocks, matching
    # jnp.argmax over the full row
    idx_s[...] = jnp.where(bm > m_prev, vi * bv + bi, idx_s[...])
    l_s[...] = (l_s[...] * jnp.exp(m_prev - m_new)
                + jnp.exp(x - m_new).sum(axis=1, keepdims=True))
    m_s[...] = m_new

    @pl.when(vi == nv - 1)
    def _finalize():
        tok_ref[...] = idx_s[...]
        # greedy logprob: logit[argmax] - logsumexp = -log(l)
        lp_ref[...] = -jnp.log(jnp.maximum(l_s[...], 1e-30))


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def greedy_sample(logits, *, block_v: int = 1024, interpret: bool = True):
    """logits: (B, V) -> (tokens (B,) int32, logprobs (B,) float32).

    One fused pass: ``tokens = argmax(logits)`` (first occurrence on ties)
    and ``logprobs = log_softmax(logits)[tokens]``.  Every block holds all
    B rows, so the block's row dim is the array's own whatever B is (the
    TPU tiling rule), and the grid walks the vocabulary only."""
    B, V = logits.shape
    bv = min(block_v, V)
    nv = -(-V // bv)
    pad = nv * bv - V
    x = logits
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=NEG_INF)

    kernel = functools.partial(_greedy_kernel, bv=bv, nv=nv)
    col = pl.BlockSpec((B, 1), lambda vi: (0, 0))
    tokens, logprobs = pl.pallas_call(
        kernel,
        grid=(nv,),
        in_specs=[pl.BlockSpec((B, bv), lambda vi: (0, vi))],
        out_specs=(col, col),
        out_shape=(jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((B, 1), jnp.float32),
            pltpu.VMEM((B, 1), jnp.float32),
            pltpu.VMEM((B, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="greedy_sample",
    )(x)
    return tokens[:, 0], logprobs[:, 0]


def _topk_kernel(x_ref, out_ref, top_s, *, k: int, nv: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        top_s[...] = jnp.full_like(top_s, NEG_INF)

    x = x_ref[...].astype(jnp.float32)                # (1, bv)
    merged = jnp.concatenate([top_s[...], x], axis=1)  # (1, k + bv)
    lane = jax.lax.broadcasted_iota(jnp.int32, merged.shape, 1)
    vals = []
    for _ in range(k):          # unrolled: k is static and small
        i = jnp.argmax(merged, axis=1)                # (1,)
        vals.append(merged.max(axis=1))
        # retire only the first occurrence so duplicates stay rankable
        merged = jnp.where(lane == i[:, None], NEG_INF, merged)
    top_s[...] = jnp.stack(vals, axis=1)              # (1, k) descending

    @pl.when(vi == nv - 1)
    def _finalize():
        out_ref[...] = top_s[...]


@functools.partial(jax.jit, static_argnames=("k", "block_v", "interpret"))
def topk_values(logits, k: int, *, block_v: int = 1024,
                interpret: bool = True):
    """logits: (B, V) -> (B, k) largest values per row, descending."""
    B, V = logits.shape
    if not 0 < k <= V:
        raise ValueError(f"k={k} out of range for vocab {V}")
    bv = min(block_v, V)
    nv = -(-V // bv)
    pad = nv * bv - V
    x = logits
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=NEG_INF)

    kernel = functools.partial(_topk_kernel, k=k, nv=nv)
    return pl.pallas_call(
        kernel,
        grid=(B, nv),
        in_specs=[pl.BlockSpec((1, bv), lambda b, vi: (b, vi))],
        out_specs=pl.BlockSpec((1, k), lambda b, vi: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="topk_values",
    )(x)


def topk_mask(logits, k: int, *, block_v: int = 1024,
              interpret: bool = True):
    """Mask logits below the k-th largest per row to NEG_INF.

    Feed the result to ``jax.random.categorical`` for top-k sampling.
    Rows keep every entry >= the k-th value, so ties at the threshold may
    leave more than k candidates (same as ``ref.topk_mask_ref``)."""
    top = topk_values(logits, k, block_v=block_v, interpret=interpret)
    thresh = top[:, k - 1]
    return jnp.where(logits >= thresh[:, None],
                     logits.astype(jnp.float32), NEG_INF)
