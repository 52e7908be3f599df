"""Flash attention Pallas TPU kernel — the compute-bound training hotspot.

Grid (B, H, nq, nk): the nk dimension is sequential ("arbitrary"); running
max / denominator / accumulator live in VMEM scratch across nk steps.
BlockSpecs tile Q/K/V into (bq, D)/(bk, D) VMEM blocks with MXU-friendly
128-multiples; GQA is handled in the K/V index_map (kv head = q head // G),
so grouped K/V blocks are fetched once per group without materializing a
repeated KV tensor in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
               scale: float, causal: bool, window, bq: int, bk: int,
               nk: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)              # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_len                           # padding
    if causal:
        mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + p.sum(axis=1)
    acc_s[...] = acc_s[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True):
    """q: (B,S,H,D); k/v: (B,S,Hkv,D). Returns (B,S,H,D)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale = D ** -0.5
    bq, bk = min(block_q, S), min(block_k, S)
    nq, nk = -(-S // bq), -(-S // bk)
    pad_q, pad_k = nq * bq - S, nk * bk - S
    # (B,H,S,D) layout so BlockSpec tiles the trailing (S, D) plane
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk, seq_len=S)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :S], 1, 2)
