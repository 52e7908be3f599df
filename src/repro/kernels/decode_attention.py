"""One-token GQA decode attention Pallas kernels — the memory-bandwidth-bound
rollout hotspot (the phase RollMux offloads to the cheap pool).

:func:`decode_attention` (contiguous): the KV cache streams through VMEM in
(bk, D) blocks along the sequential nk grid axis; all G query heads of a KV
group are processed together so each KV block is read from HBM exactly once
(arithmetic intensity ~ 2G flops/byte — bandwidth-bound, which is precisely
the paper's motivation for H20-class hardware). Live cache lengths arrive
via scalar prefetch (SMEM) — a scalar (uniform batch) or per-row ``(B,)``
vector (the engine's ragged slot pool).

:func:`paged_decode_attention` (block-table): same online-softmax loop, but
K/V live in a shared block pool (``models/kvcache.init_paged_cache``
layout) and each batch row owns a *block table* of physical block ids.  The
table is scalar-prefetched and consumed inside the BlockSpec ``index_map``,
so the kernel DMAs exactly the row's own physical blocks straight out of
the pool — no gather materialization, which is the entire point of paged
serving: the contiguous view never has to exist in HBM.  Optional
``k_scale``/``v_scale`` pools dequantize int8 blocks inside the block loop
(per-position scales, so incremental decode writes stay exact).

Both kernels take a ``window`` operand (sliding-window attention, gemma3's
local layers): the single query sits at position ``length-1`` and attends
``(length-1) - pos < window``.  ``window`` is a traced scalar so the
per-layer value can ride a ``lax.scan`` over layers; ``None`` uses a
sentinel large enough to never mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
# matches models/stacks.NO_WINDOW: far beyond any max_seq_len, never masks
NO_WINDOW = 2 ** 30


def _dec_kernel(len_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                m_s, l_s, acc_s, *, scale: float, bk: int, nk: int):
    b, ki = pl.program_id(0), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    length = len_ref[b]
    # query position is length-1: live prefix plus the sliding window
    valid = (pos < length) & (length - 1 - pos < win_ref[0])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # a fully-masked block while m is still NEG_INF would give
    # exp(NEG_INF - NEG_INF) = 1 per masked lane — zero them explicitly
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + p.sum(axis=1)
    acc_s[...] = acc_s[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, length, *, window=None, block_k: int = 512,
                     interpret: bool = True):
    """q: (B,H,D); k/v: (B,S,Hkv,D); length: int32 scalar or (B,) per-row
    live prefix; window: optional sliding-window size (scalar, traced OK).

    Returns (B,H,D)."""
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = D ** -0.5
    bk = min(block_k, S)
    nk = -(-S // bk)
    pad_k = nk * bk - S
    qt = q.reshape(B, Hkv, G, D)
    kt = jnp.moveaxis(k, 2, 1)                        # (B,Hkv,S,D)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    lengths = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    win = jnp.asarray(NO_WINDOW if window is None else window,
                      jnp.int32).reshape(1)

    kernel = functools.partial(_dec_kernel, scale=scale, bk=bk, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # lengths, window
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, ki, lens, w: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, ki, lens, w: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, ki, lens, w: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, ki, lens, w: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(lengths, win, qt, kt, vt)
    return out.reshape(B, H, D)


def _paged_dec_kernel(tbl_ref, len_ref, win_ref, q_ref, k_ref, v_ref, *rest,
                      scale: float, bs: int, nb: int, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, m_s, l_s, acc_s = rest
    b, ki = pl.program_id(0), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (bs, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if quant:
        # per-position scales ride the lane axis as (1, bs) rows: scaling
        # score column j equals dequantizing K row j, and needs no
        # sublane<->lane relayout of the scale vector
        s = s * ks_ref[0]
    # logical position of this table entry's tokens; masks the live prefix,
    # the sliding window, and any null-block (table id 0) tail entries
    pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    length = len_ref[b]
    valid = (pos < length) & (length - 1 - pos < win_ref[0])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # zero masked lanes: a fully-masked block (all-null tail past the
    # length, or everything outside the window) with m still NEG_INF
    # would otherwise contribute exp(NEG_INF - NEG_INF) = 1 per lane
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + p.sum(axis=1)
    # V's per-position scale weights the probabilities instead of V's rows
    pv = p * vs_ref[0] if quant else p
    acc_s[...] = acc_s[...] * corr[:, None] + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nb - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           window=None, k_scale=None, v_scale=None,
                           interpret: bool = True):
    """Block-table GQA decode attention over a shared paged KV pool.

    q: (B,H,D); k_pool/v_pool: (NB,bs,Hkv,D) — a pool of NB physical blocks
    of bs token positions (entry 0 = null block); block_tables: (B,MB) int32
    physical block ids per batch row (0 where unassigned); lengths: (B,)
    live prefix per row.  Row b attends over logical positions
    ``[0, lengths[b])`` of the sequence ``concat(pool[tables[b]])``,
    windowed to the trailing ``window`` positions when given.  With
    ``k_scale``/``v_scale`` ((NB,bs) float32 per-position scales) the pools
    are int8 and dequantized inside the block loop.  Returns (B,H,D) —
    allclose to ``decode_attention`` on the gathered contiguous cache
    (``kernels/ref.paged_decode_attention_ref``).
    """
    B, H, D = q.shape
    NB, bs, Hkv, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = H // Hkv
    scale = D ** -0.5
    qt = q.reshape(B, Hkv, G, D)
    kt = jnp.moveaxis(k_pool, 2, 1)                   # (NB, Hkv, bs, D)
    vt = jnp.moveaxis(v_pool, 2, 1)
    tbl = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    win = jnp.asarray(NO_WINDOW if window is None else window,
                      jnp.int32).reshape(1)
    quant = k_scale is not None

    kernel = functools.partial(_paged_dec_kernel, scale=scale, bs=bs, nb=MB,
                               quant=quant)
    # the paged DMA: row b's ki-th logical block comes from physical pool
    # block tbl[b, ki]
    pool_spec = pl.BlockSpec((1, 1, bs, D),
                             lambda b, h, ki, tbl, lens, w: (tbl[b, ki],
                                                             h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, G, D),
                     lambda b, h, ki, tbl, lens, w: (b, h, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    operands = [qt, kt, vt]
    if quant:
        # (NB, 1, bs): the block's last two dims are the array's own, which
        # the TPU tiling rule accepts where a (1, bs) block of (NB, bs) is not
        scale_spec = pl.BlockSpec(
            (1, 1, bs), lambda b, h, ki, tbl, lens, w: (tbl[b, ki], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [jnp.asarray(k_scale, jnp.float32).reshape(NB, 1, bs),
                     jnp.asarray(v_scale, jnp.float32).reshape(NB, 1, bs)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,               # block tables, lengths, window
        grid=(B, Hkv, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, ki, tbl, lens, w: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(tbl, lengths, win, *operands)
    return out.reshape(B, H, D)
