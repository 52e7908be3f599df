"""One-token GQA decode attention Pallas kernels — the memory-bandwidth-bound
rollout hotspot (the phase RollMux offloads to the cheap pool).

:func:`decode_attention` (contiguous): the KV cache streams through VMEM in
(bk, D) blocks along the sequential nk grid axis; all G query heads of a KV
group are processed together so each KV block is read from HBM exactly once
(arithmetic intensity ~ 2G flops/byte — bandwidth-bound, which is precisely
the paper's motivation for H20-class hardware). Live cache lengths arrive
via scalar prefetch (SMEM) — a scalar (uniform batch) or per-row ``(B,)``
vector (the engine's ragged slot pool).

:func:`paged_decode_attention` (block-table): K/V live in a shared block
pool (``models/kvcache.init_paged_cache`` layout) and each batch row owns
a *block table* of physical block ids, scalar-prefetched into SMEM.  The
pool is read where it lies, in its native ``(NB, bs, Hkv, D)`` layout: a
page is one contiguous ``(bs*Hkv, D)`` tile (a free reshape, so no
per-call transpose or gather of the pool ever exists in HBM), and all H
query heads of a row score it in one matmul, each score column masked to
the query heads of its own KV head (column ``c`` is KV head ``c % Hkv``,
query row ``r`` is KV head ``r // G``; the redundant MXU work is free in a
bandwidth-bound kernel).  One grid step per row walks the row's pages in
chunks of P pages by manual DMA, double-buffered, the last chunk of a row
prefetching the first of the next live row.  P comes from the shapes
alone: about ``_STEP_BYTES`` of K per chunk (P * bs*Hkv*D*itemsize),
capped at the table width MB.  Only pages holding live in-window
positions are DMA'd, so a row costs its length, not MB, and a row of
length 0 (a dead slot) costs nothing and returns zeros.  Optional
``k_scale``/``v_scale`` pools dequantize int8 pages per position (so
incremental decode writes stay exact), applied to the score columns and
the probabilities.

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


# K bytes (and as many of V) that one chunk of the paged kernel fetches:
# enough that the chunk's DMA, not its fixed costs, sets its time, while
# the two buffered chunks of K and V take 2 MB of VMEM.  On a v5e at
# internlm2-1.8b's pages (32 KB) 512 KB ran faster than 128 KB, 256 KB
# and 1 MB.
_STEP_BYTES = 512 * 1024


def _pages_per_step(bs: int, hkv: int, d: int, itemsize: int,
                    mb: int) -> int:
    """Pool pages the paged kernel DMAs per chunk, from the shapes alone."""
    return max(1, min(mb, _STEP_BYTES // (bs * hkv * d * itemsize)))


def _paged_dec_kernel(tbl_ref, len_ref, win_ref, q_ref, k_hbm, v_hbm, *rest,
                      scale: float, bs: int, hkv: int, mb: int, pp: int,
                      rows: int, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, sems, state = rest
    else:
        o_ref, kbuf, vbuf, sems, state = rest
    b = pl.program_id(0)
    H, D = q_ref.shape[1:]
    G = H // hkv
    W = bs * hkv                        # score columns per page
    win = win_ref[0]

    def span(row):
        """Row's pages that hold live in-window positions, [lo, hi), and
        the number of pp-page chunks that cover them (0 for a dead row)."""
        n = len_ref[row]
        hi = jnp.minimum(jax.lax.div(n + bs - 1, bs), mb)
        lo = jnp.minimum(jax.lax.div(jnp.maximum(n - win, 0), bs), hi)
        return lo, hi, jax.lax.div(hi - lo + pp - 1, pp)

    def dma(row, lo, hi, c, slot, start):
        """Start (or wait for) chunk c of row: its live pages only, each
        into the slot's p-th buffer page."""
        first = lo + c * pp

        def page(p, carry):
            pid = tbl_ref[row * mb + first + p]
            for i, (src, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(src.at[pid], buf.at[slot, p],
                                           sems.at[slot, i])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pp, hi - first), page, 0)

    @pl.when(b == 0)
    def _init():
        # a chunk's pages past its row's end keep what an earlier chunk
        # left there; start from zeros so that is always finite
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        state[0] = 0                    # buffer slot of the next row's chunk 0
        state[1] = 0                    # whether an earlier row started it

    lo, hi, n_chunks = span(b)

    @pl.when(n_chunks == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_chunks > 0)
    def _live():
        length = len_ref[b]
        slot0 = state[0]

        @pl.when(state[1] == 0)
        def _():
            dma(b, lo, hi, 0, slot0, True)

        state[1] = 0
        # the next live row, whose chunk 0 this row's last chunk fetches
        nxt = jax.lax.while_loop(
            lambda r: (r < rows) & (span(jnp.minimum(r, rows - 1))[2] == 0),
            lambda r: r + 1, b + 1)

        q = q_ref[0]
        cdt = jnp.promote_types(q.dtype, kbuf.dtype)
        q = q.astype(cdt)
        col = jax.lax.broadcasted_iota(jnp.int32, (H, pp * W), 1)
        # column c holds position c // hkv of the chunk, of KV head c % hkv;
        # query row r belongs to KV head r // G
        col_pos = jax.lax.div(col, hkv)
        same_head = (jax.lax.rem(col, hkv) == jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (H, pp * W), 0), G))
        if quant:
            # expands a page's (1, bs) scales to its W columns, exactly
            expand = (jax.lax.broadcasted_iota(jnp.int32, (bs, W), 0)
                      == jax.lax.div(jax.lax.broadcasted_iota(
                          jnp.int32, (bs, W), 1), hkv)).astype(jnp.float32)

            def col_scale(sc_ref, c):
                """(1, pp*W) column scales of chunk c; a page past the
                row's end takes its last page's (finite, masked anyway)."""
                return jnp.concatenate([jax.lax.dot_general(
                    sc_ref[pl.ds(tbl_ref[b * mb + jnp.minimum(
                        lo + c * pp + p, hi - 1)], 1), :], expand,
                    (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32) for p in range(pp)],
                    axis=1)

        def chunk(c, carry):
            m_prev, l_prev, acc = carry
            slot = jax.lax.rem(slot0 + c, 2)

            @pl.when(c + 1 < n_chunks)
            def _():
                dma(b, lo, hi, c + 1, 1 - slot, True)

            @pl.when((c + 1 == n_chunks) & (nxt < rows))
            def _():
                r = jnp.minimum(nxt, rows - 1)
                nlo, nhi, _ = span(r)
                dma(r, nlo, nhi, 0, 1 - slot, True)
                state[0] = 1 - slot
                state[1] = 1

            dma(b, lo, hi, c, slot, False)
            k = kbuf[slot].reshape(pp * W, D).astype(cdt)
            v = vbuf[slot].reshape(pp * W, D).astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if quant:
                s = s * col_scale(ks_ref, c)
            pos = (lo + c * pp) * bs + col_pos
            valid = same_head & (pos < length) & (length - 1 - pos < win)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            # masked lanes add nothing, also while m is still NEG_INF
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
            if quant:
                # V's per-position scale weights the probabilities instead
                p = p * col_scale(vs_ref, c)
            acc = acc * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        m, l, acc = jax.lax.fori_loop(
            0, n_chunks, chunk,
            (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, D), jnp.float32)))
        out = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        for h in range(hkv):
            o_ref[0, h] = out[h * G:(h + 1) * G]


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
    are int8 and dequantized after the matmuls.  Returns (B,H,D) —
    allclose to ``decode_attention`` on the gathered contiguous cache
    (``kernels/ref.paged_decode_attention_ref``).

    The pools are read where they lie, in their native layout: a page is
    one contiguous ``(bs*Hkv, D)`` tile (a free reshape), scored against
    all H query heads of the row at once and masked to each query head's
    own KV head.  One grid step per row; the row's pages come in chunks
    of P pages by manual DMA, double-buffered, with P from the shapes
    alone (about ``_STEP_BYTES`` of K per chunk, at most MB).  Only the
    pages that hold live in-window positions are DMA'd, and the last
    chunk of a row prefetches the first of the next live row.  A row of
    length 0 costs no DMA and returns zeros.
    """
    B, H, D = q.shape
    NB, bs, Hkv, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = H // Hkv
    W = bs * Hkv
    pp = _pages_per_step(bs, Hkv, D, k_pool.dtype.itemsize, MB)
    quant = k_scale is not None
    tbl = jnp.asarray(block_tables, jnp.int32).reshape(B * MB)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    win = jnp.asarray(NO_WINDOW if window is None else window,
                      jnp.int32).reshape(1)
    pool_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
                pool_spec, pool_spec]
    operands = [q, k_pool.reshape(NB, W, D), v_pool.reshape(NB, W, D)]
    if quant:
        # a page's bs scales are too narrow a slice to DMA; the layer's
        # (NB, bs) scale pools come into VMEM whole, once a call
        in_specs += [pl.BlockSpec((NB, bs), lambda b, *_: (0, 0))] * 2
        operands += [jnp.asarray(k_scale, jnp.float32),
                     jnp.asarray(v_scale, jnp.float32)]

    kernel = functools.partial(_paged_dec_kernel, scale=D ** -0.5, bs=bs,
                               hkv=Hkv, mb=MB, pp=pp, rows=B, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,               # block tables, lengths, window
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, pp, W, D), k_pool.dtype),
                        pltpu.VMEM((2, pp, W, D), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    # the output is (B, Hkv, G, D), reshaped to (B, H, D) by a bitcast: an
    # op's profile stats name its operands, and this keeps the kernel's
    # name out of its consumer's, so a trace shows one op per call by it
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        # rows run in order: a row's last chunk fetches the next row's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(tbl, lengths, win, *operands)
    return out.reshape(B, H, D)
