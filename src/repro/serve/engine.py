"""Continuous-batching rollout engine (in-flight batching over a slot pool).

The engine services generation requests the way a rollout pool must under
heavy traffic: a :class:`~repro.serve.queue.RequestQueue` feeds a fixed
pool of KV-cache slots (:class:`~repro.serve.slots.SlotManager`) in the
order a pluggable admission policy picks (:mod:`repro.serve.sched`:
``fifo`` strict arrival order, ``deadline`` EDF with bounded head
skipping and per-job token budgets, ``slo`` deadlines derived from the
inter-group SLO contract); each scheduler iteration first *prefills*
picked requests into free slots, then runs one (or ``block_size`` fused)
*decode* step(s) for every live slot at once.  Requests therefore join
and leave the decode batch mid-flight: a slot is recycled the moment its
request hits EOS or its per-request decode budget, and the next queued
request prefills into it — no static-batch barrier, no head-of-line
blocking on long generations.

Per-slot sequence positions are independent (the pool cache carries a
per-slot ``index`` vector); decode is the model's own single-token step
``vmap``-ped over slots, so engine output is mathematically the per-request
``rl.rollout.generate`` computation, token for token and logprob for
logprob (the equivalence ``tests/test_serve_engine.py`` asserts).

``block_size > 1`` fuses K decode steps into one jitted ``lax.scan`` to
amortise per-step dispatch (scheduling decisions then happen every K
tokens); ``block_size=1`` is exact per-token continuous batching.

Two KV layouts (``EngineConfig.kv_layout``): **contiguous** gives every
slot a full ``max_seq_len`` stripe; **paged** stores ``cache_seq`` leaves
in a shared pool of ``kv_block_size``-token blocks
(:class:`~repro.serve.slots.PagedSlotManager`).  Paged admission gates on
*block* availability as well as slots (a request reserves only what its
own budget can touch), block tables grow on demand as ``index`` crosses
block boundaries, and decode runs the same model step over a gathered
per-slot view of the block table — a pure permutation-copy, so paged
output is token/logprob-identical to contiguous (locked in by
``tests/test_serve_paged.py``).

``EngineConfig.prefix_share`` (paged only) adds radix prompt-prefix KV
sharing (:mod:`repro.serve.radix`): a content-addressed radix tree over
full token blocks, so *any* two requests agreeing on a block-aligned
token prefix — GRPO's ``group``-way duplicated prompts, a shared system
preamble across tenants, or a multi-turn episode replaying its own
history — share exactly those blocks, no tag required
(``prefix_key`` is now just an optional isolation namespace).  An exact
repeat of a registered prompt admits with zero model compute from the
boundary snapshot; partial overlaps pin the matching full blocks
(ref-counted, several slot owners per block) and prefill into a
write-masked row plus a private copy-on-write tail.  Admission then
gates on *net new* blocks, which is where the extra concurrency at
equal KV memory comes from.  Output stays bit-identical to the unshared
engine
(the shared blocks hold exactly the donor's prefill, and gathers are
permutation-copies).

Compilation notes: jitted prefill / admit / decode-block functions are
cached per (model, max_seq_len, temperature, eos_id) — engines with the
same serving shape share compilations (cheap to construct per trace), and
prefill additionally specialises on prompt length, so drivers should
bucket prompt lengths (the benchmark uses a handful of buckets).
"""
from __future__ import annotations

import copy
import functools
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import MetricsSnapshot, span, warn_legacy_once
from repro.data import tokenizer as tok
from repro.models.attention import gather_blocks
from repro.serve.blocks import blocks_for
from repro.serve.queue import RequestQueue
from repro.serve.radix import RadixPrefixIndex
from repro.serve.request import Request, RequestOutput
from repro.serve.sched import make_policy
from repro.serve.slots import (PagedSlotManager, SlotManager, _batch_axis,
                               insert_cache)


@dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 8
    max_seq_len: int = 256
    eos_id: int = tok.EOS
    temperature: float = 0.0          # 0 => greedy
    block_size: int = 1               # decode steps fused per scheduler tick
    max_waiting: Optional[int] = None
    kv_layout: str = "contiguous"     # "contiguous" | "paged"
    kv_block_size: int = 16           # tokens per KV block (paged only)
    num_kv_blocks: Optional[int] = None   # paged pool size (default: same
                                          # memory as contiguous num_slots)
    sched: str = "fifo"               # admission policy (serve.sched):
                                      # "fifo" | "deadline" | "slo" — or pass
                                      # a policy object to Engine(policy=...)
    prefix_share: bool = False        # radix prompt-prefix KV sharing
                                      # (paged layout only)
    kernel_backend: str = "jnp"       # decode-step backend: "jnp" (vmapped
                                      # model step) | "pallas" (batched
                                      # decode-attention kernels + fused
                                      # sampling epilogue)
    kv_dtype: Optional[str] = None    # paged KV storage: None/"auto" keeps
                                      # the model dtype, "int8" quantizes
                                      # blocks with per-position scales

    def __post_init__(self):
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        if self.max_seq_len < 2:
            raise ValueError("max_seq_len must cover prompt + decode")
        if self.kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv_layout {self.kv_layout!r}")
        if self.kv_block_size < 1:
            raise ValueError("kv_block_size must be >= 1")
        if self.sched not in ("fifo", "deadline", "slo"):
            raise ValueError(f"unknown sched policy {self.sched!r}")
        if self.prefix_share and self.kv_layout != "paged":
            raise ValueError("prefix_share requires kv_layout='paged' "
                             "(sharing is block-granular)")
        if self.kernel_backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown kernel_backend "
                             f"{self.kernel_backend!r}")
        if self.kv_dtype not in (None, "auto", "int8"):
            raise ValueError(f"unknown kv_dtype {self.kv_dtype!r}")
        if self.kv_dtype == "int8" and self.kv_layout != "paged":
            raise ValueError("kv_dtype='int8' requires kv_layout='paged' "
                             "(quantization is per KV block)")


# Engine.stats legacy-shim warn-once flag (mutable so tests can reset it;
# same pattern as rl.rollout's RolloutSpec kwargs migration shim).
_warned_legacy = [False]


@dataclass
class EngineStats:
    steps: int = 0                    # decode steps executed (all slots)
    blocks: int = 0                   # scheduler ticks that ran a decode
    prefills: int = 0
    recorded_tokens: int = 0          # useful (mask=1) tokens produced
    slot_steps: int = 0               # num_slots * steps (capacity offered)
    peak_active: int = 0              # max concurrently live requests
    peak_kv_blocks: int = 0           # max KV blocks in use (paged only)
    prefix_hits: int = 0              # admits that skipped prefill entirely
    prefix_partial_hits: int = 0      # admits that shared blocks but prefilled
    blocks_saved: int = 0             # KV blocks pinned instead of allocated
    decode_time_s: float = 0.0        # wall time inside decode dispatch+sync
    adoptions: int = 0                # admits fed by a KV transfer handle
    #                                   (disaggregated prefill, serve.disagg)
    suspends: int = 0                 # requests suspended (tool boundary or
    #                                   carry_live weight sync)
    resumes: int = 0                  # suspended requests re-admitted

    @property
    def slot_utilization(self) -> float:
        return self.recorded_tokens / max(self.slot_steps, 1)

    @property
    def time_per_token(self) -> float:
        """Measured service time of one decode step (all live slots decode
        one token each in that time) — the engine-side estimate SLO
        admission consumes (``SchedulerPolicy.observe_step``)."""
        return self.decode_time_s / max(self.steps, 1)


def _make_sampler(temperature: float, kernel_backend: str, interpret: bool):
    """(logits, key) -> (next_token (N,), token_logprob (N,)).

    The pallas backend fuses the greedy argmax + logprob epilogue into one
    kernel pass over the vocabulary (``kernels.sampling.greedy_sample``);
    sampled decoding keeps ``jax.random.categorical`` (the draw itself
    needs the full distribution either way)."""
    def sample_logp(logits, key):
        if temperature == 0:
            if kernel_backend == "pallas":
                from repro.kernels.sampling import greedy_sample
                return greedy_sample(logits, interpret=interpret)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        else:
            nxt = jax.random.categorical(
                key, logits / temperature, axis=-1).astype(jnp.int32)
        logp = jax.nn.log_softmax(logits, -1)
        return nxt, jnp.take_along_axis(logp, nxt[:, None], -1)[:, 0]
    return sample_logp


@functools.lru_cache(maxsize=32)
def _engine_fns(model, max_seq_len: int, temperature: float, eos_id: int,
                kernel_backend: str = "jnp", interpret: bool = True):
    """Jitted prefill / admit / decode-block shared by all engines with the
    same serving shape (keyed on the hashable frozen ``Model``).

    ``kernel_backend="pallas"`` swaps the decode block's vmapped model step
    for the batched Pallas path (``Model.kernel_decode_step``: one call per
    step over the whole slot pool, decode attention in a kernel) plus the
    fused greedy sampling epilogue; admission/prefill stay shared."""

    def prefill_fn(params, prompt, frontend):
        cache = model.init_cache(1, max_seq_len)
        logits, cache = model.prefill(params, prompt, cache,
                                      frontend=frontend)
        return logits[0], cache

    def scatter_fn(logits, one, pool, slot, last_logits, alive, remaining,
                   budget):
        """Splice a prefilled batch=1 cache into slot ``slot`` plus the
        logits/alive/budget row updates — the insert half of ``admit``,
        standalone so a disaggregated prefill result (KV transfer handle)
        can be adopted without re-running the model."""
        return (insert_cache(pool, one, slot),
                last_logits.at[slot].set(logits),
                alive.at[slot].set(True),
                remaining.at[slot].set(budget))

    def admit_fn(params, prompt, frontend, pool, slot, last_logits, alive,
                 remaining, budget):
        """Prefill one request and splice it into slot ``slot`` — a single
        dispatch covering cache insert + logits/alive/budget row updates."""
        logits, one = prefill_fn(params, prompt, frontend)
        return scatter_fn(logits, one, pool, slot, last_logits, alive,
                          remaining, budget)

    cache_axes = {k: _batch_axis(k) for k in model.cache_logical_specs()}

    def decode_one(params, token, cache):
        # re-grow the batch=1 axis the vmap stripped, run the model's own
        # decode step, then strip it again for out_axes
        cache_b = {k: (v if k == "index" else v[:, None])
                   for k, v in cache.items()}
        logits, cache_b = model.decode_step(
            params, jnp.reshape(token, (1, 1)), cache_b)
        cache_o = {k: (v if k == "index" else v[:, 0])
                   for k, v in cache_b.items()}
        return logits[0], cache_o

    pool_decode = jax.vmap(decode_one, in_axes=(None, 0, cache_axes),
                           out_axes=(0, cache_axes))
    sample_logp = _make_sampler(temperature, kernel_backend, interpret)

    def block_fn(params, last_logits, cache, alive, remaining, keys):
        def step(carry, key):
            logits, cache, alive, remaining = carry
            nxt, tok_logp = sample_logp(logits, key)        # (N,), (N,)
            rec = alive & (remaining > 0)
            if kernel_backend == "pallas":
                logits, cache = model.kernel_decode_step(
                    params, nxt[:, None], cache, interpret=interpret)
            else:
                logits, cache = pool_decode(params, nxt, cache)
            alive = alive & (nxt != eos_id)
            remaining = remaining - rec.astype(jnp.int32)
            return (logits, cache, alive, remaining), (nxt, tok_logp, rec)

        carry, out = jax.lax.scan(
            step, (last_logits, cache, alive, remaining), keys)
        return carry, out                   # out: (toks, logps, recs) (K,N)

    def extract_fn(pool, slot):
        """Gather slot ``slot``'s full cache stripe into a batch=1 pytree —
        the inverse of ``insert_cache`` (suspension capture)."""
        out = {}
        for name, leaf in pool.items():
            if name == "index":
                out[name] = leaf[slot]
            else:
                start = (0, slot) + (0,) * (leaf.ndim - 2)
                sizes = (leaf.shape[0], 1) + leaf.shape[2:]
                out[name] = jax.lax.dynamic_slice(leaf, start, sizes)
        return out

    def inject_fn(params, tokens, one):
        """Advance a batch=1 cache view through forced tokens (a tool
        result) with the model's own decode step; the returned logits
        predict the first post-injection token.  Specialises on the token
        count, like prefill does on prompt length."""
        def step(one, t):
            logits, one = model.decode_step(
                params, jnp.reshape(t, (1, 1)), one)
            return one, logits
        one, logits = jax.lax.scan(step, one, tokens)
        return logits[-1, 0], one

    return {"admit": jax.jit(admit_fn), "block": jax.jit(block_fn),
            "prefill": jax.jit(prefill_fn), "scatter": jax.jit(scatter_fn),
            "extract": jax.jit(extract_fn), "inject": jax.jit(inject_fn)}


@functools.lru_cache(maxsize=32)
def _paged_engine_fns(model, max_seq_len: int, kv_block_size: int,
                      temperature: float, eos_id: int,
                      kernel_backend: str = "jnp",
                      kv_dtype: Optional[str] = None,
                      interpret: bool = True):
    """Jitted admit / decode-block for the paged KV layout.

    Admission scatters a prefilled contiguous cache into the slot's block
    table; decode gathers each live slot's blocks into a contiguous view,
    runs the model's own single-token step on it (value-identical to the
    contiguous path — the gather is a permutation-copy), then scatters back
    only the block that step wrote.  Dead / over-budget slots carry
    all-zero table rows, so their writes land in the null block 0.

    ``kernel_backend="pallas"`` replaces the gather/vmap/scatter decode
    with one batched ``Model.kernel_decode_step`` per step: the block
    tables are scalar-prefetched into the decode-attention kernel, so the
    contiguous view is never materialized.

    ``kv_dtype="int8"`` stores paged pools quantized with per-position
    scales (``models.kvcache.quantize_kv``): admission quantizes on the
    block write, the jnp decode path dequantizes the gathered view (and
    writes back only the one freshly written position, keeping stored
    blocks stable), and the pallas path dequantizes inside the kernel's
    block loop.  Radix snapshots stay float — sharing quantizes on the
    tail-block write like any other write.

    Besides the fused ``admit`` (prefill + scatter, the non-sharing fast
    path), the prefix-sharing engine uses the split pieces: ``prefill``
    runs the model once, ``scatter`` writes a given prefill result through
    a (possibly write-masked) table row, ``snapshot`` extracts the radix
    entry (partial tail block + slot-resident rows), and ``share_admit``
    admits a radix hit with *no* model compute — cached logits, cached
    slot rows, and a copy-on-write tail block seeded from the snapshot.
    """
    from repro.models import kvcache
    SUF = kvcache.SCALE_SUFFIX
    paged = frozenset(model.paged_cache_names())
    quant = kv_dtype == "int8"
    view_dtype = jnp.dtype(model.cfg.dtype)       # gathered-view dtype
    MB = blocks_for(max_seq_len, kv_block_size)   # table entries per slot
    S_view = MB * kv_block_size                   # gathered view length

    def prefill_fn(params, prompt, frontend):
        cache = model.init_cache(1, max_seq_len)
        logits, cache = model.prefill(params, prompt, cache,
                                      frontend=frontend)
        return logits[0], cache

    def _blockify(u):
        """(L, S, *rest) -> (L, MB, kv_block_size, *rest), zero-padded."""
        pad = [(0, 0)] * u.ndim
        pad[1] = (0, S_view - u.shape[1])
        u = jnp.pad(u, pad)
        return u.reshape(u.shape[0], MB, kv_block_size, *u.shape[2:])

    def scatter_fn(logits, one, pool, table_row, slot,
                   last_logits, alive, remaining, budget):
        """Write one prefilled batch=1 cache into the pool through
        ``table_row`` (write-masked rows send shared-prefix blocks to the
        null block) plus the logits/alive/budget row updates."""
        out = {}
        for name, leaf in pool.items():
            if name.endswith(SUF):
                continue                  # written beside the parent leaf
            upd = one[name]
            if name == "index":
                out[name] = leaf.at[slot].set(jnp.asarray(upd, leaf.dtype))
            elif name in paged:
                u = _blockify(upd[:, 0])                    # (L, MB, bs, ...)
                # unassigned / masked table entries are 0: their blocks
                # fall through to the null block
                if quant:
                    q, s = kvcache.quantize_kv(u, 3)
                    out[name] = leaf.at[:, table_row].set(q)
                    out[name + SUF] = pool[name + SUF].at[:, table_row].set(s)
                else:
                    out[name] = leaf.at[:, table_row].set(u.astype(leaf.dtype))
            else:
                start = (0, slot) + (0,) * (leaf.ndim - 2)
                out[name] = jax.lax.dynamic_update_slice(
                    leaf, upd.astype(leaf.dtype), start)
        return (out, last_logits.at[slot].set(logits),
                alive.at[slot].set(True), remaining.at[slot].set(budget))

    def admit_fn(params, prompt, frontend, pool, table_row, slot,
                 last_logits, alive, remaining, budget):
        """Prefill one request and scatter it into its block table (plus the
        slot-resident leaf rows) in a single dispatch."""
        logits, one = prefill_fn(params, prompt, frontend)
        return scatter_fn(logits, one, pool, table_row, slot,
                          last_logits, alive, remaining, budget)

    def snapshot_fn(one, *, tail_block):
        """Radix-entry extraction from a prefill result: the partial tail
        block of every paged leaf (``tail_block`` is its static table
        position, or None when the prompt ends on a block boundary) and the
        full batch=1 rows of every slot-resident leaf."""
        tail = {}
        if tail_block is not None:
            for name in sorted(paged):
                tail[name] = _blockify(one[name][:, 0])[:, tail_block]
        slot_leaves = {name: v for name, v in one.items()
                       if name != "index" and name not in paged}
        return tail, slot_leaves

    def share_admit_fn(pool, tail, slot_leaves, logits, tail_pid, slot,
                       last_logits, alive, remaining, budget, index_val):
        """Admit an exact radix hit with zero model compute: seed the
        private copy-on-write tail block and the slot-resident rows from
        the donor's snapshot, and restore the cached post-prompt logits."""
        out = {}
        for name, leaf in pool.items():
            if name.endswith(SUF):
                continue                  # written beside the parent leaf
            if name == "index":
                out[name] = leaf.at[slot].set(
                    jnp.asarray(index_val, leaf.dtype))
            elif name in paged:
                if name in tail:
                    if quant:             # snapshots are float: quantize
                        q, s = kvcache.quantize_kv(tail[name], 2)
                        out[name] = leaf.at[:, tail_pid].set(q)
                        out[name + SUF] = \
                            pool[name + SUF].at[:, tail_pid].set(s)
                    else:
                        out[name] = leaf.at[:, tail_pid].set(
                            tail[name].astype(leaf.dtype))
                else:           # prompt ends on a block boundary: no tail
                    out[name] = leaf
                    if quant:
                        out[name + SUF] = pool[name + SUF]
            else:
                upd = slot_leaves[name]
                start = (0, slot) + (0,) * (leaf.ndim - 2)
                out[name] = jax.lax.dynamic_update_slice(
                    leaf, upd.astype(leaf.dtype), start)
        return (out, last_logits.at[slot].set(logits),
                alive.at[slot].set(True), remaining.at[slot].set(budget))

    cache_keys = tuple(model.cache_logical_specs()) + \
        (model.scale_cache_names() if quant else ())
    cache_axes = {k: (0 if k == "index" else
                      (None if k in paged or k.endswith(SUF) else 1))
                  for k in cache_keys}
    slot_axes = {k: ax for k, ax in cache_axes.items()
                 if k not in paged and not k.endswith(SUF)}

    def decode_one(params, token, cache, table_row):
        # gather this slot's blocks into a contiguous (batch=1) view, run
        # the model's own decode step, and hand back the written block
        # (int8: dequantize the view, hand back only the written *row* so
        # already-stored positions are never re-quantized)
        old_idx = cache["index"]
        cache_b = {}
        for k, v in cache.items():
            if k == "index":
                cache_b[k] = v
            elif k.endswith(SUF):
                continue
            elif k in paged:
                # (L, S_view, *rest) with the batch=1 axis re-grown
                g = gather_blocks(v, table_row, axis=1)
                if quant:
                    s = gather_blocks(cache[k + SUF], table_row, axis=1)
                    g = kvcache.dequantize_kv(g, s, view_dtype)
                cache_b[k] = g[:, None]
            else:
                cache_b[k] = v[:, None]
        logits, cache_b = model.decode_step(
            params, jnp.reshape(token, (1, 1)), cache_b)
        b = jnp.minimum(old_idx // kv_block_size, MB - 1)
        pid = jnp.take(table_row, b)        # 0 (null) if not materialized
        out, written = {}, {}
        for k, v in cache_b.items():
            if k == "index":
                out[k] = v
            elif k in paged:
                if quant:
                    written[k] = jax.lax.dynamic_slice_in_dim(
                        v[:, 0], jnp.minimum(old_idx, S_view - 1), 1,
                        axis=1)[:, 0]       # just the new row (L, ...)
                else:
                    written[k] = jax.lax.dynamic_slice_in_dim(
                        v[:, 0], b * kv_block_size, kv_block_size, axis=1)
            else:
                out[k] = v[:, 0]
        return logits[0], out, written, pid, old_idx % kv_block_size

    pool_decode = jax.vmap(
        decode_one, in_axes=(None, 0, cache_axes, 0),
        out_axes=(0, slot_axes, {k: 0 for k in paged}, 0, 0))
    sample_logp = _make_sampler(temperature, kernel_backend, interpret)

    def jnp_decode(params, nxt, cache, tables):
        logits, slot_cache, written, pids, offs = pool_decode(
            params, nxt, cache, tables)
        new_cache = dict(cache) | dict(slot_cache)
        for k in paged:
            # distinct live slots own distinct blocks, so pids collide
            # only at the null block 0 (dead slots) — a don't-care write
            if quant:
                rows = jnp.moveaxis(written[k], 0, 1)       # (L, N, ...)
                q, s = kvcache.quantize_kv(rows, 2)
                new_cache[k] = cache[k].at[:, pids, offs].set(q)
                new_cache[k + SUF] = cache[k + SUF].at[:, pids, offs].set(s)
            else:
                blk = jnp.moveaxis(written[k], 0, 1)        # (L, N, bs, ...)
                new_cache[k] = cache[k].at[:, pids].set(blk)
        return logits, new_cache

    def block_fn(params, last_logits, cache, tables, alive, remaining, keys):
        def step(carry, key):
            logits, cache, alive, remaining = carry
            nxt, tok_logp = sample_logp(logits, key)        # (N,), (N,)
            rec = alive & (remaining > 0)
            if kernel_backend == "pallas":
                logits, cache = model.kernel_decode_step(
                    params, nxt[:, None], cache, tables=tables,
                    interpret=interpret)
            else:
                logits, cache = jnp_decode(params, nxt, cache, tables)
            alive = alive & (nxt != eos_id)
            remaining = remaining - rec.astype(jnp.int32)
            return (logits, cache, alive, remaining), (nxt, tok_logp, rec)

        carry, out = jax.lax.scan(
            step, (last_logits, cache, alive, remaining), keys)
        return carry, out                   # out: (toks, logps, recs) (K,N)

    def suspend_fn(pool, slot, tail_pid):
        """Capture a live slot's mid-generation state for suspension: the
        (dequantized) partial tail block of every paged leaf plus the
        batch=1 rows of every slot-resident leaf — the same snapshot shape
        a radix entry / KV transfer handle carries, taken from the *pool*
        instead of a prefill result.  The full blocks travel as allocator
        pins, not copies."""
        tail = {}
        for name in sorted(paged):
            t = pool[name][:, tail_pid]
            if quant:
                t = kvcache.dequantize_kv(t, pool[name + SUF][:, tail_pid],
                                          view_dtype)
            tail[name] = t
        slot_leaves = {}
        for name, leaf in pool.items():
            if name == "index" or name in paged or name.endswith(SUF):
                continue
            start = (0, slot) + (0,) * (leaf.ndim - 2)
            sizes = (leaf.shape[0], 1) + leaf.shape[2:]
            slot_leaves[name] = jax.lax.dynamic_slice(leaf, start, sizes)
        return tail, slot_leaves

    def inject_fn(params, tokens, one):
        """Advance a batch=1 contiguous cache view through forced tokens
        (a tool result) with the model's own decode step; the returned
        logits predict the first post-injection token."""
        def step(one, t):
            logits, one = model.decode_step(
                params, jnp.reshape(t, (1, 1)), one)
            return one, logits
        one, logits = jax.lax.scan(step, one, tokens)
        return logits[-1, 0], one

    return {"admit": jax.jit(admit_fn), "block": jax.jit(block_fn),
            "prefill": jax.jit(prefill_fn),
            "scatter": jax.jit(scatter_fn),
            "snapshot": jax.jit(snapshot_fn,
                                static_argnames=("tail_block",)),
            "share_admit": jax.jit(share_admit_fn),
            "suspend": jax.jit(suspend_fn), "inject": jax.jit(inject_fn)}


class SuspendedRequest:
    """A live generation exported out of its slot at a tool/stop boundary
    (or a weight sync), waiting to be resumed.

    The handle is the mid-generation generalization of
    :class:`~repro.serve.disagg.KVTransferHandle`: paged engines pin the
    sequence's *full* KV blocks in the source pool (one ``incref`` each —
    zero copies) and carry a small device snapshot (dequantized partial
    tail block, slot-resident rows, the slot's last logits); contiguous
    engines carry the whole batch=1 cache stripe in ``one``.  The slot
    itself is released at suspension — capacity is immediately reusable.

    ``history`` is the full token sequence behind ``index`` (prompt +
    tokens generated so far): a resume re-admits through the same
    ``admit_prefilled`` adoption path disaggregated prefill uses, with
    ``history`` (+ tool tokens) as the synthetic prompt, so it works on
    monolithic and disagg engines alike and across engines of the same
    serving shape.

    ``logits`` is the boundary logits row and is only usable
    (``logits_valid``) when the stop token was the last token the fused
    decode block produced — a suspension truncated out of a ``block_size
    > 1`` overrun recomputes the boundary logits at resume (tool-token
    injection, or a one-token replay of the final history token).

    :meth:`release` drops the pins exactly once (idempotent), mirroring
    ``KVTransferHandle.release`` — a handle dropped mid-flight must
    restore the allocator's conservation invariant.
    """

    __slots__ = ("req", "out", "history", "index", "remaining", "logits",
                 "logits_valid", "block_ids", "tail", "slot_leaves", "one",
                 "source", "weight_version", "released")

    def __init__(self, req: Request, out: RequestOutput, history, index: int,
                 remaining: int, logits, *, source, logits_valid: bool = True,
                 block_ids=(), tail=None, slot_leaves=None, one=None,
                 weight_version: int = 0):
        self.req = req
        self.out = out
        self.history = np.asarray(history, np.int32).reshape(-1)
        self.index = int(index)
        self.remaining = int(remaining)
        self.logits = logits
        self.logits_valid = logits_valid
        self.block_ids = tuple(int(b) for b in block_ids)
        self.tail = tail if tail is not None else {}
        self.slot_leaves = slot_leaves if slot_leaves is not None else {}
        self.one = one                      # contiguous: full batch=1 cache
        self.source = source                # the Engine holding the pins
        self.weight_version = weight_version
        self.released = False

    def release(self) -> None:
        """Drop this handle's pins in the source pool (idempotent)."""
        if self.released:
            return
        self.released = True
        self.source._release_suspended(self)
        self.one = None
        self.tail = {}
        self.slot_leaves = {}
        self.logits = None


class Engine:
    """Continuous-batching generation engine over a fixed slot pool."""

    def __init__(self, model, params, config: EngineConfig,
                 rng: Optional[jax.Array] = None, policy=None):
        self.model = model
        self.params = params
        self.config = config
        self.queue = RequestQueue(config.max_waiting)
        # admission policy (serve.sched): a policy object wins over the
        # config's policy name (SLO policies carry per-group parameters)
        self.policy = policy if policy is not None else \
            make_policy(config.sched)
        self.paged = config.kv_layout == "paged"
        self.kernel_backend = self._resolve_backend(config.kernel_backend)
        self._build_fns()
        self.radix = (RadixPrefixIndex(self.slots.alloc)
                      if config.prefix_share else None)
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        N = config.num_slots
        self._last_logits = jnp.zeros((N, model.cfg.vocab_size), jnp.float32)
        self._alive = jnp.zeros((N,), bool)
        self._remaining = jnp.zeros((N,), jnp.int32)
        self._zero_keys = jnp.zeros((config.block_size, 2), jnp.uint32)
        self._host_index = [0] * N    # per-slot sequence position (host view)
        self._active: dict[int, tuple[Request, RequestOutput]] = {}
        self.finished: dict[int, RequestOutput] = {}
        self._unharvested: list[RequestOutput] = []
        # ---- suspend/resume + partial-rollout bookkeeping ----
        # weights swapped via reset(params=...) bump weight_version; each
        # slot remembers the version that produced its current last-logits
        # row, so per-token provenance is exact across carry_live resets
        self.weight_version = 0
        self._slot_version = [0] * N
        # carry-resumed outputs arrive pre-seeded with earlier tokens;
        # _seed_tokens[slot] marks how many, so sequence-position math
        # (index = prompt_len + generated-this-lifetime) stays right
        self._seed_tokens: dict[int, int] = {}
        self.suspended: dict[int, SuspendedRequest] = {}    # by rid
        self._newly_suspended: list[SuspendedRequest] = []
        # stop-token rollback is only safe when every non-index cache leaf
        # is sequence-shaped (attention masks positions >= index); recurrent
        # state (ssm/hybrid) cannot rewind, so those families must suspend
        # at block_size=1 (no overrun to truncate)
        paged_names = set(model.paged_cache_names())
        self._rollback_safe = all(
            k == "index" or k in paged_names
            for k in model.cache_logical_specs())
        self._stats = EngineStats()
        self.clock = None             # optional wall-clock for trace drivers

    def _resolve_backend(self, backend: str) -> str:
        """Effective decode backend for this model: recurrent families
        (rwkv6: no sequence-shaped KV for the kernel to touch) silently
        fall back from pallas to jnp; families the kernel path cannot
        serve faithfully (MLA/hybrid/audio) refuse loudly."""
        if backend != "pallas" or self.model.kernel_supported():
            return backend
        if self.model.cfg.family == "ssm":
            return "jnp"            # pure recurrent state: nothing to page
        raise ValueError(
            f"kernel_backend='pallas' does not support family "
            f"{self.model.cfg.family!r} / attention "
            f"{self.model.cfg.attention!r}")

    def _build_fns(self) -> None:
        """(Re)build the jitted fns + slot pool for the current config and
        effective backend (cached per shape, so flips are cheap)."""
        from repro.kernels.ops import resolve_interpret
        model, config = self.model, self.config
        # interpret mode resolved once per engine (at call time relative to
        # the lazy env/flag override) and baked into the jitted fns; kept
        # on the engine so a caller can check the kernels run compiled
        self.interpret = (resolve_interpret()
                          if self.kernel_backend == "pallas" else True)
        kv_dtype = None if config.kv_dtype == "auto" else config.kv_dtype
        if self.paged:
            if not hasattr(self, "slots"):
                self.slots = PagedSlotManager(
                    model, config.num_slots, config.max_seq_len,
                    block_size=config.kv_block_size,
                    num_blocks=config.num_kv_blocks,
                    kv_dtype=kv_dtype)
            self._fns = _paged_engine_fns(
                model, config.max_seq_len, config.kv_block_size,
                config.temperature, config.eos_id,
                kernel_backend=self.kernel_backend, kv_dtype=kv_dtype,
                interpret=self.interpret)
        else:
            if kv_dtype is not None:
                raise ValueError("kv_dtype requires kv_layout='paged'")
            if not hasattr(self, "slots"):
                self.slots = SlotManager(model, config.num_slots,
                                         config.max_seq_len)
            self._fns = _engine_fns(
                model, config.max_seq_len, config.temperature, config.eos_id,
                kernel_backend=self.kernel_backend,
                interpret=self.interpret)
        self._admit_fn, self._block = self._fns["admit"], self._fns["block"]

    def decode_hlo(self) -> str:
        """Compiled HLO text of the decode block at this engine's live
        shapes.  A ``tpu_custom_call`` in it means the Pallas kernels were
        compiled for the device rather than interpreted."""
        args = [self.params, self._last_logits, self.slots.cache]
        if self.paged:
            args.append(self.slots.device_tables())
        args += [self._alive, self._remaining, self._zero_keys]
        return self._block.lower(*args).compile().as_text()

    def set_kernel_backend(self, backend: str) -> None:
        """Switch the decode backend on a drained engine.

        The jitted decode block is rebuilt (cached per shape, so A/B flips
        re-use earlier compilations) and the admission policy is told via
        ``on_backend_change()``: a backend flip invalidates any learned
        per-token service-time estimate — the SLO policy re-arms its
        first-sample compile discard and falls back to its initial
        estimate rather than steering deadlines with the old backend's
        timings."""
        if backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown kernel_backend {backend!r}")
        if backend == self.config.kernel_backend:
            return
        if not self.idle:
            raise RuntimeError("set_kernel_backend() on a live engine; "
                               "drain or export_state() first")
        import dataclasses
        self.config = dataclasses.replace(self.config,
                                          kernel_backend=backend)
        self.kernel_backend = self._resolve_backend(backend)
        self._build_fns()
        self.policy.on_backend_change()

    # ---- submission --------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Enqueue a request.  Malformed requests (too big for the engine)
        raise; a full queue returns ``False`` — a backpressure signal the
        caller should honour by deferring and retrying after the engine
        drains (``run_trace`` and ``generate_continuous`` do)."""
        if req.total_budget > self.config.max_seq_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + budget "
                f"{req.max_new_tokens} exceeds max_seq_len "
                f"{self.config.max_seq_len}")
        if self.paged:
            need = self.slots.blocks_required(req.total_budget)
            if need > self.slots.alloc.num_blocks:
                raise ValueError(
                    f"request {req.rid}: needs {need} KV blocks but the "
                    f"pool has {self.slots.alloc.num_blocks}")
        self._validate_stop_tokens(req)
        return self.queue.push(req)

    def _validate_stop_tokens(self, req: Request) -> None:
        if not req.stop_tokens:
            return
        if self.config.eos_id in req.stop_tokens:
            raise ValueError(
                f"request {req.rid}: stop_tokens contain eos_id "
                f"{self.config.eos_id} — EOS finishes, it cannot suspend")
        if self.config.block_size > 1 and not self._rollback_safe:
            raise ValueError(
                f"request {req.rid}: stop-token suspension on family "
                f"{self.model.cfg.family!r} needs block_size=1 — its "
                f"recurrent cache state cannot be rolled back past a "
                f"mid-block stop boundary")

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def idle(self) -> bool:
        return not self.queue and not self._active

    # ---- telemetry ---------------------------------------------------------
    @property
    def stats(self) -> EngineStats:
        """Deprecated raw counter record — use :meth:`metrics` (the unified
        ``core.telemetry.MetricsSnapshot`` API).  Still served (warn-once)
        so pre-telemetry callers keep working."""
        warn_legacy_once(
            _warned_legacy,
            "Engine.stats is deprecated; read the unified telemetry via "
            "Engine.metrics() (core.telemetry.MetricsSnapshot)")
        return self._stats

    @stats.setter
    def stats(self, value: EngineStats) -> None:
        warn_legacy_once(
            _warned_legacy,
            "Engine.stats is deprecated; read the unified telemetry via "
            "Engine.metrics() (core.telemetry.MetricsSnapshot)")
        self._stats = value

    def metrics(self) -> MetricsSnapshot:
        """One merged :class:`~repro.core.telemetry.MetricsSnapshot` of this
        engine: queue/slot occupancy gauges, decode counters, KV block
        occupancy, prefix-sharing counters, suspend/resume traffic.  The
        elastic controller and the benchmarks consume only this."""
        s = self._stats
        snap = MetricsSnapshot(
            source="engine",
            queue_depth=len(self.queue),
            num_slots=self.config.num_slots,
            num_active=len(self._active),
            peak_active=s.peak_active,
            slot_steps=s.slot_steps,
            steps=s.steps,
            decode_time_s=s.decode_time_s,
            prefills=s.prefills,
            generated_tokens=s.recorded_tokens,
            peak_kv_blocks=s.peak_kv_blocks,
            prefix_hits=s.prefix_hits,
            prefix_partial_hits=s.prefix_partial_hits,
            blocks_saved=s.blocks_saved,
            suspends=s.suspends,
            resumes=s.resumes,
            suspended=len(self.suspended),
            weight_version=self.weight_version)
        if self.radix is not None:
            snap.snapshot_demotions = self.radix.stats["snapshot_demotions"]
        return snap

    # ---- scheduler ---------------------------------------------------------
    def _match(self, req: Request, *, count: bool = False):
        """Radix lookup for ``req`` (``None`` with sharing off or no match).

        Requests carrying frontend embeddings never share: the prompt
        tokens alone don't identify their KV (prefill conditions on the
        frontend), so a token-verified hit could still serve another
        request's image/audio-conditioned cache.  ``count=True`` marks
        the admission lookup — the radix index owns all hit/partial/miss
        counters and bumps exactly one per counted call."""
        if self.radix is None or req.frontend is not None:
            return None
        return self.radix.match(req, count=count)

    def _can_admit(self, req: Request) -> bool:
        """Admission gate the policy consults per candidate: a free slot,
        and (paged) enough uncommitted KV blocks for the candidate's
        worst-case budget **net of prefix-shared blocks**.  Under block
        pressure the radix index LRU-evicts unused entries (never the one
        this candidate would share from) before giving up."""
        if not self.paged:
            return bool(self.slots.num_free)
        if not self.slots.num_free:
            return False
        m = self._match(req)
        n_shared = m.n_shared if m is not None else 0
        if self.slots.can_admit(req.total_budget, shared_blocks=n_shared):
            return True
        if self.radix is not None and len(self.radix):
            need = max(self.slots.blocks_required(req.total_budget)
                       - n_shared, 0)
            if self.radix.evict_for(
                    need, protect=m.node_ids if m is not None else ()):
                return True
            # last resort: the path this request would share from is
            # itself pinning the pool — drop it too and admit unshared
            return self.radix.evict_for(
                self.slots.blocks_required(req.total_budget))
        return False

    def _admit(self) -> None:
        """Admit waiting requests into free slots, in the order the policy
        picks them (FIFO preserves strict arrival order; deadline/SLO may
        skip a blocked head — boundedly)."""
        live_tokens: dict[str, int] = {}
        for r, _ in self._active.values():
            if r.job_id is not None:
                live_tokens[r.job_id] = (live_tokens.get(r.job_id, 0)
                                         + r.max_new_tokens)
        now = self.clock() if self.clock is not None else 0.0
        while self.queue:
            idx = self.policy.pick(self.queue, self._can_admit, now=now,
                                   live_tokens=live_tokens)
            if idx is None:
                break
            req = self.queue.pop_at(idx)
            self._admit_one(req)
            if req.job_id is not None:
                live_tokens[req.job_id] = (live_tokens.get(req.job_id, 0)
                                           + req.max_new_tokens)
        self._stats.peak_active = max(self._stats.peak_active,
                                     len(self._active))
        if self.paged:
            self._stats.peak_kv_blocks = max(self._stats.peak_kv_blocks,
                                            self.slots.blocks_in_use)

    def _admit_one(self, req: Request) -> None:
        """Prefill (or share) one picked request into a free slot, under an
        ``engine.share`` span for an exact radix hit and ``engine.prefill``
        for every path that runs the model."""
        m = self._match(req, count=True) if self.paged else None
        exact = m is not None and m.exact
        with span("engine.share" if exact else "engine.prefill",
                  rid=req.rid):
            slot, shared_blocks = self._admit_slot(req, m)
        self._host_index[slot] = req.prompt_len
        self._slot_version[slot] = self.weight_version
        self._seed_tokens[slot] = 0
        out = RequestOutput(rid=req.rid, prompt=req.prompt,
                            prefill_step=self._stats.steps,
                            arrival_time=req.arrival_time,
                            priority=req.priority, deadline=req.deadline,
                            job_id=req.job_id,
                            prefix_shared_blocks=shared_blocks)
        self._active[slot] = (req, out)
        self._stats.prefills += 1
        self._stats.blocks_saved += shared_blocks

    def _admit_slot(self, req: Request, m) -> tuple[int, int]:
        """Place ``req`` (radix match ``m``) in a slot; returns the slot and
        the number of prefix-shared blocks it holds."""
        prompt_dev = jnp.asarray(req.prompt)[None]
        budget = jnp.asarray(req.max_new_tokens, jnp.int32)
        if not self.paged:
            slot = self.slots.assign(req.rid)
            (self.slots.cache, self._last_logits, self._alive,
             self._remaining) = self._admit_fn(
                self.params, prompt_dev, req.frontend,
                self.slots.cache, jnp.asarray(slot, jnp.int32),
                self._last_logits, self._alive, self._remaining, budget)
            return slot, 0
        if m is not None and m.exact:
            return self._admit_shared_exact(req, m, budget), m.n_shared
        if m is not None and m.n_shared > 0:
            return (self._admit_shared_prefix(req, m, prompt_dev, budget),
                    m.n_shared)
        slot = self.slots.assign(req.rid, prompt_len=req.prompt_len,
                                 total_budget=req.total_budget)
        row = self.slots.device_tables()[slot]
        if self.radix is not None and req.frontend is None:
            # donor path: split prefill + scatter so the radix path
            # (blocks + tail/slot-row snapshot) can register
            logits, one = self._fns["prefill"](
                self.params, prompt_dev, req.frontend)
            (self.slots.cache, self._last_logits, self._alive,
             self._remaining) = self._fns["scatter"](
                logits, one, self.slots.cache, row,
                jnp.asarray(slot, jnp.int32), self._last_logits,
                self._alive, self._remaining, budget)
            self._register_prefix(req, slot, logits, one)
        else:
            (self.slots.cache, self._last_logits, self._alive,
             self._remaining) = self._admit_fn(
                self.params, prompt_dev, req.frontend,
                self.slots.cache, row, jnp.asarray(slot, jnp.int32),
                self._last_logits, self._alive, self._remaining, budget)
        return slot, 0

    def _register_prefix(self, req: Request, slot: int, logits, one) -> None:
        """Record the donor's full prompt blocks + admit snapshot."""
        bs = self.config.kv_block_size
        n_full = req.prompt_len // bs
        if self.slots.paged_names:
            block_ids = [int(b) for b in self.slots.tables[slot, :n_full]]
        else:
            block_ids = []          # nothing paged (e.g. rwkv6): share the
            #                         snapshot (prefill-once), not blocks
        tail_block = n_full if req.prompt_len % bs else None
        tail, slot_leaves = self._fns["snapshot"](one, tail_block=tail_block)
        if not self.slots.paged_names:
            tail = {}
        self.radix.register(req, block_ids, logits=logits, tail=tail,
                            slot_leaves=slot_leaves)

    def _admit_shared_exact(self, req: Request, m, budget) -> int:
        """Radix exact hit: no model compute.  Pin the shared full blocks
        under this slot, materialize a private copy-on-write tail from the
        boundary snapshot, restore cached logits / slot-resident rows."""
        self.radix.touch(m)
        snap = m.snapshot
        slot = self.slots.assign_shared(
            req.rid, prompt_len=req.prompt_len,
            total_budget=req.total_budget,
            shared_ids=m.block_ids)
        tail_pid = (int(self.slots.tables[slot, m.n_shared])
                    if snap.tail else 0)
        (self.slots.cache, self._last_logits, self._alive,
         self._remaining) = self._fns["share_admit"](
            self.slots.cache, snap.tail, snap.slot_leaves, snap.logits,
            jnp.asarray(tail_pid, jnp.int32), jnp.asarray(slot, jnp.int32),
            self._last_logits, self._alive, self._remaining, budget,
            jnp.asarray(req.prompt_len, jnp.int32))
        self._stats.prefix_hits += 1
        return slot

    def _admit_shared_prefix(self, req: Request, m, prompt_dev,
                             budget) -> int:
        """Block-granular prefix hit (prompt extends / diverges from every
        registered path): prefill runs — compute is not shareable — but
        the matching full blocks are pinned instead of allocated, and the
        scatter goes through a write-masked row so shared blocks are never
        written.  The extension blocks then register in turn, so the tree
        deepens along whatever prefixes the workload actually repeats."""
        self.radix.touch(m)
        slot = self.slots.assign_shared(
            req.rid, prompt_len=req.prompt_len,
            total_budget=req.total_budget,
            shared_ids=m.block_ids)
        masked = self.slots.tables[slot].copy()
        masked[:m.n_shared] = 0             # shared blocks -> null (no write)
        logits, one = self._fns["prefill"](self.params, prompt_dev,
                                           req.frontend)
        (self.slots.cache, self._last_logits, self._alive,
         self._remaining) = self._fns["scatter"](
            logits, one, self.slots.cache, jnp.asarray(masked),
            jnp.asarray(slot, jnp.int32), self._last_logits, self._alive,
            self._remaining, budget)
        self._register_prefix(req, slot, logits, one)
        self._stats.prefix_partial_hits += 1
        return slot

    # ---- disaggregated-prefill adoption ------------------------------------
    def can_admit_prefilled(self, req: Request) -> bool:
        """Adoption gate for a KV transfer handle (``serve.disagg``): a free
        slot, and (paged) enough uncommitted blocks for the request's
        worst-case decode budget.  No radix *matching* — the handle's
        prompt KV arrives prefilled; sharing happened on the prefill
        side — though :meth:`admit_prefilled` does register the adopted
        prompt so later requests can share it."""
        if not self.slots.num_free:
            return False
        if not self.paged:
            return True
        if self.slots.can_admit(req.total_budget):
            return True
        if self.radix is not None and len(self.radix):
            return self.radix.evict_for(
                self.slots.blocks_required(req.total_budget))
        return False

    def admit_prefilled(self, req: Request, logits, one) -> int:
        """Adopt an externally prefilled request into a fresh slot.

        ``one`` is a batch=1 cache pytree holding exactly the prompt's
        prefill state (``index`` = prompt length) and ``logits`` the
        post-prompt logits — a ``prefill_fn`` result, whether produced
        in-process or materialized from a
        :class:`~repro.serve.disagg.KVTransferHandle`.  The splice is the
        same jitted ``scatter`` the monolithic admit path uses, so decode
        from an adopted slot is bit-identical to a monolithic admit.
        Returns the slot.  Callers must gate on
        :meth:`can_admit_prefilled` — like ``SlotManager.assign``, this
        raises rather than queues when the pool is full."""
        self._validate_stop_tokens(req)
        with span("engine.adopt", rid=req.rid):
            budget = jnp.asarray(req.max_new_tokens, jnp.int32)
            if not self.paged:
                slot = self.slots.assign(req.rid)
                (self.slots.cache, self._last_logits, self._alive,
                 self._remaining) = self._fns["scatter"](
                    logits, one, self.slots.cache,
                    jnp.asarray(slot, jnp.int32), self._last_logits,
                    self._alive, self._remaining, budget)
            else:
                slot = self.slots.assign(req.rid, prompt_len=req.prompt_len,
                                         total_budget=req.total_budget)
                row = self.slots.device_tables()[slot]
                (self.slots.cache, self._last_logits, self._alive,
                 self._remaining) = self._fns["scatter"](
                    logits, one, self.slots.cache, row,
                    jnp.asarray(slot, jnp.int32), self._last_logits,
                    self._alive, self._remaining, budget)
                if self.radix is not None and req.frontend is None:
                    # register the adopted prompt — for multi-turn resume()
                    # this is the episode's whole history, so sibling
                    # rollouts and turn k+1 match turn k's blocks
                    self._register_prefix(req, slot, logits, one)
                self._stats.peak_kv_blocks = max(self._stats.peak_kv_blocks,
                                                self.slots.blocks_in_use)
            self._host_index[slot] = req.prompt_len
            self._slot_version[slot] = self.weight_version
            self._seed_tokens[slot] = 0
            out = RequestOutput(rid=req.rid, prompt=req.prompt,
                                prefill_step=self._stats.steps,
                                arrival_time=req.arrival_time,
                                priority=req.priority, deadline=req.deadline,
                                job_id=req.job_id)
            self._active[slot] = (req, out)
            self._stats.prefills += 1
            self._stats.adoptions += 1
            self._stats.peak_active = max(self._stats.peak_active,
                                         len(self._active))
            return slot

    def _finalize(self, slot: int) -> None:
        req, out = self._active[slot]
        out.finish_reason = ("eos" if out.tokens and
                             out.tokens[-1] == self.config.eos_id else "length")
        out.finish_step = self._stats.steps
        if self.clock is not None:
            out.finish_time = self.clock()
        self.finished[req.rid] = out
        self._unharvested.append(out)
        del self._active[slot]
        self._seed_tokens.pop(slot, None)
        self.slots.release(slot)
        self.policy.observe_finish(out)     # fallback service-time estimate

    def harvest(self) -> list[RequestOutput]:
        """Pop the requests that finished since the last harvest, *without*
        draining the engine: queued and live requests keep decoding.  This
        is the partial-harvest contract the streaming mux uses to hand
        completed GRPO prompt groups to reward verification while the
        engine is still serving the stragglers.  Outputs also stay in
        :attr:`finished`, so batch drivers that collect everything at the
        end are unaffected."""
        out, self._unharvested = self._unharvested, []
        return out

    def step(self) -> int:
        """One scheduler iteration: admit waiting requests, then run
        ``block_size`` decode steps for all slots.  Returns the number of
        decode steps executed — ``0`` means *no work* (nothing admissible
        queued and no live slot), so drivers waiting on late submissions
        can sleep instead of spinning (see :func:`run_trace`).

        The tick is an ``engine.step`` span holding, in order,
        ``engine.admit``, ``engine.dispatch`` (the decode block's launch),
        ``engine.wait`` (the host's one wait on the device) and
        ``engine.record`` (the outputs into each request)."""
        with span("engine.step"):
            with span("engine.admit"):
                self._admit()
            if not self._active:
                if self.queue:
                    # nothing live, requests waiting, nothing admissible —
                    # and with an empty engine nothing will ever change
                    # that: the admission gate depends only on engine
                    # state.  A per-job token budget smaller than a single
                    # request's decode budget is the one way to get here;
                    # fail loud over spinning forever.
                    raise RuntimeError(
                        f"admission stalled: {len(self.queue)} waiting, 0 "
                        f"active — check policy token budgets / pool sizing")
                return 0
            K = self.config.block_size
            with span("engine.dispatch"):
                t_decode = time.perf_counter()
                out = self._dispatch(K)
            with span("engine.wait"):
                fetched = jax.device_get((*out, self._alive, self._remaining))
            t_decode = time.perf_counter() - t_decode
            with span("engine.record"):
                self._record(K, t_decode, *fetched)
            return K

    def _dispatch(self, K: int):
        """Launch the decode block of ``K`` steps over every slot; returns
        its per-step outputs, still on the device."""
        if self.config.temperature == 0:
            keys = self._zero_keys          # unused by greedy sampling
        else:
            self._rng, sub = jax.random.split(self._rng)
            keys = jax.random.split(sub, K)
        if self.paged:
            # materialize blocks this decode block will write into
            # (allocation stays within each request's admit-time reservation)
            for slot in self._active:
                self.slots.ensure(slot, self._host_index[slot] + K - 1)
            self._stats.peak_kv_blocks = max(self._stats.peak_kv_blocks,
                                            self.slots.blocks_in_use)
            (self._last_logits, self.slots.cache, self._alive,
             self._remaining), out = self._block(
                self.params, self._last_logits, self.slots.cache,
                self.slots.device_tables(), self._alive, self._remaining,
                keys)
        else:
            (self._last_logits, self.slots.cache, self._alive,
             self._remaining), out = self._block(
                self.params, self._last_logits, self.slots.cache,
                self._alive, self._remaining, keys)
        for slot in self._active:
            self._host_index[slot] += K
        return out

    def _record(self, K: int, t_decode: float, toks, logps, recs, alive,
                remaining) -> None:
        """Account the decode block that took ``t_decode`` seconds and
        append its tokens and log-probabilities to each live request,
        finalising or suspending those it ended."""
        self._stats.decode_time_s += t_decode
        # engine-measured service time straight into the admission policy:
        # K decode steps just took t_decode (every live slot advanced one
        # token per step), so SLO deadline estimates track the hardware
        # actually serving — no finish-time heuristics involved
        self.policy.observe_step(t_decode, K)
        self._stats.steps += K
        self._stats.blocks += 1
        self._stats.slot_steps += K * self.config.num_slots
        for slot in list(self._active):
            req, o = self._active[slot]
            rec_col = recs[:, slot]
            n_rec = int(rec_col.sum())
            stop_at = None                  # position of a stop trigger
            if n_rec:
                if not o.tokens and self.clock is not None:
                    o.first_token_time = self.clock()   # first token on host
                new_toks = [int(t) for t in toks[rec_col, slot]]
                if req.stop_tokens:
                    for j, t in enumerate(new_toks):
                        if t in req.stop_tokens:
                            stop_at = j
                            break
                # a stop trigger is recorded like EOS; anything the fused
                # block over-ran past it is truncated (the stale KV sits
                # beyond the rolled-back index, which attention masks)
                keep = n_rec if stop_at is None else stop_at + 1
                o.tokens.extend(new_toks[:keep])
                o.logprobs.extend(
                    float(x) for x in logps[rec_col, slot][:keep])
                # token 1 of the block was sampled from last_logits (the
                # slot's remembered version — stale across a carry resume);
                # later tokens from logits this block just produced
                o.token_versions.extend(
                    [self._slot_version[slot]]
                    + [self.weight_version] * (keep - 1))
                self._slot_version[slot] = self.weight_version
                self._stats.recorded_tokens += keep
            if stop_at is not None:
                # tool boundary before EOS/budget: suspend, free the slot.
                # Boundary logits are only live when the trigger was the
                # block's final step (no truncation).
                o.finish_reason = "stop"
                sreq = self._suspend_slot(
                    slot, logits_valid=(stop_at + 1 == K))
                self._newly_suspended.append(sreq)
            elif (not alive[slot]) or remaining[slot] <= 0:
                self._finalize(slot)

    def run(self, *, max_ticks: Optional[int] = None,
            should_yield=None) -> list[RequestOutput]:
        """Drive the engine until queue and slots are empty; outputs by rid.

        ``max_ticks`` bounds the number of scheduler iterations and
        ``should_yield()`` (checked between ticks) lets a driver preempt a
        live engine cooperatively — in both cases ``run`` returns with work
        possibly still in flight (``idle`` is False); call ``run`` again, or
        :meth:`export_state` to checkpoint the live slots, to continue.
        """
        ticks = 0
        while not self.idle:
            if max_ticks is not None and ticks >= max_ticks:
                break
            if should_yield is not None and ticks and should_yield():
                break
            self.step()
            ticks += 1
        return [self.finished[r] for r in sorted(self.finished)]

    # ---- suspend / resume --------------------------------------------------
    def harvest_suspended(self) -> list[SuspendedRequest]:
        """Pop the requests that hit a stop-token boundary since the last
        call — the agentic driver's pickup point (the partial-harvest twin
        of :meth:`harvest`).  Handles stay registered in :attr:`suspended`
        until resumed or released."""
        out, self._newly_suspended = self._newly_suspended, []
        return out

    def suspend(self, rid: int) -> SuspendedRequest:
        """Suspend a live request by rid (manual / carry-side suspension, at
        a fused-block boundary so the captured logits stay valid), freeing
        its slot.  Returns the pinned handle; also registered in
        :attr:`suspended` until resumed or released."""
        for slot, (req, _) in self._active.items():
            if req.rid == rid:
                return self._suspend_slot(slot)
        raise KeyError(f"rid {rid} is not live")

    def _suspend_slot(self, slot: int, *,
                      logits_valid: bool = True) -> SuspendedRequest:
        """Export slot ``slot``'s generation into a SuspendedRequest and
        release the slot.  Paged: pin the sequence's full blocks (zero
        copy), snapshot the partial tail + slot rows.  Contiguous: extract
        the batch=1 stripe."""
        req, out = self._active.pop(slot)
        seed = self._seed_tokens.pop(slot, 0)
        produced = len(out.tokens) - seed   # tokens this slot lifetime
        idx = req.prompt_len + produced     # rolled-back sequence position
        self._host_index[slot] = idx
        history = np.concatenate(
            [req.prompt, np.asarray(out.tokens[seed:], np.int32)])
        kwargs = dict(source=self, logits_valid=logits_valid,
                      weight_version=self._slot_version[slot])
        logits = self._last_logits[slot]
        if not self.paged:
            one = dict(self._fns["extract"](
                self.slots.cache, jnp.asarray(slot, jnp.int32)))
            one["index"] = jnp.asarray(idx, jnp.int32)
            sreq = SuspendedRequest(
                req, out, history, idx, req.max_new_tokens - produced,
                logits, one=one, **kwargs)
        else:
            bs = self.config.kv_block_size
            has_paged = bool(self.slots.paged_names)
            n_full = (idx // bs) if has_paged else 0
            pinned = self.slots.pin_prefix(slot, n_full)
            has_tail = has_paged and idx % bs != 0
            tail_pid = (int(self.slots.tables[slot, n_full])
                        if has_tail else 0)
            tail, slot_leaves = self._fns["suspend"](
                self.slots.cache, jnp.asarray(slot, jnp.int32),
                jnp.asarray(tail_pid, jnp.int32))
            if not has_tail:
                tail = {}
            sreq = SuspendedRequest(
                req, out, history, idx, req.max_new_tokens - produced,
                logits, block_ids=pinned, tail=tail,
                slot_leaves=dict(slot_leaves), **kwargs)
        self.slots.release(slot)
        self.suspended[req.rid] = sreq
        self._stats.suspends += 1
        return sreq

    def _materialize(self, sreq: SuspendedRequest) -> dict:
        """Batch=1 contiguous cache view of a handle suspended from *this*
        engine's pool — the resume-side twin of
        ``PrefillEngine.export_cache`` (same jitted fetch: gather the
        pinned blocks through a padded table row, splice the tail
        snapshot, dequantizing int8 on the way out)."""
        if sreq.released:
            raise RuntimeError(
                f"suspended rid {sreq.req.rid} was already released")
        if not self.paged:
            one = dict(sreq.one)
            one["index"] = jnp.asarray(sreq.index, jnp.int32)
            return one
        one = dict(sreq.slot_leaves)
        one["index"] = jnp.asarray(sreq.index, jnp.int32)
        if self.slots.paged_names:
            from repro.serve.disagg import _transfer_fns
            kv_dtype = (None if self.config.kv_dtype == "auto"
                        else self.config.kv_dtype)
            xfer = _transfer_fns(self.model, self.config.max_seq_len,
                                 self.config.kv_block_size,
                                 kv_dtype=kv_dtype)
            row = np.zeros((self.slots.max_blocks,), np.int32)
            row[:len(sreq.block_ids)] = sreq.block_ids
            src = {name: self.slots.cache[name]
                   for name in self.slots.paged_names}
            if kv_dtype == "int8":
                src.update({name: self.slots.cache[name]
                            for name in self.model.scale_cache_names()})
            one.update(xfer["fetch"](
                src, jnp.asarray(row), sreq.tail,
                jnp.asarray(len(sreq.block_ids), jnp.int32)))
        return one

    def _release_suspended(self, sreq: SuspendedRequest) -> None:
        if self.paged:
            for bid in sreq.block_ids:
                self.slots.alloc.decref(bid)
        if self.suspended.get(sreq.req.rid) is sreq:
            del self.suspended[sreq.req.rid]

    def can_resume(self, sreq: SuspendedRequest, tool_tokens=(), *,
                   max_new_tokens: Optional[int] = None) -> bool:
        """Re-admission gate for a suspended handle: a free slot and
        (paged) blocks for the continued sequence's worst-case budget —
        the same gate :meth:`can_admit_prefilled` applies to transfer
        handles."""
        if not self.slots.num_free:
            return False
        if not self.paged:
            return True
        budget = (max_new_tokens if max_new_tokens is not None
                  else max(sreq.remaining, 1))
        total = sreq.index + len(tool_tokens) + budget
        if self.slots.can_admit(total):
            return True
        if self.radix is not None and len(self.radix):
            return self.radix.evict_for(self.slots.blocks_required(total))
        return False

    def resume(self, sreq: SuspendedRequest, tool_tokens=(), *,
               max_new_tokens: Optional[int] = None,
               rid: Optional[int] = None,
               stop_tokens: Optional[tuple] = None,
               continue_output: bool = False) -> int:
        """Re-adopt a suspended generation into a fresh slot, optionally
        feeding ``tool_tokens`` (the environment's reply) through the
        model first so decoding continues past them.

        The adoption itself is :meth:`admit_prefilled` on a synthetic
        request whose prompt is the handle's token history (+ tool
        tokens) — the same path disaggregated prefill handles take, so a
        handle suspended on one engine resumes on any engine with the
        same serving shape (``sreq.source`` keeps the pins until the view
        is materialized here).  Greedy continuation is bit-identical to
        never having suspended on float pools: every array decode
        restarts from is moved by pure copies, and injection uses the
        model's own decode step.  int8 pools requantize to the same int8
        payload but the recomputed per-position scale can drift one float
        ulp (``(amax/127)*127``), so logprobs match to float tolerance —
        the same contract the disaggregated int8 transfer carries.

        ``continue_output=True`` (partial-rollout continuation) carries
        the suspended :class:`RequestOutput` forward — tokens, behaviour
        logprobs and per-token weight versions accumulate across the
        suspension instead of starting a fresh per-turn output.
        ``max_new_tokens`` grants a fresh per-turn budget (default: the
        handle's remaining budget) and ``stop_tokens`` replaces the
        request's boundary set (``()`` on the final turn lets the episode
        run to EOS instead of re-suspending; ``None`` inherits).  Returns
        the slot."""
        if sreq.released:
            raise RuntimeError(
                f"suspended rid {sreq.req.rid} was already released")
        tool = np.asarray(tool_tokens, np.int32).reshape(-1)
        if tool.size == 0 and not sreq.logits_valid \
                and not self._rollback_safe:
            raise RuntimeError(
                f"rid {sreq.req.rid} was truncated out of a fused decode "
                f"block and family {self.model.cfg.family!r} cannot replay "
                f"past recurrent state; resume with tool tokens")
        budget = (max_new_tokens if max_new_tokens is not None
                  else max(sreq.remaining, 1))
        prompt = (np.concatenate([sreq.history, tool])
                  if tool.size else sreq.history)
        src = sreq.req
        req = Request(rid=src.rid if rid is None else rid, prompt=prompt,
                      max_new_tokens=budget, arrival_time=src.arrival_time,
                      frontend=src.frontend, priority=src.priority,
                      deadline=src.deadline, job_id=src.job_id,
                      stop_tokens=(src.stop_tokens if stop_tokens is None
                                   else stop_tokens))
        if req.total_budget > self.config.max_seq_len:
            raise ValueError(
                f"resume of rid {req.rid}: history {sreq.index} + tool "
                f"{tool.size} + budget {budget} exceeds max_seq_len "
                f"{self.config.max_seq_len}")
        one = sreq.source._materialize(sreq)
        if tool.size:
            logits, one = self._fns["inject"](
                self.params, jnp.asarray(tool), one)
            version = self.weight_version
        elif not sreq.logits_valid:
            # the boundary logits were truncated out of a fused decode
            # block: replay the final history token one position back — a
            # pure KV overwrite on the materialized copy (attention masks
            # by index; recurrent families never get here, their stop
            # requests are gated to block_size=1)
            one["index"] = jnp.asarray(sreq.index - 1, jnp.int32)
            logits, one = self._fns["inject"](
                self.params, jnp.asarray(sreq.history[-1:]), one)
            version = self.weight_version
        else:
            # first resumed token samples from the captured boundary row —
            # across a carry_live weight sync that row is *stale*, which is
            # exactly the behaviour-provenance the version tracks
            logits = sreq.logits
            version = sreq.weight_version
        slot = self.admit_prefilled(req, logits, one)
        self._slot_version[slot] = version
        if continue_output:
            prev = sreq.out
            _, out = self._active[slot]
            out.prompt = prev.prompt
            out.tokens = list(prev.tokens)
            out.logprobs = list(prev.logprobs)
            out.token_versions = list(prev.token_versions)
            out.finish_reason = ""
            out.prefill_step = prev.prefill_step
            out.arrival_time = prev.arrival_time
            out.first_token_time = prev.first_token_time
            out.prefix_shared_blocks = prev.prefix_shared_blocks
            self._seed_tokens[slot] = len(out.tokens)
        sreq.release()
        self._stats.resumes += 1
        return slot

    def reset(self, params=None, rng: Optional[jax.Array] = None, *,
              carry_live: bool = False) -> None:
        """Prepare a drained engine for its next batch of requests: swap in
        freshly synced weights and a new key stream, and drop the previous
        batch's outputs.  This is how the mux trainer reuses one engine
        (and its jit cache) across GRPO iterations.

        ``carry_live=True`` is partial-rollout continuation: instead of
        requiring a drained engine, every live generation is suspended,
        the reset (weight swap, radix flush, policy reset) runs, and the
        suspended generations are resumed under the new weights with
        their outputs carried forward (mixed per-token weight versions —
        the clipped importance-ratio machinery sees the stale prefix).
        Queued-but-unadmitted requests simply stay queued; harvest
        completed outputs *before* the reset, they are dropped like any
        other reset."""
        carried: list[SuspendedRequest] = []
        if carry_live:
            for slot in sorted(self._active):
                carried.append(self._suspend_slot(slot))
        if self._active or (self.queue and not carry_live):
            raise RuntimeError("reset() on a live engine; drain or "
                               "export_state() first")
        if self.suspended and not carry_live:
            raise RuntimeError(
                f"reset() with {len(self.suspended)} suspended request(s) "
                f"still pinning the pool (rids "
                f"{sorted(self.suspended)!r}); resume or release them, or "
                f"reset(carry_live=True)")
        if params is not None:
            self.params = params
            self.weight_version += 1
        if rng is not None:
            self._rng = rng
        if self.radix is not None:
            # new weights invalidate every cached prefill (logits + KV)
            self.radix.flush()
        # the policy keeps its measured service-time state (the jit cache
        # is kept, so the compile-discard must NOT re-trigger) but drops
        # per-request bookkeeping: rids repeat across GRPO iterations, and
        # stale arrival seqs / skip counts would poison the next batch
        self.policy.on_reset()
        if self.paged:
            pins = [b for s in self.suspended.values() for b in s.block_ids]
            if pins:
                # suspended handles legitimately hold blocks: check exact
                # conservation against those pins instead of emptiness
                self.slots.check(extra_pins=pins)
            else:
                # an idle engine with a flushed radix must hold zero
                # blocks — any dangling refcount here is a leak that would
                # compound across iterations of a persistent engine
                self.slots.alloc.assert_clean(context="Engine.reset")
        self.finished.clear()
        self._unharvested.clear()
        for sreq in carried:
            self.resume(sreq, continue_output=True)

    def export_state(self) -> dict:
        """Checkpoint the live serving state mid-flight (drain of live
        slots): ``{"device": <array pytree>, "host": <bookkeeping>}``.

        The device part is a pure array pytree — exactly what a host-DRAM
        actor cache (``train.checkpoints.HostStateCache``) offloads when a
        co-executing job suspends between run permits.  The host part is a
        deep copy, so the snapshot stays valid however the engine runs on
        afterwards.  :meth:`import_state` on any engine with the same model
        and config resumes token-for-token.
        """
        device = {"cache": self.slots.cache,
                  "last_logits": self._last_logits,
                  "alive": self._alive,
                  "remaining": self._remaining,
                  "rng": self._rng}
        if self.suspended:
            # suspended handles split like radix entries: array pytrees in
            # the device section, metadata (deep-copied) in the host part;
            # the allocator pins they hold are already in the alloc state
            device["suspended"] = {
                rid: {"logits": s.logits, "one": s.one, "tail": s.tail,
                      "slot_leaves": s.slot_leaves}
                for rid, s in self.suspended.items()}
        slots: dict = {"owner": list(self.slots.owner),
                       "free": list(self.slots.free),
                       "events": list(self.slots.events)}
        if self.paged:
            a = self.slots.alloc
            slots.update(
                tables=self.slots.tables.copy(),
                nblocks=list(self.slots.nblocks),
                shared={s: list(v) for s, v in self.slots.shared.items()},
                alloc={"free": list(a.free),
                       "refcount": dict(a.refcount),
                       "quota": dict(a.quota),
                       "owned": {k: list(v) for k, v in a.owned.items()},
                       "events": list(a.events)})
        host = copy.deepcopy({
            "host_index": list(self._host_index),
            "active": dict(self._active),
            "queue": list(self.queue._q),
            "finished": dict(self.finished),
            "unharvested_rids": [o.rid for o in self._unharvested],
            "stats": self._stats,
            "slots": slots,
            "weight_version": self.weight_version,
            "slot_version": list(self._slot_version),
            "seed_tokens": dict(self._seed_tokens),
            "suspended": {
                rid: {"req": s.req, "out": s.out, "history": s.history,
                      "index": s.index, "remaining": s.remaining,
                      "block_ids": s.block_ids,
                      "weight_version": s.weight_version,
                      "logits_valid": s.logits_valid}
                for rid, s in self.suspended.items()},
            "newly_suspended": [s.req.rid for s in self._newly_suspended],
        })
        if self.radix is not None:
            # snapshot pytrees (logits/tail/slot rows) are device arrays:
            # they travel in the device section; the allocator pins the
            # tree nodes stand behind are already in the exported alloc
            # state, and the tree structure (parent links, tokens,
            # counters) is host data
            device["radix"] = self.radix.export_device_state()
            host["radix"] = self.radix.export_host_state()
        return {"device": device, "host": host}

    def import_state(self, state: dict) -> None:
        """Restore a :meth:`export_state` snapshot (device leaves may come
        back as host numpy arrays from an actor cache — they are re-put)."""
        dev = state["device"]
        self.slots.cache = jax.tree.map(jnp.asarray, dev["cache"])
        self._last_logits = jnp.asarray(dev["last_logits"])
        self._alive = jnp.asarray(dev["alive"])
        self._remaining = jnp.asarray(dev["remaining"])
        self._rng = jnp.asarray(dev["rng"])
        host = copy.deepcopy(state["host"])
        self._host_index = list(host["host_index"])
        self._active = dict(host["active"])
        self.queue._q.clear()
        self.queue._q.extend(host["queue"])
        self.finished = dict(host["finished"])
        self._unharvested = [self.finished[r]
                             for r in host.get("unharvested_rids", ())
                             if r in self.finished]
        self._stats = host["stats"]
        self.weight_version = host.get("weight_version", 0)
        self._slot_version = list(host.get(
            "slot_version", [0] * self.config.num_slots))
        self._seed_tokens = {int(k): int(v)
                             for k, v in host.get("seed_tokens", {}).items()}
        dev_susp = dev.get("suspended", {})
        self.suspended = {}
        for rid, m in host.get("suspended", {}).items():
            d = dev_susp[rid]
            self.suspended[int(rid)] = SuspendedRequest(
                m["req"], m["out"], m["history"], m["index"],
                m["remaining"], jnp.asarray(d["logits"]), source=self,
                logits_valid=m["logits_valid"], block_ids=m["block_ids"],
                tail=jax.tree.map(jnp.asarray, d["tail"]),
                slot_leaves=jax.tree.map(jnp.asarray, d["slot_leaves"]),
                one=(None if d["one"] is None
                     else jax.tree.map(jnp.asarray, d["one"])),
                weight_version=m["weight_version"])
        self._newly_suspended = [
            self.suspended[r] for r in host.get("newly_suspended", ())
            if r in self.suspended]
        sl = host["slots"]
        self.slots.owner = list(sl["owner"])
        self.slots.free = list(sl["free"])
        self.slots.events = list(sl["events"])
        if self.paged:
            self.slots.tables = sl["tables"].copy()
            self.slots.nblocks = list(sl["nblocks"])
            self.slots.shared = {int(s): list(v)
                                 for s, v in sl.get("shared", {}).items()}
            self.slots._dirty = True
            a = self.slots.alloc
            a.free = list(sl["alloc"]["free"])
            a.refcount = dict(sl["alloc"]["refcount"])
            a.quota = dict(sl["alloc"]["quota"])
            a.owned = {k: list(v) for k, v in sl["alloc"]["owned"].items()}
            a.events = list(sl["alloc"]["events"])
        if self.radix is not None:
            self.radix.import_state(
                host.get("radix"),
                jax.tree.map(jnp.asarray, state["device"].get("radix", {})))


def run_trace(engine: Engine, requests: list[Request],
              *, realtime: bool = True, controller=None) -> dict:
    """Replay a timed arrival trace through ``engine`` against the wall
    clock: each request is submitted once ``arrival_time`` (seconds from
    trace start) has elapsed, and per-request first-token / finish
    timestamps are recorded.  ``realtime=False`` fast-forwards idle gaps
    instead of sleeping through them: when the engine runs dry the next
    pending request is submitted immediately and its ``arrival_time`` is
    rebased to the current clock so latency/TTFT stay well-defined.
    Returns a report with latency, throughput and slot-utilization
    aggregates (the benchmark's raw material).

    ``controller`` (a ``serve.elastic.ElasticController``) closes the
    capacity loop: every arrival passes through its admission gate (which
    may shed it or clamp its decode budget), and between steps the
    controller may replace the engine with a resized one (live work
    carried over).  The returned report then carries an ``"elastic"``
    section — capacity-seconds, sheds/degrades, resize history."""
    pending = sorted(requests, key=lambda r: (r.arrival_time, r.rid))
    t0 = time.perf_counter()
    engine.clock = lambda: time.perf_counter() - t0
    if controller is not None:
        controller.attach(engine, engine.clock())
    while pending or not engine.idle:
        now = engine.clock()
        while pending and pending[0].arrival_time <= now:
            req = pending[0]
            if controller is not None:
                verdict, req = controller.admit(req, now, engine)
                if verdict == "shed":
                    pending.pop(0)          # recorded by the controller —
                    continue                # shed, never silently dropped
            if not engine.submit(req):
                break                       # queue full: defer, retry after
            pending.pop(0)                  # the engine drains a bit
        if controller is not None:
            engine = controller.maybe_resize(engine, engine.clock())
        progressed = engine.step()
        if not progressed and pending:
            if realtime:
                # engine reported "no work": sleep the idle gap away in one
                # go — the next event is the head arrival, nothing else can
                # wake a single-threaded trace replay (no busy spin)
                wait = pending[0].arrival_time - engine.clock()
                if wait > 0:
                    time.sleep(wait)
            else:
                nxt = pending[0]
                nxt.arrival_time = engine.clock()
                if controller is not None:
                    verdict, nxt = controller.admit(nxt, engine.clock(),
                                                    engine)
                    if verdict == "shed":
                        pending.pop(0)
                        continue
                if engine.submit(nxt):
                    pending.pop(0)
    makespan = engine.clock()
    engine.clock = None
    outs = [engine.finished[r] for r in sorted(engine.finished)]
    lat = np.array([o.finish_time - o.arrival_time for o in outs])
    ttft = np.array([o.first_token_time - o.arrival_time for o in outs])
    n_tok = sum(o.num_tokens for o in outs)
    report = {
        "outputs": outs,
        "makespan_s": makespan,
        "tokens": n_tok,
        "tok_per_s": n_tok / max(makespan, 1e-9),
        "latency_mean_s": float(lat.mean()) if len(lat) else 0.0,
        "latency_p95_s": float(np.quantile(lat, 0.95)) if len(lat) else 0.0,
        "ttft_mean_s": float(ttft.mean()) if len(ttft) else 0.0,
        "slot_utilization": engine._stats.slot_utilization,
        "peak_active": engine._stats.peak_active,
        "rejected_submits": engine.queue.rejected,
    }
    with_dl = [o for o in outs if o.deadline is not None]
    if with_dl:
        met = sum(o.finish_time <= o.deadline for o in with_dl)
        report["deadline_total"] = len(with_dl)
        report["deadline_met"] = int(met)
        report["deadline_attainment"] = met / len(with_dl)
    if engine.paged:
        total = engine.slots.alloc.num_blocks
        report["kv_blocks_total"] = total
        report["peak_kv_blocks"] = engine._stats.peak_kv_blocks
        report["kv_block_utilization"] = (
            engine._stats.peak_kv_blocks / max(total, 1))
    if engine.radix is not None:
        report["prefix"] = dict(engine.radix.stats,
                                blocks_saved=engine._stats.blocks_saved,
                                hit_admits=engine._stats.prefix_hits)
    if controller is not None:
        report["elastic"] = controller.summary(makespan)
    return report
