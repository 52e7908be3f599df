"""Prefill/decode disaggregation router.

:class:`DisaggRouter` composes a :class:`~repro.serve.disagg.PrefillEngine`
and a decode :class:`~repro.serve.engine.Engine` over *independently sized*
slot + block pools (:class:`DisaggConfig`) and moves
:class:`~repro.serve.disagg.KVTransferHandle`\\ s between them:

::

    submit ──> [queue] ──policy──> PrefillEngine ──handle──> (transfer
               ^                    pool P slots,             queue)
               |                    P blocks                     |
               └── backpressure ◄── pinned blocks                v
                                                   Engine.admit_prefilled
                                                    decode pool D slots,
                                                    D blocks ──> finished

Each scheduler tick runs prefill admission (the configured policy picks
from the shared waiting queue), adopts as many ready handles as the
decode pool can admit (FIFO in completion order — admission order never
changes *what* a request decodes, only when, so output stays bit-exact
for every policy), then one decode tick.  Un-adopted handles pin prefill
blocks, which throttles prefill admission when decode falls behind — the
two pool sizes are the only knobs, exactly the heterogeneous-pool shape
the paper gives rollout vs training.

The router duck-types the ``Engine`` surface that ``run_trace``,
``generate_continuous`` and the streaming executor drive (``submit`` /
``step`` / ``idle`` / ``harvest`` / ``finished`` / ``stats`` / ``reset``),
so every existing driver works unchanged with ``disagg=...``.

The KV transfer is **planner-visible**: pass a
:class:`~repro.core.phase_control.RollMuxRuntime` and each adoption runs
under a ``runtime.permit("transfer", "<job>:transfer")`` scope, so the
co-execution DES sees transfer occupancy as a phase timeline alongside
rollout/train/reward (``phase_profiles(transfer_pool="transfer")`` folds
it into the job's rollout-side critical path).
"""
from __future__ import annotations

import contextlib
import copy
import time
from collections import deque
from dataclasses import dataclass
from typing import Optional

from repro.core.telemetry import MetricsSnapshot, warn_legacy_once
from repro.data import tokenizer as tok
from repro.serve.disagg import KVTransferHandle, PrefillEngine
from repro.serve.engine import Engine, EngineConfig
from repro.serve.sched import make_policy

# DisaggRouter.stats legacy-shim warn-once flag (mutable so tests reset it)
_warned_legacy = [False]


@dataclass(frozen=True)
class DisaggConfig:
    """Two-pool serving shape: one ``max_seq_len``/layout/sampler contract,
    independently sized prefill and decode pools.

    ``prefill_slots`` bounds prefills per tick (paged) or resident
    un-adopted handles (contiguous); ``prefill_kv_blocks`` sizes the pool
    those handles pin.  ``decode_slots``/``decode_kv_blocks`` size the
    decode engine exactly like a monolithic ``EngineConfig`` would.  The
    admission policy (``sched``) runs on the prefill side — that is where
    requests wait; ``prefix_share`` builds the content-addressed radix
    tree over each prefill pool (exact repeats become zero-compute
    handles, block-aligned prefix overlaps prefill only their
    extension).

    ``prefill_engines`` scales the prefill side out: each engine gets its
    own full-size slot/block pools *and its own radix tree*, and the
    router steers each request with ``kv_routing`` — ``"kv_aware"``
    (default) scores every engine by how many prompt blocks its tree
    already holds and sends the request to the longest prefix
    (production-stack's ``kvaware_routing``), falling back to the least
    loaded; ``"queue"`` ignores KV residency and balances purely on
    queue depth + resident handles."""
    prefill_slots: int = 2
    decode_slots: int = 8
    max_seq_len: int = 256
    eos_id: int = tok.EOS
    temperature: float = 0.0
    block_size: int = 1
    max_waiting: Optional[int] = None
    kv_layout: str = "contiguous"
    kv_block_size: int = 16
    prefill_kv_blocks: Optional[int] = None
    decode_kv_blocks: Optional[int] = None
    sched: str = "fifo"
    prefix_share: bool = False
    prefill_engines: int = 1        # parallel prefill pools (each full-size)
    kv_routing: str = "kv_aware"    # "kv_aware" | "queue" steering between
    #                                 prefill engines (moot with one engine)
    kernel_backend: str = "jnp"     # decode-step backend for BOTH pools
    kv_dtype: Optional[str] = None  # paged KV storage dtype for BOTH pools
    #                                 (the handle interchange stays float)

    def prefill_config(self) -> EngineConfig:
        return EngineConfig(
            num_slots=self.prefill_slots, max_seq_len=self.max_seq_len,
            eos_id=self.eos_id, temperature=self.temperature,
            block_size=self.block_size, max_waiting=self.max_waiting,
            kv_layout=self.kv_layout, kv_block_size=self.kv_block_size,
            num_kv_blocks=self.prefill_kv_blocks, sched=self.sched,
            prefix_share=self.prefix_share,
            kernel_backend=self.kernel_backend, kv_dtype=self.kv_dtype)

    def decode_config(self) -> EngineConfig:
        # the decode engine is fed adopted handles, never a policy-ordered
        # queue, and adopted prompts bypass prefix lookup by construction
        return EngineConfig(
            num_slots=self.decode_slots, max_seq_len=self.max_seq_len,
            eos_id=self.eos_id, temperature=self.temperature,
            block_size=self.block_size, kv_layout=self.kv_layout,
            kv_block_size=self.kv_block_size,
            num_kv_blocks=self.decode_kv_blocks, sched="fifo",
            prefix_share=False,
            kernel_backend=self.kernel_backend, kv_dtype=self.kv_dtype)


class RouterStats:
    """Transfer counters + delegation to the two engines' stats, presenting
    the single-engine surface trace drivers read."""

    def __init__(self, router: "DisaggRouter"):
        self._router = router
        self.transfers = 0
        self.transfer_time_s = 0.0
        self.transferred_blocks = 0
        self.kv_routed = 0          # requests steered to a non-empty prefix

    @property
    def transfer_overhead_frac(self) -> float:
        """Transfer wall time as a fraction of transfer + decode time —
        guarded against the zero-decode-steps trace (nothing served)."""
        busy = self.transfer_time_s + self._router.decode._stats.decode_time_s
        if busy <= 0.0:
            return 0.0
        return self.transfer_time_s / busy

    # -- decode-side delegation (what run_trace reads) ----------------------
    @property
    def steps(self):
        return self._router.decode._stats.steps

    @property
    def decode_time_s(self):
        return self._router.decode._stats.decode_time_s

    @property
    def time_per_token(self):
        return self._router.decode._stats.time_per_token

    @property
    def slot_utilization(self):
        return self._router.decode._stats.slot_utilization

    @property
    def peak_active(self):
        return self._router.decode._stats.peak_active

    @property
    def peak_kv_blocks(self):
        return self._router.decode._stats.peak_kv_blocks

    @property
    def recorded_tokens(self):
        return self._router.decode._stats.recorded_tokens

    # -- prefill-side delegation (summed across prefill engines) ------------
    @property
    def prefills(self):
        return sum(pe.stats.prefills for pe in self._router.prefills)

    @property
    def prefix_hits(self):
        return sum(pe.stats.prefix_hits for pe in self._router.prefills)

    @property
    def prefix_partial_hits(self):
        return sum(pe.stats.prefix_partial_hits
                   for pe in self._router.prefills)

    @property
    def blocks_saved(self):
        return sum(pe.stats.blocks_saved for pe in self._router.prefills)


class DisaggRouter:
    """Drive one request stream through disaggregated prefill/decode pools.

    ``runtime``/``job_id`` make each KV transfer a planner-visible phase
    (see module docstring); both default to the in-process fast path with
    a local :attr:`transfer_timeline` either way.
    """

    def __init__(self, model, params, config: DisaggConfig, rng=None,
                 policy=None, runtime=None, job_id: Optional[str] = None):
        self.model = model
        self.config = config
        if config.prefill_engines < 1:
            raise ValueError(
                f"prefill_engines must be >= 1, got {config.prefill_engines}")
        if config.kv_routing not in ("kv_aware", "queue"):
            raise ValueError(
                f"kv_routing must be 'kv_aware' or 'queue', "
                f"got {config.kv_routing!r}")
        # ONE policy object drives every prefill queue: per-job token
        # budgets and the SLO service-time estimate are router-global, and
        # the deadline policies prune per-queue (keyed on queue identity)
        # so multi-queue sharing is safe.  A caller-supplied policy is
        # shared the same way.
        shared_policy = policy if policy is not None \
            else make_policy(config.sched)
        self.prefills = [
            PrefillEngine(model, params, config.prefill_config(),
                          policy=shared_policy)
            for _ in range(config.prefill_engines)]
        self.decode = Engine(model, params, config.decode_config(), rng=rng)
        self.pending_transfer: deque[KVTransferHandle] = deque()
        self.runtime = runtime
        self.job_id = job_id
        self._stats = RouterStats(self)
        self.transfer_timeline: list[tuple[str, float, float]] = []
        self._clock = None

    # ---- telemetry ---------------------------------------------------------
    @property
    def stats(self) -> RouterStats:
        """Deprecated stats facade — use :meth:`metrics` (the unified
        ``core.telemetry.MetricsSnapshot`` API).  Warn-once shim."""
        warn_legacy_once(
            _warned_legacy,
            "DisaggRouter.stats is deprecated; read the unified telemetry "
            "via DisaggRouter.metrics() (core.telemetry.MetricsSnapshot)")
        return self._stats

    def metrics(self) -> MetricsSnapshot:
        """One merged :class:`~repro.core.telemetry.MetricsSnapshot` across
        both planes: the decode engine's snapshot, prefill-side counters
        summed over all prefill engines, and the router's own transfer
        counters."""
        snap = self.decode.metrics()
        snap.source = "router"
        for pe in self.prefills:
            s = pe.stats                    # PrefillEngine: plain record
            snap.prefills += s.prefills
            snap.prefix_hits += s.prefix_hits
            snap.prefix_partial_hits += s.prefix_partial_hits
            snap.blocks_saved += s.blocks_saved
            snap.queue_depth += len(pe.queue)
            if pe.radix is not None:
                snap.snapshot_demotions += \
                    pe.radix.stats["snapshot_demotions"]
        snap.transfers = self._stats.transfers
        snap.transfer_time_s = self._stats.transfer_time_s
        snap.transferred_blocks = self._stats.transferred_blocks
        snap.kv_routed = self._stats.kv_routed
        return snap

    # ---- Engine surface ----------------------------------------------------
    @property
    def prefill(self):
        """First prefill engine — the single-engine surface existing
        callers (and single-engine configs) read."""
        return self.prefills[0]

    @property
    def clock(self):
        return self._clock

    @clock.setter
    def clock(self, fn):
        self._clock = fn
        for pe in self.prefills:
            pe.clock = fn
        self.decode.clock = fn

    @property
    def params(self):
        return self.decode.params

    @property
    def paged(self) -> bool:
        return self.decode.paged

    @property
    def slots(self):
        return self.decode.slots

    @property
    def radix(self):
        return self.prefill.radix

    @property
    def queue(self):
        return self.prefill.queue

    @property
    def finished(self):
        return self.decode.finished

    @property
    def num_active(self) -> int:
        return self.decode.num_active + len(self.pending_transfer)

    @property
    def idle(self) -> bool:
        return (not any(pe.queue for pe in self.prefills)
                and not self.pending_transfer and self.decode.idle)

    def harvest(self):
        return self.decode.harvest()

    def submit(self, req) -> bool:
        """Validate against *both* pools, then enqueue on the prefill side.
        A request too big for either pool can never be served and raises;
        a full queue returns ``False`` (backpressure)."""
        if req.total_budget > self.config.max_seq_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + budget "
                f"{req.max_new_tokens} exceeds max_seq_len "
                f"{self.config.max_seq_len}")
        if self.decode.paged:
            need = self.decode.slots.blocks_required(req.total_budget)
            if need > self.decode.slots.alloc.num_blocks:
                raise ValueError(
                    f"request {req.rid}: needs {need} KV blocks but the "
                    f"decode pool has {self.decode.slots.alloc.num_blocks}")
        self.decode._validate_stop_tokens(req)
        for pe in self._route(req):
            if pe.submit(req):
                return True
        return False

    def _route(self, req) -> list:
        """Order the prefill engines for ``req``: with ``kv_aware``
        routing, by longest registered prefix first (each engine's radix
        tree probed with a countless ``match`` — admission counters stay
        untouched), ties broken by load (queue depth + resident handles);
        with ``"queue"`` routing, by load alone.  The request falls
        through to later engines on queue backpressure."""
        if len(self.prefills) == 1:
            return [self.prefills[0]]
        scored = []
        for i, pe in enumerate(self.prefills):
            score = 0
            if (self.config.kv_routing == "kv_aware"
                    and pe.radix is not None and req.frontend is None):
                m = pe.radix.match(req)
                if m is not None:
                    score = m.n_shared + (1 if m.exact else 0)
            load = len(pe.queue) + pe.resident
            scored.append((-score, load, i, pe))
        scored.sort(key=lambda s: s[:3])
        if -scored[0][0] > 0:
            self._stats.kv_routed += 1
        return [s[3] for s in scored]

    # ---- scheduler ---------------------------------------------------------
    def step(self) -> int:
        """One router tick: prefill admission, handle adoption, decode.
        Returns decode steps executed, or 1 when only prefill/transfer
        progressed — ``0`` keeps the ``Engine.step`` "no work" contract
        trace drivers sleep on."""
        prefilled = 0
        for pe in self.prefills:
            prefilled += pe.step()
            self.pending_transfer.extend(pe.pop_ready())
        moved = 0
        while (self.pending_transfer
               and self.decode.can_admit_prefilled(
                   self.pending_transfer[0].req)):
            self._transfer(self.pending_transfer.popleft())
            moved += 1
        k = self.decode.step()
        if not (prefilled or moved or k):
            if self.pending_transfer and self.decode.idle:
                h = self.pending_transfer[0]
                raise RuntimeError(
                    f"transfer stalled: handle for rid {h.req.rid} "
                    f"(budget {h.req.total_budget}) does not fit the idle "
                    f"decode pool — check decode slot/block sizing")
            waiting = sum(len(pe.queue) for pe in self.prefills)
            if waiting and self.decode.idle:
                raise RuntimeError(
                    f"admission stalled: {waiting} waiting, "
                    f"0 active — check prefill pool sizing")
            return 0
        return k if k else 1

    def _transfer(self, handle: KVTransferHandle) -> None:
        who = f"{self.job_id or handle.req.job_id or 'serve'}:transfer"
        ctx = (self.runtime.permit("transfer", who)
               if self.runtime is not None else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ctx:
            # export from the engine that prefilled it — with several
            # prefill pools the handle's blocks live in its source pool
            one = handle.source.export_cache(handle)
            self.decode.admit_prefilled(handle.req, handle.logits, one)
        n_blocks = len(handle.block_ids)
        handle.release()
        dt = time.perf_counter() - t0
        now = self._clock() if self._clock is not None else t0 + dt
        self.transfer_timeline.append((who, now - dt, now))
        self._stats.transfers += 1
        self._stats.transfer_time_s += dt
        self._stats.transferred_blocks += n_blocks

    def run(self, *, max_ticks: Optional[int] = None):
        """Drive until queue, transfer queue and decode pool are empty."""
        ticks = 0
        while not self.idle:
            if max_ticks is not None and ticks >= max_ticks:
                break
            self.step()
            ticks += 1
        return [self.finished[r] for r in sorted(self.finished)]

    # ---- suspend / resume --------------------------------------------------
    @property
    def weight_version(self) -> int:
        return self.decode.weight_version

    @property
    def suspended(self):
        return self.decode.suspended

    def harvest_suspended(self):
        return self.decode.harvest_suspended()

    def suspend(self, rid: int):
        """Suspend an *actively decoding* request.  Requests still waiting
        or mid-transfer have no KV worth keeping — drop and resubmit
        those instead."""
        return self.decode.suspend(rid)

    def can_resume(self, sreq, tool_tokens=(), *,
                   max_new_tokens: Optional[int] = None) -> bool:
        return self.decode.can_resume(sreq, tool_tokens,
                                      max_new_tokens=max_new_tokens)

    def resume(self, sreq, tool_tokens=(), *,
               max_new_tokens: Optional[int] = None,
               rid: Optional[int] = None,
               stop_tokens: Optional[tuple] = None,
               continue_output: bool = False) -> int:
        """Resume a suspended request straight into the decode pool —
        suspended KV already lives (or is re-materialized) decode-side,
        so resumption bypasses the prefill engine entirely."""
        return self.decode.resume(
            sreq, tool_tokens, max_new_tokens=max_new_tokens, rid=rid,
            stop_tokens=stop_tokens, continue_output=continue_output)

    def can_admit_prefilled(self, req) -> bool:
        return self.decode.can_admit_prefilled(req)

    def admit_prefilled(self, req, logits, one) -> int:
        return self.decode.admit_prefilled(req, logits, one)

    # ---- checkpoint --------------------------------------------------------
    def export_state(self) -> dict:
        """Checkpoint the full router: the decode engine's device/host
        snapshot plus the prefill-side waiting set.  Prefilled-but-unadopted
        handles fold back into plain waiting requests (re-queued at the
        front, their pins released) — re-prefilling them under the same
        weights is bit-identical, so the snapshot stays exact without
        serializing the prefill pool."""
        for pe in self.prefills:
            self.pending_transfer.extend(pe.pop_ready())
        requeue = [h.req for h in self.pending_transfer]
        self.drop_pending()
        for req in reversed(requeue):
            self.prefill.queue._q.appendleft(req)
        state = self.decode.export_state()
        # the snapshot flattens every engine's waiting set into one list;
        # import re-routes each request through _route (kv_aware when
        # enabled), so the restored load spreads across all prefill
        # engines instead of concentrating in engine 0
        state["prefill_queue"] = copy.deepcopy(
            [r for pe in self.prefills for r in pe.queue._q])
        return state

    def import_state(self, state: dict) -> None:
        state = dict(state)
        waiting = state.pop("prefill_queue", [])
        for pe in self.prefills:
            self.pending_transfer.extend(pe.pop_ready())
        self.drop_pending()
        for pe in self.prefills:
            if pe.radix is not None:
                pe.radix.flush()
            if pe.paged:
                pe.slots.alloc.assert_clean(
                    context="DisaggRouter.import_state")
            pe.queue._q.clear()
        self._requeue(copy.deepcopy(waiting))
        self.decode.import_state(state)

    def _requeue(self, reqs) -> None:
        """Spread restored / carried waiting requests back over the prefill
        engines through the same :meth:`_route` scoring live submissions
        use (kv_aware when enabled; right after a flush every score is 0,
        so this degenerates to load balancing).  Restored requests are
        never dropped: when every queue refuses (backpressure), the
        best-ranked engine takes it on its raw deque."""
        for req in reqs:
            order = self._route(req)
            for pe in order:
                if pe.queue.push(req):
                    break
            else:
                order[0].queue._q.append(req)

    def drop_pending(self) -> int:
        """Release every handle still waiting for adoption (mid-flight
        drop).  The block conservation invariant must hold again
        afterwards — ``reset`` asserts it."""
        n = len(self.pending_transfer)
        while self.pending_transfer:
            self.pending_transfer.popleft().release()
        return n

    def reset(self, params=None, rng=None, *, carry_live=False) -> None:
        """Prepare both engines for the next batch (persistent-router reuse
        across GRPO iterations).  In-flight transfer handles are dropped —
        their pins released — and both pools are asserted leak-free.

        ``carry_live=True`` is the partial-rollout weight sync: live decode
        generations are suspended and resumed under the new weights by the
        decode engine itself (their outputs keep accumulating, with
        ``token_versions`` recording the switch), the waiting queue is held
        across the prefill reset, and prefilled-but-unadopted handles fall
        back to plain waiting requests — their KV is stale the moment the
        weights swap, so re-prefilling under the new weights is the correct
        (and cheapest-to-keep-exact) continuation."""
        if not carry_live:
            if any(pe.queue for pe in self.prefills) or not self.decode.idle:
                raise RuntimeError("reset() on a live router; drain first")
            for pe in self.prefills:
                self.pending_transfer.extend(pe.pop_ready())
            self.drop_pending()
            for pe in self.prefills:
                pe.reset(params)
            self.decode.reset(params, rng)
            return
        for pe in self.prefills:
            self.pending_transfer.extend(pe.pop_ready())
        requeue = [h.req for h in self.pending_transfer]
        self.drop_pending()
        held = [r for pe in self.prefills for r in pe.queue._q]
        for pe in self.prefills:
            pe.queue._q.clear()
            pe.reset(params)
        self.decode.reset(params, rng, carry_live=True)
        self._requeue(requeue + held)
