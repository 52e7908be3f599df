"""Unified telemetry: one typed, mergeable snapshot of every metrics
surface in the stack.

Before this module the observability story was ad hoc: ``Engine`` exposed
an ``EngineStats`` record, ``DisaggRouter`` a stats facade that summed and
delegated, the radix tree its own hit/miss counter dict, and the runtime
per-phase ``PhaseStats``.  Consumers (benchmarks, the launchers, and now
the elastic controller) had to know which shape they were holding.

:class:`MetricsSnapshot` is the one shape.  ``Engine.metrics()``,
``DisaggRouter.metrics()`` and ``RollMuxRuntime.metrics()`` all return it;
snapshots from different components merge (:meth:`MetricsSnapshot.merge`)
by the obvious per-field rule — counters sum, peaks max, gauges from the
later/other snapshot win, dict-valued fields union.  The elastic
controller (``serve.elastic``) and the benchmarks consume *only* this API;
legacy attribute access (``engine.stats`` / ``router.stats``) survives via
a warn-once :class:`DeprecationWarning` shim (same pattern as the PR 8
``RolloutSpec`` migration).

:func:`span` is the other half: named host spans at the boundaries of
the engine's scheduler tick and the mux's permits, written into the
``jax.profiler`` trace beside the device's operations and on its clock.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field, fields

import jax

# every span the program opens starts with one of these
SPAN_PREFIXES = ("engine.", "mux.")


def span(name: str, **meta):
    """A host span named ``name`` in the profiler's trace, with ``meta``
    (request or job ids) as the event's stats, so that the name stays the
    same for every request.  With the profiler stopped it is a no-op
    annotation."""
    return jax.profiler.TraceAnnotation(name, **meta)


def warn_legacy_once(flag: list, message: str) -> None:
    """Emit ``message`` as a :class:`DeprecationWarning` the first time the
    module-level ``flag`` (a one-element mutable list, so tests can reset
    it) is seen unset.  ``stacklevel=3`` points at the caller of the
    deprecated property, not the shim machinery."""
    if not flag[0]:
        flag[0] = True
        warnings.warn(message, DeprecationWarning, stacklevel=3)


@dataclass
class MetricsSnapshot:
    """One merged view of serving/runtime health at a point in time.

    Field classes (and their merge rule):

    * **counters** (sum): monotone totals — steps, prefills, transfers,
      sheds, … .
    * **peaks** (max): high-water marks — ``peak_active``,
      ``peak_kv_blocks``.
    * **gauges** (other wins when set): instantaneous occupancy —
      ``queue_depth``, ``num_active``, ``suspended``, … .  Merging
      a router's decode + prefill snapshots sums these *before* they meet
      this rule (the router does that itself), so cross-component merges
      just keep the freshest reading.
    * **dicts** (union, other wins per key): per-pool busy fractions.
    """

    source: str = ""

    # -- queueing / slot occupancy (gauges except the peaks/counters noted)
    queue_depth: int = 0                 # gauge: waiting requests
    num_slots: int = 0                   # gauge: configured decode slots
    num_active: int = 0                  # gauge: live decode slots
    peak_active: int = 0                 # peak
    slot_steps: int = 0                  # counter: slot-steps with work

    # -- decode progress (counters)
    steps: int = 0
    decode_time_s: float = 0.0
    prefills: int = 0
    generated_tokens: int = 0

    # -- KV block pool
    peak_kv_blocks: int = 0              # peak

    # -- prefix sharing (counters)
    prefix_hits: int = 0
    prefix_partial_hits: int = 0
    snapshot_demotions: int = 0          # counter: TTL demotions
    blocks_saved: int = 0

    # -- suspend/resume + disaggregation
    suspends: int = 0                    # counter
    resumes: int = 0                     # counter
    suspended: int = 0                   # gauge: live suspended handles
    transfers: int = 0                   # counter
    transfer_time_s: float = 0.0         # counter
    transferred_blocks: int = 0          # counter
    kv_routed: int = 0                   # counter

    # -- admission control (counters)
    sheds: int = 0
    degrades: int = 0

    # -- runtime permit pools (dict gauge)
    pool_busy_frac: dict = field(default_factory=dict)

    weight_version: int = 0              # gauge

    _PEAKS = ("peak_active", "peak_kv_blocks")
    _GAUGES = ("queue_depth", "num_slots", "num_active", "suspended",
               "weight_version")
    _DICTS = ("pool_busy_frac",)

    # -- derived ------------------------------------------------------
    @property
    def time_per_token(self) -> float:
        """Mean decode step wall time (the SLO policy's EMA seed)."""
        return self.decode_time_s / max(self.steps, 1)

    @property
    def slot_utilization(self) -> float:
        """Useful tokens per slot-step of capacity offered (matches
        ``EngineStats.slot_utilization``)."""
        return self.generated_tokens / max(self.slot_steps, 1)

    @property
    def transfer_overhead_frac(self) -> float:
        """KV-transfer wall time as a fraction of transfer + decode time
        (zero when nothing was served)."""
        busy = self.transfer_time_s + self.decode_time_s
        if busy <= 0.0:
            return 0.0
        return self.transfer_time_s / busy

    @property
    def queue_pressure(self) -> float:
        """Waiting requests per configured slot — the controller's primary
        grow signal."""
        return self.queue_depth / max(self.num_slots, 1)

    @property
    def occupancy(self) -> float:
        """Live slots / configured slots — the controller's shrink signal."""
        return self.num_active / max(self.num_slots, 1)

    # -- merging ------------------------------------------------------
    def merge(self, other: "MetricsSnapshot") -> "MetricsSnapshot":
        """Per-field merge: counters sum, peaks max, gauges take ``other``
        when it carries a reading, dicts union with ``other`` winning per
        key.  Returns a new snapshot; neither input is mutated."""
        out = MetricsSnapshot(source=self.source or other.source)
        if self.source and other.source and other.source != self.source:
            out.source = f"{self.source}+{other.source}"
        for f in fields(self):
            if f.name == "source":
                continue
            a, b = getattr(self, f.name), getattr(other, f.name)
            if f.name in self._DICTS:
                setattr(out, f.name, {**a, **b})
            elif f.name in self._PEAKS:
                setattr(out, f.name, max(a, b))
            elif f.name in self._GAUGES:
                setattr(out, f.name, b if b else a)
            else:
                setattr(out, f.name, a + b)
        return out

    @classmethod
    def merged(cls, snapshots) -> "MetricsSnapshot":
        out = cls()
        for s in snapshots:
            out = out.merge(s)
        return out

    def to_dict(self) -> dict:
        """Flat dict (dataclass fields + the derived ratios) for JSON
        reports."""
        d = {f.name: getattr(self, f.name) for f in fields(self)}
        d.update(time_per_token=self.time_per_token,
                 slot_utilization=self.slot_utilization)
        return d
