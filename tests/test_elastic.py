"""Elastic capacity: resize actuators, the closed-loop controller, the
unified telemetry API, and overload admission control.

The master invariant under test is the same one every serving feature in
this repo carries: capacity changes (resizes, admission clamps, permit
retunes) move *when* work runs, never *what* it computes — greedy tokens
of every admitted, non-degraded request are bit-identical to a static
run, a degraded request's tokens are an exact prefix of its unclamped
ones, and shed requests are always recorded, never silently dropped.
"""
import threading
import warnings

import numpy as np
import pytest
from test_serve_engine import MAX_LEN, get_model, reference

from repro.core import MetricsSnapshot
from repro.core.phase_control import PermitPool, PhaseProfile
from repro.data import tokenizer as tok
from repro.serve import (DisaggConfig, DisaggRouter, ElasticConfig,
                         ElasticController, Engine, EngineConfig, Request,
                         rederive_slo, resize_engine, resize_router,
                         run_trace)
from repro.serve.sched import FIFOPolicy, SLOPolicy

PROMPTS = [f"{i}+{i + 1}=" for i in range(8)]


def _requests(n, max_new=6, deadline=None):
    return [Request(rid=i,
                    prompt=np.asarray(tok.encode(PROMPTS[i % len(PROMPTS)],
                                                 bos=True), np.int32),
                    max_new_tokens=max_new, deadline=deadline)
            for i in range(n)]


def _engine(slots, **over):
    m, params = get_model("internlm2-1.8b")
    kw = dict(num_slots=slots, max_seq_len=MAX_LEN, temperature=0.0,
              eos_id=-1)
    kw.update(over)
    return Engine(m, params, EngineConfig(**kw))


# ---------------------------------------------------------------------------
# Resize actuators: live work carried, tokens unchanged, pools conserved
# ---------------------------------------------------------------------------
def test_elastic_trace_matches_static_tokens():
    """End-to-end: a trace replayed through the controller (forced onto a
    1->2->4 growth path) finishes every request with exactly the tokens
    the static engine produces, sheds nothing, and logs its resizes."""
    static = run_trace(_engine(4), _requests(8), realtime=False)
    ctrl = ElasticController(ElasticConfig(
        ladder=(1, 2, 4), interval_s=0.0, cooldown_s=0.0,
        grow_pressure=0.5))
    rep = run_trace(_engine(1), _requests(8), realtime=False,
                    controller=ctrl)
    e = rep["elastic"]
    assert e["resizes"] >= 1 and e["resizes"] == len(e["resize_log"])
    assert e["sheds"] == 0 and e["shed_records"] == []
    assert e["class_counts"]["batch"]["admitted"] == 8
    # capacity log opens at the static shape and tracks every resize
    assert e["capacity_log"][0][1] == 1
    assert [c[1] for c in e["capacity_log"][1:]] == \
        [r[2] for r in e["resize_log"]]
    ref = {o.rid: o.tokens for o in static["outputs"]}
    assert {o.rid for o in rep["outputs"]} == set(ref)
    for o in rep["outputs"]:
        assert o.tokens == ref[o.rid], o.rid


def test_resize_engine_carries_live_work_and_monotone_counters():
    m, params = get_model("internlm2-1.8b")
    eng = _engine(2)
    reqs = _requests(4)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    before = eng.metrics()
    assert before.num_active == 2 and before.queue_depth == 2
    new = resize_engine(eng, 4)
    assert new is not eng and new.config.num_slots == 4
    after = new.metrics()
    # shared counter record: nothing reset, suspend/resume traffic visible
    assert after.steps == before.steps
    assert after.prefills >= before.prefills
    assert after.suspends == before.suspends + 2
    assert after.resumes == after.suspends
    new.run()
    assert sorted(new.finished) == [0, 1, 2, 3]
    for r in reqs:
        ref_t, _ = reference(m, params, r, max_new=6, eos_id=-1)
        assert new.finished[r.rid].tokens == ref_t, r.rid


def test_resize_shrink_refuses_to_strand_live_work():
    eng = _engine(4)
    for r in _requests(4):
        eng.submit(r)
    eng.step()
    assert eng.num_active == 4
    with pytest.raises(ValueError, match="live requests"):
        resize_engine(eng, 2)
    # same-size resize is a no-op, not a rebuild
    assert resize_engine(eng, 4) is eng


def test_resize_conserves_blocks_with_suspended_handle_and_radix():
    """The hard conservation case: the old paged pool holds radix pins
    AND an agentic suspended handle at resize time.  The actuator's
    internal conservation check must pass (handle pins are the only
    residue), the handle must resume on the *new* engine, and the old
    pool must be provably empty once the handle's view materializes."""
    m, params = get_model("internlm2-1.8b")
    eng = _engine(2, kv_layout="paged", kv_block_size=4, num_kv_blocks=64,
                  prefix_share=True)
    reqs = _requests(3, max_new=6)
    for r in reqs:
        eng.submit(r)
    for _ in range(2):
        eng.step()
    held = eng._suspend_slot(sorted(eng._active)[0])
    assert held.req.rid in {s.req.rid for s in eng.suspended.values()}
    new = resize_engine(eng, 4)         # conservation asserted inside
    new.resume(held, continue_output=True)
    new.run()
    assert sorted(new.finished) == [0, 1, 2]
    for r in reqs:
        ref_t, _ = reference(m, params, r, max_new=6, eos_id=-1)
        assert new.finished[r.rid].tokens == ref_t, r.rid
    # the handle's pins were released at materialization: old pool clean
    # (the old radix was flushed by the resize — its snapshots referenced
    # the old pool)
    eng.slots.alloc.assert_clean(context="test")
    new.radix.flush()                   # drop the new tree's live pins
    new.slots.alloc.assert_clean(context="test")


def test_elastic_router_trace_matches_static_tokens():
    m, params = get_model("internlm2-1.8b")

    def build(decode):
        return DisaggRouter(m, params, DisaggConfig(
            prefill_slots=1, decode_slots=decode, max_seq_len=MAX_LEN,
            temperature=0.0, eos_id=-1))

    static = run_trace(build(4), _requests(6), realtime=False)
    ctrl = ElasticController(ElasticConfig(
        ladder=(1, 2, 4), interval_s=0.0, cooldown_s=0.0,
        grow_pressure=0.5))
    rep = run_trace(build(1), _requests(6), realtime=False, controller=ctrl)
    assert rep["elastic"]["resizes"] >= 1
    ref = {o.rid: o.tokens for o in static["outputs"]}
    assert {o.rid for o in rep["outputs"]} == set(ref)
    for o in rep["outputs"]:
        assert o.tokens == ref[o.rid], o.rid


# ---------------------------------------------------------------------------
# Unified telemetry: one snapshot shape, warn-once legacy shims
# ---------------------------------------------------------------------------
def test_stats_shims_warn_once_and_metrics_is_silent():
    import repro.serve.engine as em
    import repro.serve.router as rm

    m, params = get_model("internlm2-1.8b")
    eng = _engine(2)
    router = DisaggRouter(m, params, DisaggConfig(
        prefill_slots=1, decode_slots=2, max_seq_len=MAX_LEN,
        temperature=0.0))
    for mod, obj, label in ((em, eng, "Engine"), (rm, router,
                                                  "DisaggRouter")):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            mod._warned_legacy[0] = False   # fresh process view
            obj.stats
            obj.stats                       # second access: no new warning
        deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
        assert len(deps) == 1, label
        assert "metrics()" in str(deps[0].message)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert isinstance(eng.metrics(), MetricsSnapshot)
        assert isinstance(router.metrics(), MetricsSnapshot)
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]


def test_metrics_snapshot_merge_rules():
    a = MetricsSnapshot(source="engine", steps=10, decode_time_s=1.0,
                        peak_active=3, queue_depth=5, num_slots=4,
                        pool_busy_frac={"rollout": 0.5, "reward": 0.2})
    b = MetricsSnapshot(source="runtime", steps=2, decode_time_s=0.5,
                        peak_active=2, queue_depth=0, num_slots=0,
                        pool_busy_frac={"rollout": 0.9, "train": 0.1})
    m = a.merge(b)
    assert m.source == "engine+runtime"
    assert m.steps == 12                          # counters sum
    assert m.peak_active == 3                     # peaks max
    assert m.queue_depth == 5 and m.num_slots == 4  # gauges: b unset -> a
    # dicts: union, b wins per key
    assert m.pool_busy_frac == {"rollout": 0.9, "train": 0.1, "reward": 0.2}
    # gauge where b carries a reading: b wins
    c = a.merge(MetricsSnapshot(queue_depth=1))
    assert c.queue_depth == 1
    # derived ratios
    assert m.time_per_token == pytest.approx(1.5 / 12)
    assert a.queue_pressure == pytest.approx(5 / 4)
    assert MetricsSnapshot.merged([a, b]).steps == 12
    assert "time_per_token" in a.to_dict()


def test_engine_and_router_metrics_share_one_shape():
    m, params = get_model("internlm2-1.8b")
    eng = _engine(2)
    for r in _requests(3):
        eng.submit(r)
    eng.run()
    snap = eng.metrics()
    assert snap.source == "engine"
    assert snap.prefills == 3 and snap.generated_tokens > 0
    assert 0.0 < snap.slot_utilization <= 1.0
    router = DisaggRouter(m, params, DisaggConfig(
        prefill_slots=1, decode_slots=2, max_seq_len=MAX_LEN,
        temperature=0.0, eos_id=-1))
    for r in _requests(3):
        router.submit(r)
    router.run()
    rs = router.metrics()
    assert rs.source == "router"
    assert rs.transfers == 3 and rs.prefills >= 3
    assert rs.num_slots == 2                  # decode plane gauge
    # snapshots merge across components without shape knowledge
    assert snap.merge(rs).transfers == 3


# ---------------------------------------------------------------------------
# Overload admission control: degrade before shed, never silent
# ---------------------------------------------------------------------------
def _seed_served(engine, time_per_token=0.05, steps=100):
    """Give the engine a measured decode history so the admission
    predictor has a real time-per-token to reason from."""
    engine._stats.steps += steps
    engine._stats.decode_time_s += time_per_token * steps


def test_admission_gate_degrades_then_sheds_and_records():
    eng = _engine(2)
    _seed_served(eng, time_per_token=0.05)
    ctrl = ElasticController(ElasticConfig(
        ladder=(2,), shed=True, min_degrade_tokens=8))
    ctrl.attach(eng, 0.0)
    # plenty of slack: admitted at full budget
    v, r = ctrl.admit(_requests(1, max_new=10, deadline=10.0)[0], 0.0, eng)
    assert v == "admit" and r.max_new_tokens == 10
    # slack fits 8..31 tokens at 0.05 s/tok: degraded, budget clamped
    req = Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                  max_new_tokens=32, deadline=1.0)
    v, clamped = ctrl.admit(req, 0.0, eng)
    assert v == "degrade"
    assert 8 <= clamped.max_new_tokens < 32
    assert ctrl.degrade_records[0]["rid"] == 1
    # deadline already unmeetable even at the minimum budget: shed
    req = Request(rid=2, prompt=np.arange(4, dtype=np.int32),
                  max_new_tokens=32, deadline=0.1)
    v, _ = ctrl.admit(req, 0.0, eng)
    assert v == "shed"
    assert ctrl.shed_records[0]["rid"] == 2
    assert "deadline" in ctrl.shed_records[0]["reason"]
    cc = ctrl.class_counts["interactive"]
    assert cc == {"admitted": 2, "degraded": 1, "shed": 1}
    # driver retry after queue backpressure: cached verdict, no recount
    v2, _ = ctrl.admit(req, 0.5, eng)
    assert v2 == "shed"
    assert len(ctrl.shed_records) == 1
    assert ctrl.class_counts["interactive"]["shed"] == 1


def test_subsaturation_sheds_exactly_zero():
    """The predictor is conservative by construction: with no measured
    service time, or with deadlines the measured backlog provably meets,
    nothing is shed — even with admission control armed."""
    ctrl = ElasticController(ElasticConfig(ladder=(2,), shed=True))
    rep = run_trace(_engine(2), _requests(6, deadline=1e9), realtime=False,
                    controller=ctrl)
    assert rep["elastic"]["sheds"] == 0
    assert rep["elastic"]["degrades"] == 0
    assert len(rep["outputs"]) == 6


def test_overload_sheds_are_reported_not_silent():
    eng = _engine(2)
    _seed_served(eng, time_per_token=0.2)      # slow engine, hard deadlines
    ctrl = ElasticController(ElasticConfig(ladder=(2,), shed=True))
    reqs = _requests(4, max_new=6, deadline=1e-4)
    rep = run_trace(eng, reqs, realtime=False, controller=ctrl)
    e = rep["elastic"]
    assert e["sheds"] == 4 == len(e["shed_records"])
    assert sorted(r["rid"] for r in e["shed_records"]) == [0, 1, 2, 3]
    assert len(rep["outputs"]) == 0
    # accounting closes: every arrival is admitted, degraded-admitted,
    # or shed — nothing vanishes
    cc = e["class_counts"]["interactive"]
    assert cc["admitted"] + cc["shed"] == len(reqs)


def test_degraded_budget_yields_exact_prefix():
    """A degrade is a max_new clamp and nothing else: the clamped
    request's greedy tokens are an exact prefix of the unclamped run."""
    m, params = get_model("internlm2-1.8b")
    full = _requests(1, max_new=8)[0]
    eng = _engine(1)
    eng.submit(full)
    eng.run()
    long_toks = eng.finished[0].tokens
    eng2 = _engine(1)
    eng2.submit(Request(rid=0, prompt=full.prompt, max_new_tokens=4))
    eng2.run()
    short = eng2.finished[0].tokens
    assert short == long_toks[:len(short)] and len(short) == 4


# ---------------------------------------------------------------------------
# SLO re-derivation from measured profiles
# ---------------------------------------------------------------------------
def test_rederive_slo_updates_policy_from_profiles():
    class FakeRuntime:
        def phase_profiles(self):
            return {"job0": PhaseProfile(job_id="job0",
                                         rollout_s=(2.0, 2.2),
                                         train_s=(1.0, 1.1))}

    policy = SLOPolicy(slowdown=2.0)
    bound = rederive_slo(policy, FakeRuntime())
    assert bound is not None and bound >= 1.0
    assert policy.slowdown == bound
    # no contract / no runtime / no profiles: explicit no-op
    assert rederive_slo(FIFOPolicy(), FakeRuntime()) is None
    assert rederive_slo(policy, None) is None

    class EmptyRuntime:
        def phase_profiles(self):
            return {}

    assert rederive_slo(policy, EmptyRuntime()) is None


# ---------------------------------------------------------------------------
# Radix boundary-snapshot TTL demotion
# ---------------------------------------------------------------------------
def test_radix_snapshot_ttl_demotion_counts_and_survives_roundtrip():
    eng = _engine(2, kv_layout="paged", kv_block_size=4, num_kv_blocks=64,
                  prefix_share=True)
    reqs = [Request(rid=i, prompt=np.asarray(tok.encode("12+34=", bos=True),
                                             np.int32), max_new_tokens=4)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    radix = eng.radix
    assert radix.stats["snapshots"] >= 1
    n_before = radix.stats["snapshots"]
    assert radix.demote_stale(10 ** 9) == 0          # generous ttl: keep all
    # age everything past the horizon, then demote
    for _ in range(5):
        radix._bump()
    n = radix.demote_stale(0)
    assert n == n_before
    assert radix.stats["snapshots"] == 0
    assert radix.stats["snapshot_demotions"] == n
    assert eng.metrics().snapshot_demotions == n
    # tree structure (and block pins) untouched: still block-shares
    assert radix.stats["pinned_blocks"] > 0
    # counters and last_used survive the checkpoint round-trip
    host, dev = radix.export_host_state(), radix.export_device_state()
    assert host["counters"]["demotions"] == n
    eng2 = _engine(2, kv_layout="paged", kv_block_size=4, num_kv_blocks=64,
                   prefix_share=True)
    eng2.radix.import_state(host, dev)
    assert eng2.radix.snapshot_demotions == n
    assert {x.last_used for x in eng2.radix.nodes.values()} == \
        {x.last_used for x in radix.nodes.values()}


# ---------------------------------------------------------------------------
# Router restore: re-routed spread + shared policy (PR 9 residual)
# ---------------------------------------------------------------------------
def test_router_requeue_spreads_over_prefill_engines():
    m, params = get_model("internlm2-1.8b")
    router = DisaggRouter(m, params, DisaggConfig(
        prefill_slots=1, decode_slots=2, max_seq_len=MAX_LEN,
        temperature=0.0, prefill_engines=2))
    # one shared admission-policy object across every prefill engine
    assert len({id(pe.policy) for pe in router.prefills}) == 1
    router._requeue(_requests(6, max_new=4))
    lens = [len(pe.queue._q) for pe in router.prefills]
    assert sum(lens) == 6
    assert all(n > 0 for n in lens), lens    # spread, not engine-0 pile-up


# ---------------------------------------------------------------------------
# PermitPool.resize: grow wakes waiters, shrink never revokes
# ---------------------------------------------------------------------------
def test_permit_pool_resize_under_contention():
    pool = PermitPool("reward", capacity=1)
    pool.acquire()
    got = threading.Event()

    def waiter():
        pool.acquire()
        got.set()

    t = threading.Thread(target=waiter)
    t.start()
    assert not got.wait(0.1)                # blocked behind the bound
    assert pool.waiting == 1
    pool.resize(2)                          # grow: waiter admitted now
    assert got.wait(2.0)
    t.join()
    pool.resize(1)                          # shrink with 2 permits held
    pool.release()                          # neither holder was revoked
    pool.release()
    assert pool.waiting == 0
    pool.acquire()                          # bound is 1 again
    reacquired = threading.Event()
    t2 = threading.Thread(target=lambda: (pool.acquire(), reacquired.set()))
    t2.start()
    assert not reacquired.wait(0.1)
    pool.release()
    assert reacquired.wait(2.0)
    t2.join()
    pool.release()
    with pytest.raises(ValueError):
        pool.resize(0)


# ---------------------------------------------------------------------------
# Streaming executor: permit retune rides the same telemetry loop
# ---------------------------------------------------------------------------
def test_stream_elastic_retunes_permits_without_changing_math():
    from test_stream import make_job

    from repro.rl.stream import run_streaming

    _, h_ref, _ = run_streaming(make_job(), max_staleness=0,
                                reward_workers=3)
    _, h_el, _ = run_streaming(make_job(), max_staleness=0,
                               reward_workers=3, elastic=True)
    assert [r["loss"] for r in h_ref] == [r["loss"] for r in h_el]
    assert all(1 <= r["reward_permits"] <= 3 for r in h_el)
    assert all("reward_permits" not in r for r in h_ref)
