"""Content-addressed radix-tree KV sharing (``repro.serve.radix``):
engine-level greedy equivalence (shared == unshared == per-request
``generate``, bit for bit), cross-request/untagged/multi-turn sharing by
token content, namespace isolation, strict-LRU node eviction, tree
checkpoint round-trips, KV-aware routing across prefill engines, and the
allocator/slot-manager invariants under random shared
admit/grow/release interleavings (refcounts conserved, no double free,
null block untouched, tree pins accounted).
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st
from test_serve_engine import MAX_LEN, get_model, reference

from repro.data import tokenizer as tok
from repro.serve import (Engine, EngineConfig, PagedSlotManager, Request,
                         blocks_for)
from repro.serve.blocks import BlockAllocator
from repro.serve.radix import RadixPrefixIndex


def group_requests(texts, group, *, max_new=6, job="j"):
    """GRPO-shaped trace: each prompt duplicated ``group`` times, members
    tagged with one shared namespace key (isolation between groups — the
    sharing itself is by content)."""
    reqs = []
    rid = 0
    for gi, text in enumerate(texts):
        prompt = np.asarray(tok.encode(text, bos=True), np.int32)
        for _ in range(group):
            reqs.append(Request(rid=rid, prompt=prompt.copy(),
                                max_new_tokens=max_new,
                                prefix_key=(job, gi)))
            rid += 1
    return reqs


def run_engine(m, params, reqs, **cfg):
    eng = Engine(m, params, EngineConfig(max_seq_len=MAX_LEN,
                                         temperature=0.0, **cfg))
    for r in reqs:
        eng.submit(Request(rid=r.rid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens,
                           prefix_key=r.prefix_key))
    return eng, eng.run()


# ---------------------------------------------------------------------------
# Exact-hit sharing: bit-identical output, prefill once per group
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["internlm2-1.8b",   # dense GQA attention
                                  "gemma3-4b"])       # sliding-window layers
def test_shared_engine_bit_identical_to_unshared(arch):
    """Two interleaved GRPO groups (different prompt lengths, small blocks
    so prompts span several full blocks + a partial tail): the sharing
    engine's greedy tokens/logprobs equal the unshared paged engine's and
    per-request ``generate``'s, while prefilling each prompt once."""
    m, params = get_model(arch)
    reqs = group_requests(["123+456=", "7+8="], group=3)
    kw = dict(num_slots=3, kv_layout="paged", kv_block_size=4)
    _, base = run_engine(m, params, reqs, **kw)
    eng, outs = run_engine(m, params, reqs, prefix_share=True, **kw)
    for r, o, c in zip(reqs, outs, base):
        ref_t, ref_l = reference(m, params, r, max_new=6)
        assert o.tokens == c.tokens == ref_t, (arch, o.rid)
        np.testing.assert_allclose(o.logprobs, c.logprobs, atol=0)
        np.testing.assert_allclose(o.logprobs, ref_l, atol=1e-5)
    assert eng.stats.prefix_hits == 4        # 2 groups x (3 members - donor)
    assert eng.radix.misses == 2             # one prefill per group
    assert eng.stats.blocks_saved > 0
    # every live structure drained; tree pins are the only refs left
    eng.slots.check(extra_pins=eng.radix.pinned_blocks())
    eng.radix.flush()
    eng.slots.check()
    assert eng.slots.blocks_in_use == 0


def test_untagged_cross_request_sharing_by_content():
    """No keys anywhere: an exact prompt repeat admits with zero compute
    and an extension pins the common full blocks — content alone drives
    sharing, and probes (``count=False``) never skew the counters."""
    m, params = get_model("internlm2-1.8b")
    prompt = np.asarray(tok.encode("1234+5678=", bos=True), np.int32)
    ext = np.concatenate([prompt, np.asarray([9, 9, 9], np.int32)])
    reqs = [Request(rid=0, prompt=prompt.copy(), max_new_tokens=5),
            Request(rid=1, prompt=prompt.copy(), max_new_tokens=5),
            Request(rid=2, prompt=ext, max_new_tokens=5)]
    kw = dict(num_slots=3, kv_layout="paged", kv_block_size=4)
    _, base = run_engine(m, params, reqs, **kw)
    eng, outs = run_engine(m, params, reqs, prefix_share=True, **kw)
    for r, o, c in zip(reqs, outs, base):
        ref_t, ref_l = reference(m, params, r, max_new=5)
        assert o.tokens == c.tokens == ref_t, o.rid
        # the extension (rid 2) is a partial hit: it prefills only its
        # suffix against the pinned blocks, which sums attention in another
        # order than a full prefill — 1 ulp (~1e-6) apart on XLA:CPU
        atol = 1e-5 if o.rid == 2 else 0
        np.testing.assert_allclose(o.logprobs, c.logprobs, atol=atol)
        np.testing.assert_allclose(o.logprobs, ref_l, atol=1e-5)
    assert eng.radix.misses == 1             # only the first prompt prefills
    assert eng.radix.hits == 1               # the exact repeat
    assert eng.radix.partial_hits == 1       # the extension
    assert eng.stats.blocks_saved >= 2 * (len(prompt) // 4)
    # a capacity-probe style lookup must not move the admission counters
    before = dict(eng.radix.stats)
    assert eng.radix.match(reqs[0]) is not None
    assert dict(eng.radix.stats) == before


def test_namespace_isolation():
    """Identical prompts under distinct ``prefix_key`` namespaces never
    share — each namespace grows its own root and pays its own prefill."""
    m, params = get_model("internlm2-1.8b")
    prompt = np.asarray(tok.encode("123+456=", bos=True), np.int32)
    reqs = [Request(rid=i, prompt=prompt.copy(), max_new_tokens=4,
                    prefix_key=key)
            for i, key in enumerate(("tenant-a", "tenant-b", None))]
    eng, outs = run_engine(m, params, reqs, num_slots=3, kv_layout="paged",
                           kv_block_size=4, prefix_share=True)
    assert eng.radix.hits == 0 and eng.radix.partial_hits == 0
    assert eng.radix.misses == 3
    assert eng.stats.blocks_saved == 0
    # one node path per namespace, same content thrice
    assert len(eng.radix.roots) == 3
    assert len(eng.radix) == 3 * (len(prompt) // 4)
    ref_t, _ = reference(m, params, reqs[0], max_new=4)
    for o in outs:
        assert o.tokens == ref_t
    eng.slots.check(extra_pins=eng.radix.pinned_blocks())


def test_shared_blocks_pinned_under_multiple_owners():
    """While a group is in flight, its prompt's full blocks carry one ref
    per live member (+ the tree pin) — several slot owners per block."""
    m, params = get_model("internlm2-1.8b")
    reqs = group_requests(["1234+5678="], group=3, max_new=8)
    eng = Engine(m, params, EngineConfig(
        num_slots=3, max_seq_len=MAX_LEN, temperature=0.0,
        kv_layout="paged", kv_block_size=4, prefix_share=True))
    for r in reqs:
        eng.submit(r)
    eng.step()                               # all three admitted, 1 decode
    probe = eng.radix.match(reqs[0])
    assert probe is not None and probe.exact
    assert len(probe.block_ids) >= 1
    for bid in probe.block_ids:
        # donor's own ref + 2 sharers + the tree pin
        assert eng.slots.alloc.refcount[bid] == 4
    eng.slots.check(extra_pins=eng.radix.pinned_blocks())
    eng.run()
    # members gone: only the tree pin remains
    for bid in probe.block_ids:
        assert eng.slots.alloc.refcount[bid] == 1


def test_shared_admits_more_groups_at_equal_memory():
    """The acceptance criterion in miniature: at the same KV pool size,
    prefix sharing admits strictly more concurrent GRPO-group members
    than the unshared paged engine (prompt blocks are pinned, not
    duplicated, so admission's net-new demand shrinks)."""
    m, params = get_model("internlm2-1.8b")
    reqs = group_requests(["123+456="], group=6, max_new=8)
    total = reqs[0].total_budget
    # pool sized for ~3 unshared members' worst case
    blocks = 3 * blocks_for(total, 4)
    kw = dict(num_slots=6, kv_layout="paged", kv_block_size=4,
              num_kv_blocks=blocks)
    unshared, _ = run_engine(m, params, reqs, **kw)
    shared, outs = run_engine(m, params, reqs, prefix_share=True, **kw)
    assert shared.stats.peak_active > unshared.stats.peak_active
    for r, o in zip(reqs, outs):
        ref_t, _ = reference(m, params, r, max_new=8)
        assert o.tokens == ref_t


def test_rwkv6_degenerate_sharing_is_prefill_cache():
    """No ``cache_seq`` leaves: nothing to page, but an exact hit still
    skips prefill via the root boundary snapshot — outputs unchanged."""
    m, params = get_model("rwkv6-7b")
    reqs = group_requests(["12+34="], group=3)
    kw = dict(num_slots=2, kv_layout="paged", kv_block_size=8)
    _, base = run_engine(m, params, reqs, **kw)
    eng, outs = run_engine(m, params, reqs, prefix_share=True, **kw)
    assert [o.tokens for o in outs] == [o.tokens for o in base]
    assert eng.stats.prefix_hits == 2 and eng.stats.blocks_saved == 0


def test_prefix_hit_extension_shares_blocks():
    """A prompt that *extends* a registered prefix (longer prompt, same
    leading tokens) can't skip prefill but pins the matching full blocks
    and still decodes exactly (write-masked scatter never touches them).
    The extension registers in turn, so a repeat of the longer prompt is
    then an exact hit."""
    m, params = get_model("internlm2-1.8b")
    base_text, ext_text = "1234+5678=", "1234+5678=9"
    prompt0 = np.asarray(tok.encode(base_text, bos=True), np.int32)
    prompt1 = np.asarray(tok.encode(ext_text, bos=True), np.int32)
    assert np.array_equal(prompt1[:len(prompt0)], prompt0)
    eng = Engine(m, params, EngineConfig(
        num_slots=2, max_seq_len=MAX_LEN, temperature=0.0,
        kv_layout="paged", kv_block_size=4, prefix_share=True))
    r0 = Request(rid=0, prompt=prompt0, max_new_tokens=5)
    r1 = Request(rid=1, prompt=prompt1, max_new_tokens=5)
    eng.submit(r0)
    eng.submit(r1)
    outs = eng.run()
    assert eng.stats.prefix_partial_hits == 1
    assert outs[1].prefix_shared_blocks > 0
    for r, o in zip((r0, r1), outs):
        ref_t, ref_l = reference(m, params, r, max_new=5)
        assert o.tokens == ref_t, o.rid
        np.testing.assert_allclose(o.logprobs, ref_l, atol=1e-5)
    eng.slots.check(extra_pins=eng.radix.pinned_blocks())
    # the extension's own tail boundary is now registered too
    m1 = eng.radix.match(r1)
    assert m1 is not None and m1.exact


def test_multi_turn_resume_history_registers():
    """A resumed episode's history (prompt + generated turn + tool tokens)
    registers in the tree, so a sibling rollout submitting that same
    history matches it — turn k+1 shares turn k's blocks instead of
    re-prefilling the whole conversation."""
    m, params = get_model("internlm2-1.8b")
    prompt = np.asarray(tok.encode("1+2=", bos=True), np.int32)
    ref_t, _ = reference(
        m, params, Request(rid=0, prompt=prompt, max_new_tokens=10),
        max_new=10)
    stop = ref_t[2]
    tool = np.asarray([7, 11, 13], np.int32)
    eng = Engine(m, params, EngineConfig(
        num_slots=2, max_seq_len=MAX_LEN, temperature=0.0,
        kv_layout="paged", kv_block_size=4, prefix_share=True))
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=10,
                       stop_tokens=(stop,)))
    eng.run()
    [sreq] = eng.harvest_suspended()
    eng.resume(sreq, tool, max_new_tokens=6, rid=1, stop_tokens=())
    [resumed] = eng.run()
    history = np.concatenate([prompt,
                              np.asarray(sreq.out.tokens, np.int32), tool])
    sibling = Request(rid=2, prompt=history, max_new_tokens=6)
    assert eng.radix.match(sibling) is not None   # history is in the tree
    hits0 = eng.radix.hits + eng.radix.partial_hits
    eng.submit(sibling)
    eng.run()
    out = eng.finished[2]
    assert eng.radix.hits + eng.radix.partial_hits == hits0 + 1
    assert out.prefix_shared_blocks > 0
    # same continuation the resume produced (the adoption path is
    # semantically a prefill of the history prompt)
    assert out.tokens == resumed.tokens
    eng.slots.check(extra_pins=eng.radix.pinned_blocks())


def test_frontend_requests_never_share():
    """Prompt tokens alone don't identify frontend-conditioned KV (prefill
    conditions on the embeddings), so requests carrying a frontend must
    bypass the radix tree even with matching keys and tokens."""
    import jax.numpy as jnp
    m, _ = get_model("internlm2-1.8b")
    from repro.models import build_model
    vm = build_model("qwen2-vl-7b", reduced=True)
    import jax
    vparams = vm.init(jax.random.PRNGKey(1))
    fr0 = jnp.zeros((1, vm.cfg.num_frontend_tokens, vm.cfg.d_model))
    fr1 = jnp.ones((1, vm.cfg.num_frontend_tokens, vm.cfg.d_model))
    # frontend embeddings overlay the first num_frontend_tokens prompt
    # positions, so the padded prompt must be at least that long
    prompt = np.asarray(tok.pad_batch(
        [tok.encode("1+2=", bos=True)],
        vm.cfg.num_frontend_tokens + 8)[0], np.int32)
    eng = Engine(vm, vparams, EngineConfig(
        num_slots=2, max_seq_len=MAX_LEN, temperature=0.0,
        kv_layout="paged", kv_block_size=4, prefix_share=True))
    for rid, fr in enumerate((fr0, fr1)):
        eng.submit(Request(rid=rid, prompt=prompt.copy(), max_new_tokens=4,
                           prefix_key="k", frontend=fr))
    outs = eng.run()
    assert eng.stats.prefix_hits == 0
    assert len(eng.radix) == 0 and eng.radix.stats["entries"] == 0
    # same tokens, different frontends -> genuinely different generations
    from repro.rl import SamplerConfig, generate
    for rid, fr in enumerate((fr0, fr1)):
        ref = generate(vm, vparams, jnp.asarray(prompt)[None],
                       jax.random.PRNGKey(0),
                       SamplerConfig(max_new_tokens=4, temperature=0.0),
                       frontend=fr)
        n = int(np.asarray(ref["mask"])[0].sum())
        assert outs[rid].tokens[:n] == \
            np.asarray(ref["completions"])[0][:n].tolist(), rid


def test_eviction_under_block_pressure_and_reset_flush():
    """Tree pins are evicted LRU when admission needs the blocks; reset
    flushes everything (new params invalidate cached prefills)."""
    m, params = get_model("internlm2-1.8b")
    eng = Engine(m, params, EngineConfig(
        num_slots=2, max_seq_len=MAX_LEN, temperature=0.0,
        kv_layout="paged", kv_block_size=4,
        num_kv_blocks=blocks_for(MAX_LEN, 4),  # one stripe's worth
        prefix_share=True))
    probe = Request(rid=9, prompt=np.asarray(
        tok.encode("11+22=", bos=True), np.int32), max_new_tokens=4)
    eng.submit(Request(rid=0, prompt=probe.prompt.copy(), max_new_tokens=4))
    eng.run()
    assert len(eng.radix) >= 1
    assert eng.radix.match(probe) is not None
    # a big unrelated request needs (almost) the whole pool: path evicted
    eng.submit(Request(rid=1, prompt=np.asarray(
        tok.encode("3+4=", bos=True), np.int32), max_new_tokens=40))
    eng.run()
    assert eng.radix.evictions >= 1
    assert eng.radix.match(probe) is None
    eng.reset(params)
    assert len(eng.radix) == 0
    eng.slots.check()
    assert eng.slots.blocks_in_use == 0


# ---------------------------------------------------------------------------
# Strict-LRU eviction order (single-pass heap, leaf-first)
# ---------------------------------------------------------------------------
def _fake_req(tokens, key=None):
    return Request(rid=0, prompt=np.asarray(tokens, np.int32),
                   max_new_tokens=1, prefix_key=key)


def _register_blocks(index, alloc, owner, tokens):
    """Register a block-aligned prompt, materializing its blocks as a
    transient owner the way a donor slot would (refcount drops to the
    tree's single pin on free_all)."""
    req = _fake_req(tokens)
    n = len(tokens) // alloc.block_size
    alloc.reserve(owner, n)
    bids = [alloc.allocate(owner) for _ in range(n)]
    index.register(req, bids, logits=None, tail={}, slot_leaves={})
    alloc.free_all(owner)
    return req


def test_evict_for_strict_lru_order():
    """Eviction drains least-recently-used leaves first: three
    single-block paths registered A, B, C then A touched must evict in
    order B, C, A — and ``touch`` (recency) is what reorders, not
    registration order."""
    alloc = BlockAllocator(8, 4)
    index = RadixPrefixIndex(alloc)
    ra = _register_blocks(index, alloc, 1, [1, 2, 3, 4])
    rb = _register_blocks(index, alloc, 2, [5, 6, 7, 8])
    rc = _register_blocks(index, alloc, 3, [9, 10, 11, 12])
    ids = {name: index.match(r).node_ids[0]
           for name, r in (("a", ra), ("b", rb), ("c", rc))}
    index.touch(index.match(ra))             # A most recent
    assert index.evict_for(8)                # needs the whole pool
    assert index.eviction_log == [ids["b"], ids["c"], ids["a"]]
    assert len(index) == 0
    alloc.assert_clean()


def test_evict_for_leaf_first_parent_after_child():
    """A two-block path evicts leaf before parent (the parent enters the
    victim heap only once its last child is gone), and a node shared by
    a live pin (refcount > 1) or on the ``protect`` path survives."""
    alloc = BlockAllocator(8, 4)
    index = RadixPrefixIndex(alloc)
    rd = _register_blocks(index, alloc, 1, [1, 2, 3, 4, 5, 6, 7, 8])
    child_id = index.match(rd).node_ids[1]
    parent_id = index.match(rd).node_ids[0]
    # protect the whole path: nothing evictable
    assert not index.evict_for(8, protect=index.match(rd).node_ids)
    assert index.eviction_log == []
    # pin the parent like a live slot would: only the leaf goes
    alloc.incref(index.match(rd).nodes[0].block_id)
    assert not index.evict_for(8)
    assert index.eviction_log == [child_id]
    parent_bid = index.match(rd).nodes[0].block_id
    alloc.decref(parent_bid)
    assert index.evict_for(8)
    assert index.eviction_log == [child_id, parent_id]
    alloc.assert_clean()


# ---------------------------------------------------------------------------
# Tree checkpoint round-trips
# ---------------------------------------------------------------------------
def test_tree_export_import_structural_roundtrip():
    """Host/device export of the *tree* (parent links, tokens, boundary
    snapshots, counters) rebuilds an equivalent index: every match that
    hit before hits after, node identity and LRU clocks included."""
    alloc = BlockAllocator(16, 4)
    index = RadixPrefixIndex(alloc)
    ra = _register_blocks(index, alloc, 1, [1, 2, 3, 4, 5, 6, 7, 8])
    rb = _register_blocks(index, alloc, 2, [1, 2, 3, 4, 9, 9])  # shared head
    rc = _fake_req([20, 21, 22, 23], key="ns")
    alloc.reserve(3, 1)
    index.register(rc, [alloc.allocate(3)], logits=np.arange(4.0),
                   tail={"k": np.ones(2)}, slot_leaves={"s": np.zeros(3)})
    alloc.free_all(3)
    index.match(ra, count=True)
    index.touch(index.match(ra))
    host, device = index.export_host_state(), index.export_device_state()
    clone = RadixPrefixIndex(alloc)          # pins travel with the alloc
    clone.import_state(host, device)
    assert len(clone) == len(index)
    assert set(clone.roots) == {None, "ns"}
    for req in (ra, rb, rc):
        a, b = index.match(req), clone.match(req)
        assert a.node_ids == b.node_ids and a.block_ids == b.block_ids
        assert a.exact == b.exact
    # shared head: rb's first node IS ra's first node, after import too
    assert clone.match(ra).node_ids[0] == clone.match(rb).node_ids[0]
    snap = clone.match(rc).snapshot
    np.testing.assert_array_equal(np.asarray(snap.logits), np.arange(4.0))
    np.testing.assert_array_equal(np.asarray(snap.tail["k"]), np.ones(2))
    assert clone.stats == index.stats
    assert clone._tick == index._tick
    # the clone shares the alloc's pins; only the original may drop them
    index.flush()
    alloc.assert_clean()


def test_engine_roundtrip_int8_with_suspended_handle_mid_tree():
    """Engine-level checkpoint with the tree populated (multi-node paths,
    int8 scale leaves in the pool) *and* a suspended handle pinning
    blocks mid-tree: the import rebuilds the tree, the suspended request
    resumes, and new exact hits against imported snapshots stay
    token-identical."""
    m, params = get_model("internlm2-1.8b")
    cfg = EngineConfig(num_slots=2, max_seq_len=MAX_LEN, temperature=0.0,
                       kv_layout="paged", kv_block_size=4, kv_dtype="int8",
                       prefix_share=True)
    prompt = np.asarray(tok.encode("1234+5678=", bos=True), np.int32)
    eng = Engine(m, params, cfg)
    eng.submit(Request(rid=0, prompt=prompt.copy(), max_new_tokens=8))
    eng.run()
    # int8 KV legitimately drifts from the fp32 reference, so the exact-hit
    # decode below is held to this int8 engine's own first run
    ref_t = eng.finished[0].tokens
    # suspend a second request mid-generation so the checkpoint carries a
    # live handle next to the tree pins
    stop = ref_t[2]
    eng.submit(Request(rid=1, prompt=prompt.copy(), max_new_tokens=8,
                       stop_tokens=(stop,)))
    eng.run()
    [sreq] = eng.harvest_suspended()
    state = eng.export_state()
    fresh = Engine(m, params, cfg)
    fresh.import_state(state)
    a = eng.radix.export_host_state()
    b = fresh.radix.export_host_state()
    assert a["counters"] == b["counters"]
    assert ([(n["id"], n["parent"], n["block_id"]) for n in a["nodes"]]
            == [(n["id"], n["parent"], n["block_id"]) for n in b["nodes"]])
    # an exact hit against the imported snapshot decodes identically
    hits0 = fresh.radix.hits
    fresh.submit(Request(rid=2, prompt=prompt.copy(), max_new_tokens=8))
    fresh.run()
    assert fresh.finished[2].tokens == ref_t
    assert fresh.radix.hits == hits0 + 1
    # the imported suspended handle still resumes (same rid bookkeeping)
    fsreq = fresh.suspended[1]
    fresh.resume(fsreq, (), max_new_tokens=4, rid=3, stop_tokens=())
    fresh.run()
    eng.resume(sreq, (), max_new_tokens=4, rid=3, stop_tokens=())
    eng.run()
    assert fresh.finished[3].tokens == eng.finished[3].tokens
    fresh.slots.check(extra_pins=fresh.radix.pinned_blocks())


def test_export_import_roundtrip_with_sharing_mid_flight():
    """Checkpoint a sharing engine with live shared slots; a fresh engine
    resumes token-for-token and keeps the invariants."""
    m, params = get_model("internlm2-1.8b")
    reqs = group_requests(["123+456="], group=3, max_new=8)
    cfg = EngineConfig(num_slots=2, max_seq_len=MAX_LEN, temperature=0.0,
                       kv_layout="paged", kv_block_size=4,
                       prefix_share=True)
    eng = Engine(m, params, cfg)
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()                              # live shared slots + 1 waiting
    state = eng.export_state()
    done_a = Engine(m, params, cfg)
    done_a.import_state(state)
    outs_a = done_a.run()
    outs_b = eng.run()                      # original continues too
    assert [o.tokens for o in outs_a] == [o.tokens for o in outs_b]
    for r, o in zip(reqs, outs_a):
        ref_t, _ = reference(m, params, r, max_new=8)
        assert o.tokens == ref_t
    done_a.slots.check(extra_pins=done_a.radix.pinned_blocks())


# ---------------------------------------------------------------------------
# KV-aware routing across prefill engines
# ---------------------------------------------------------------------------
def test_kv_aware_routing_steers_to_prefix_holder():
    """With two prefill engines, a request is routed to the engine whose
    tree already holds its prefix (not round-robin/least-loaded), turning
    repeats into zero-compute handles — outputs identical to monolithic."""
    from repro.serve import DisaggConfig, DisaggRouter
    m, params = get_model("internlm2-1.8b")
    pa = np.asarray(tok.encode("1234+5678=", bos=True), np.int32)
    pb = np.asarray(tok.encode("111+222=", bos=True), np.int32)
    cfg = DisaggConfig(prefill_slots=1, decode_slots=2, max_seq_len=MAX_LEN,
                       temperature=0.0, kv_layout="paged", kv_block_size=4,
                       prefix_share=True, prefill_engines=2,
                       kv_routing="kv_aware")
    router = DisaggRouter(m, params, cfg)
    assert router.prefill is router.prefills[0]
    # warm each engine with a different prompt (engine 1 warmed directly —
    # routing ties fall to engine 0 on an empty fleet)
    router.submit(Request(rid=0, prompt=pa.copy(), max_new_tokens=5))
    router.prefills[1].submit(Request(rid=1, prompt=pb.copy(),
                                      max_new_tokens=5))
    outs = {o.rid: o for o in router.run()}
    assert len(router.prefills[0].radix) > 0
    assert len(router.prefills[1].radix) > 0
    # repeats must land on their prefix holder, regardless of submit order
    router.submit(Request(rid=2, prompt=pb.copy(), max_new_tokens=5))
    router.submit(Request(rid=3, prompt=pa.copy(), max_new_tokens=5))
    outs.update({o.rid: o for o in router.run()})
    assert router.stats.kv_routed == 2
    assert router.prefills[0].stats.prefix_hits == 1
    assert router.prefills[1].stats.prefix_hits == 1
    assert outs[3].tokens == outs[0].tokens
    assert outs[2].tokens == outs[1].tokens
    for rid, prompt in ((0, pa), (1, pb)):
        ref_t, _ = reference(m, params,
                             Request(rid=rid, prompt=prompt,
                                     max_new_tokens=5), max_new=5)
        assert outs[rid].tokens == ref_t
    router.reset(params)


def test_queue_routing_balances_without_kv_affinity():
    """``kv_routing="queue"`` ignores prefix residency — requests spread
    by load alone and outputs stay correct (sharing still happens when a
    repeat happens to land on the holder)."""
    from repro.serve import DisaggConfig, DisaggRouter
    m, params = get_model("internlm2-1.8b")
    prompt = np.asarray(tok.encode("12+34=", bos=True), np.int32)
    router = DisaggRouter(m, params, DisaggConfig(
        prefill_slots=1, decode_slots=2, max_seq_len=MAX_LEN,
        temperature=0.0, kv_layout="paged", kv_block_size=4,
        prefix_share=True, prefill_engines=2, kv_routing="queue"))
    for rid in range(4):
        router.submit(Request(rid=rid, prompt=prompt.copy(),
                              max_new_tokens=5))
    outs = router.run()
    assert router.stats.kv_routed == 0
    ref_t, _ = reference(m, params,
                         Request(rid=0, prompt=prompt, max_new_tokens=5),
                         max_new=5)
    for o in outs:
        assert o.tokens == ref_t
    router.reset(params)


def test_router_config_validation():
    from repro.serve import DisaggConfig, DisaggRouter
    m, params = get_model("internlm2-1.8b")
    with pytest.raises(ValueError, match="prefill_engines"):
        DisaggRouter(m, params, DisaggConfig(prefill_engines=0))
    with pytest.raises(ValueError, match="kv_routing"):
        DisaggRouter(m, params, DisaggConfig(kv_routing="sticky"))


# ---------------------------------------------------------------------------
# Property: shared interleavings preserve allocator/slot invariants
# ---------------------------------------------------------------------------
def _drive_shared_slot_manager(ops, sm: PagedSlotManager, index_pins):
    """Random admit/admit-shared/grow/finish/evict interleavings.

    ``index_pins`` plays the radix tree: it pins (increfs) the full
    blocks of whichever live donor the op stream picks, and releases
    (decrefs) pins at random — exactly the lifecycle the engine drives.
    Invariants are checked after every op.
    """
    live, rid = [], 0
    for kind, val in ops:
        if kind == 0:                      # plain admit
            plen = 1 + val % 10
            budget = plen + 1 + val % 12
            if sm.can_admit(budget):
                slot = sm.assign(rid, prompt_len=plen, total_budget=budget)
                live.append((slot, plen, budget))
                rid += 1
        elif kind == 1 and live:           # shared admit from a live donor
            dslot, dplen, _ = live[val % len(live)]
            n_full = min(dplen // sm.block_size, sm.nblocks[dslot])
            shared = [int(b) for b in sm.tables[dslot, :n_full]]
            plen = max(dplen, 1 + val % 10)
            budget = plen + 1 + val % 12
            if sm.can_admit(budget, shared_blocks=len(shared)):
                slot = sm.assign_shared(rid, prompt_len=plen,
                                        total_budget=budget,
                                        shared_ids=shared)
                live.append((slot, plen, budget))
                rid += 1
        elif kind == 2 and live:           # decode progress -> table growth
            slot, plen, budget = live[val % len(live)]
            sm.ensure(slot, min(plen + val % 8, budget - 1))
        elif kind == 3 and live:           # pin a donor's blocks (register)
            dslot, dplen, _ = live[val % len(live)]
            n_full = min(dplen // sm.block_size, sm.nblocks[dslot])
            for b in sm.tables[dslot, :n_full]:
                sm.alloc.incref(int(b))
                index_pins.append(int(b))
        elif kind == 4 and index_pins:     # evict one pin
            sm.alloc.decref(index_pins.pop(val % len(index_pins)))
        elif kind == 5 and live:           # finish
            slot, _, _ = live.pop(val % len(live))
            sm.release(slot)
        sm.check(extra_pins=index_pins)
    for slot, _, _ in live:
        sm.release(slot)
    while index_pins:
        sm.alloc.decref(index_pins.pop())
    sm.check()
    assert sm.blocks_in_use == 0 and sm.num_free == sm.num_slots


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 63)),
                min_size=1, max_size=30))
def test_shared_slot_manager_interleaving(ops):
    m, _ = get_model("internlm2-1.8b")
    _drive_shared_slot_manager(
        ops, PagedSlotManager(m, 4, MAX_LEN, block_size=4, num_blocks=24),
        [])


@pytest.mark.slow
@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 1023)),
                min_size=1, max_size=100),
       st.integers(2, 6),                  # block size
       st.integers(8, 32))                 # pool blocks
def test_shared_slot_manager_interleaving_sweep(ops, bs, nb):
    m, _ = get_model("internlm2-1.8b")
    _drive_shared_slot_manager(
        ops, PagedSlotManager(m, 5, MAX_LEN, block_size=bs, num_blocks=nb),
        [])
