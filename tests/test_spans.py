"""Program spans in the profiler's trace (``core.telemetry.span``): the
engine's scheduler tick and the mux's permits, read back from the
``.xplane.pb`` that ``jax.profiler`` writes on the CPU.

The load-bearing guarantees:

  * every tick is an ``engine.step`` holding ``engine.admit``,
    ``engine.dispatch``, ``engine.wait`` and ``engine.record`` in that
    order, with one ``engine.prefill`` or ``engine.share`` per admitted
    request under the admit, named bare and carrying its ``rid`` as a stat;
  * tokens and log-probabilities are the same with the profiler on or off;
  * the mux opens ``mux.<pool>`` with ``job`` for every held permit, and
    ``mux.restore`` / ``mux.offload`` around each actor-cache switch.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData
from test_coexec import make_job
from test_serve_engine import MAX_LEN, get_model

from repro.core import telemetry
from repro.core.phase_control import RollMuxRuntime
from repro.data import tokenizer as tok
from repro.rl.coexec import run_coexec
from repro.serve import Engine, EngineConfig, Request


def host_spans(trace_dir) -> list[tuple[str, str, float, float, dict]]:
    """(thread, name, start, end, stats) of every program span in the
    newest profile under ``trace_dir``, by start time."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(line.name, e.name, e.start_ns,
                     e.start_ns + e.duration_ns, dict(e.stats))
                    for e in line.events
                    if e.name.startswith(telemetry.SPAN_PREFIXES)]
    return sorted(out, key=lambda s: (s[2], -s[3]))


def inside(child, parent) -> bool:
    return (child[0] == parent[0] and child[2] >= parent[2]
            and child[3] <= parent[3])


def serve(trace_dir=None):
    """A paged engine with prefix sharing serving two copies of one prompt
    (a donor and an exact radix hit) and another prompt, sampled at
    temperature 1; under the profiler when ``trace_dir`` is given."""
    m, params = get_model("internlm2-1.8b")
    eng = Engine(m, params, EngineConfig(
        num_slots=3, max_seq_len=MAX_LEN, temperature=1.0,
        kv_layout="paged", kv_block_size=4, prefix_share=True),
        rng=jax.random.PRNGKey(7))
    prompts = ["12+34=", "12+34=", "5+6="]
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, max_new_tokens=4,
                           prompt=np.asarray(tok.encode(p, bos=True),
                                             np.int32)))
    if trace_dir is None:
        return {o.rid: o for o in eng.run()}
    with jax.profiler.trace(str(trace_dir)):
        return {o.rid: o for o in eng.run()}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    d = tmp_path_factory.mktemp("engine-trace")
    return serve(), serve(d), host_spans(d)


def test_profiler_leaves_tokens_and_logprobs_unchanged(served):
    off, on, _ = served
    assert sorted(off) == sorted(on) == [0, 1, 2]
    for rid in off:
        assert on[rid].tokens == off[rid].tokens
        assert on[rid].logprobs == off[rid].logprobs
        assert len(on[rid].tokens) == 4


def test_tick_spans_nest_in_order(served):
    _, _, spans = served
    steps = [s for s in spans if s[1] == "engine.step"]
    # 4 tokens a request, all admitted in the first tick: 4 decode ticks
    assert len(steps) == 4
    for st in steps:
        kids = [s[1] for s in spans if s is not st and inside(s, st)
                and s[1] in ("engine.admit", "engine.dispatch",
                             "engine.wait", "engine.record")]
        assert kids == ["engine.admit", "engine.dispatch", "engine.wait",
                        "engine.record"]
    assert all(s[4] == {} for s in steps)


def test_admitted_requests_span_with_their_rid(served):
    _, _, spans = served
    admit = next(s for s in spans if s[1] == "engine.admit")
    per_req = [(s[1], s[4]) for s in spans
               if s[1] in ("engine.prefill", "engine.share")]
    # rid 1 repeats rid 0's prompt: an exact radix hit, no model compute
    assert per_req == [("engine.prefill", {"rid": 0}),
                       ("engine.share", {"rid": 1}),
                       ("engine.prefill", {"rid": 2})]
    assert all(inside(s, admit) for s in spans
               if s[1] in ("engine.prefill", "engine.share"))


def test_adoption_spans_with_its_rid(tmp_path):
    m, params = get_model("internlm2-1.8b")
    eng = Engine(m, params, EngineConfig(
        num_slots=2, max_seq_len=MAX_LEN, temperature=0.0,
        kv_layout="paged", kv_block_size=4))
    req = Request(rid=5, max_new_tokens=2, prompt=np.asarray(
        tok.encode("3+4=", bos=True), np.int32))
    logits, one = eng._fns["prefill"](params, jax.numpy.asarray(
        req.prompt)[None], None)
    with jax.profiler.trace(str(tmp_path)):
        eng.admit_prefilled(req, logits, one)
    adopt = [s for s in host_spans(tmp_path) if s[1] == "engine.adopt"]
    assert [s[4] for s in adopt] == [{"rid": 5}]
    assert [len(o.tokens) for o in eng.run()] == [2]


def test_permit_spans_name_pool_and_job(tmp_path):
    rt = RollMuxRuntime(host_cache_gb=0.01)
    with jax.profiler.trace(str(tmp_path)):
        for who in ("a:roll", "b:roll"):
            with rt.permit("rollout", who):
                pass
        with rt.permit("reward", "a:reward", capacity=2):
            pass
    got = [(s[1], s[4]) for s in host_spans(tmp_path)]
    assert got == [("mux.rollout", {"job": "a"}),
                   ("mux.rollout", {"job": "b"}),
                   ("mux.reward", {"job": "a"})]
    assert [w for w, _, _ in rt.pools["rollout"].timeline] == \
        ["a:roll", "b:roll"]


def test_coexec_spans_each_job_phase_and_switch(tmp_path):
    """Two jobs under ``--mux coexec``: per job and step, one
    ``mux.rollout`` and one ``mux.train`` span, each holding one restore
    and one offload of the job's state."""
    steps = 2
    jobs = [make_job("j0", seed=0, steps=steps),
            make_job("j1", seed=1, steps=steps)]
    with jax.profiler.trace(str(tmp_path)):
        _, hist, _ = run_coexec(jobs, host_cache_gb=1.0)
    assert {j: len(h) for j, h in hist.items()} == {"j0": steps,
                                                    "j1": steps}
    spans = host_spans(tmp_path)
    for jid in ("j0", "j1"):
        mine = [s for s in spans if s[4] == {"job": jid}]
        for pool in ("mux.rollout", "mux.train"):
            held = [s for s in mine if s[1] == pool]
            assert len(held) == steps, (jid, pool)
            for h in held:
                switch = [s[1] for s in mine if s is not h and inside(s, h)]
                assert switch == ["mux.restore", "mux.offload"]
    # one permit per pool at a time: a pool's spans never overlap
    for pool in ("mux.rollout", "mux.train"):
        held = sorted(s[2:4] for s in spans if s[1] == pool)
        assert all(a[1] <= b[0] for a, b in zip(held, held[1:]))
