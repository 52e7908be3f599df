"""The output check fails what it must: the control (the reference one
precision step below the configuration's, in the program's place) and
each fault a cell can have, planted underneath a whole run.

Sizes are the tiny ones of ``bench_tiny`` in bfloat16, as the cells
serve, held to the limits that ``bench_tiny.LIMITS`` sets for that size.
"""
import jax
import pytest

from bench import harness
from bench_tiny import LIMITS, shrink_for


def _run(cell, seed, **kw):
    return harness.run_cell(cell, seed, 1.0, False, devices=jax.devices(),
                            shrink=shrink_for("bfloat16", LIMITS[cell]),
                            **kw)[0]


@pytest.mark.parametrize("cell,seed", [("rollout-longtail", 7),
                                       ("rollout-longtail", 8),
                                       ("rollout-longtail", 2**31 + 9)])
def test_control_fails_where_the_program_passes(cell, seed):
    res = _run(cell, seed, controls=True)
    assert res["correct"] is True, res["checks"]
    lim = LIMITS[cell]
    tags = {k.split(".", 1)[1] for k in res["control"]}
    for tag in tags:
        over = [k for k in lim if res["control"].get(f"{k}.{tag}", 0) > lim[k]]
        assert over, (tag, res["control"])


def _alter_served_token(monkeypatch):
    from repro.serve.engine import Engine
    harvest = Engine.harvest

    def broken(self):
        outs = harvest(self)
        for o in outs:
            o.tokens[-1] = (o.tokens[-1] + 1) % self.model.cfg.vocab_size
        return outs
    monkeypatch.setattr(Engine, "harvest", broken)


def _alter_first_token(monkeypatch):
    """The token the prefill produces, altered where it is produced."""
    from repro.serve.engine import Engine
    harvest = Engine.harvest

    def broken(self):
        outs = harvest(self)
        for o in outs:
            o.tokens[0] = (o.tokens[0] + 1) % self.model.cfg.vocab_size
        return outs
    monkeypatch.setattr(Engine, "harvest", broken)


def _cut_short(monkeypatch):
    """Requests that end one token before their budget."""
    from repro.serve.engine import Engine
    harvest = Engine.harvest

    def broken(self):
        outs = harvest(self)
        for o in outs:
            if len(o.tokens) > 1:
                del o.tokens[-1], o.logprobs[-1]
        return outs
    monkeypatch.setattr(Engine, "harvest", broken)


@pytest.mark.parametrize("cell,fault", [
    ("rollout-longtail", _alter_served_token),
    ("rollout-longtail", _alter_first_token),
    ("rollout-longtail", _cut_short),
])
def test_planted_fault_reads_incorrect(cell, fault, monkeypatch):
    assert _run(cell, 3)["correct"] is True
    fault(monkeypatch)
    res = _run(cell, 3)
    assert res["correct"] is False, res["checks"]
