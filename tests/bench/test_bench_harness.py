"""The harness: every part found by name, each cell's whole path on the
CPU at the program's reduced sizes (through the test seam of
``run_cell``), the entry point's refusal of the CPU, and a cell added
from files alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import harness
from bench_tiny import shrink_for

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_every_part_loads_by_name():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.conf["name"] == w["config"]
        assert harness.load_driver(cell.traffic["kind"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
    for c in BENCH["configs"]:
        assert json.loads((ROOT / c["file"]).read_text())["name"] == \
            c["name"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_whole_path_on_cpu(name):
    res, lines = harness.run_cell(name, 2**31 + 11, 1.0, False,
                                  devices=jax.devices(),
                                  shrink=shrink_for("float32"))
    cell = harness.load_cell(name)
    assert res["correct"] is True, lines
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert lines[-len(res["checks"]):] == [
        f"check {k}: {v['value']!r} limit {v['limit']!r}"
        for k, v in res["checks"].items()]
    assert "compilations inside the window: 0" in lines


def test_entry_point_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


# A family found by a model_type that no file of bench/ names: internlm2's
# parts under another name, with CPU sizes of its own.
NEW_FAMILY = """from bench.harness import load_family

_base = load_family("internlm2")
program_config, shapes, NORM_GAINS = (_base.program_config, _base.shapes,
                                      _base.NORM_GAINS)
Reference, matmul_params = _base.Reference, _base.matmul_params
attention_flops_per_key = _base.attention_flops_per_key
paged_attention_work = _base.paged_attention_work
TINY = dict(_base.TINY, intermediate_size=384)
"""


def _only_added(tmp_path) -> None:
    """The tree under ``tmp_path`` holds every file of ``bench/`` as it
    is, and BENCHMARK.json's entries as they are with entries added."""
    for p in (ROOT / "bench").rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            assert (tmp_path / p.relative_to(ROOT)).read_bytes() == \
                p.read_bytes(), p
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert new.keys() == BENCH.keys()
    for key, old in BENCH.items():
        if key == "end_to_end":
            for o, n in zip(old, new[key], strict=True):
                if "workloads" in o:
                    w = o["workloads"]
                    assert n["workloads"][:len(w)] == w
                    n = dict(n, workloads=w)
                assert n == o
        elif isinstance(old, list):
            assert new[key][:len(old)] == old
        else:
            assert new[key] == old


@pytest.mark.parametrize("model_type", ["internlm2", "twinlm"])
def test_a_cell_added_from_files_alone(tmp_path, model_type):
    """A new configuration, traffic mix, limits and per-layer metric, each a
    file of its own, plus entries in BENCHMARK.json: nothing else.  A
    configuration of a ``model_type`` that no file names brings its
    family as a file of its own too."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = json.loads(json.dumps(BENCH))
    conf = json.loads((ROOT / "bench/configs/internlm2-1.8b.json").read_text())
    conf["name"] = "internlm2-1.8b-copy"
    conf["model_type"] = model_type
    if model_type != "internlm2":
        (tmp_path / f"bench/families/{model_type}.py").write_text(NEW_FAMILY)
    (tmp_path / "bench/configs/internlm2-1.8b-copy.json").write_text(
        json.dumps(conf))
    traffic = json.loads(
        (ROOT / "bench/traffic/rollout-longtail.json").read_text())
    traffic["prompt_lengths"] = [64]
    (tmp_path / "bench/traffic/short-prompts.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/limits/copy.short.json").write_text(json.dumps(
        json.loads((ROOT / "bench/limits/rollout-longtail.json").read_text())))
    (tmp_path / "bench/metrics/ticks_per_s.py").write_text(
        "def read(run):\n"
        "    return len(run.record['ticks']) / run.record['window_s']\n")
    bench["configs"].append(dict(bench["configs"][0],
                                 name="internlm2-1.8b-copy",
                                 file="bench/configs/internlm2-1.8b-copy.json"))
    bench["workloads"].append({"name": "copy.short",
                               "config": "internlm2-1.8b-copy",
                               "traffic": "short-prompts", "chips": 1,
                               "why": "a cell added from files alone"})
    for m in bench["end_to_end"]:
        if "rollout-longtail" in m.get("workloads", []):
            m["workloads"].append("copy.short")
    bench["per_layer"].append({"name": "ticks_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine scheduler",
                               "moves": "rollout_tok_s",
                               "workloads": ["copy.short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    _only_added(tmp_path)

    cell = harness.load_cell("copy.short", tmp_path)
    assert cell.traffic["prompt_lengths"] == [64]
    assert [m["name"] for m in cell.per_layer] == ["ticks_per_s"]
    assert Path(cell.family.__file__) == \
        tmp_path / "bench" / "families" / f"{model_type}.py"
    read = harness.load_reader("ticks_per_s", tmp_path)
    assert read(harness.Run(cell=cell, conf=cell.conf, traffic=cell.traffic,
                            peaks=None, record={"ticks": [{}, {}],
                                                "window_s": 2.0})) == 1.0

    ran = []

    def shrink(c):
        shrink_for("float32")(c)
        c.traffic["prompt_lengths"] = [16]
        ran.append(c)
    res, _ = harness.run_cell("copy.short", 3, 1.0, False, root=tmp_path,
                              devices=jax.devices(), shrink=shrink)
    assert res["correct"] is True
    assert res["metrics"]["rollout_tok_s"]["value"] > 0
    # the run was cut by its own family's sizes
    assert ran[0].conf["intermediate_size"] == \
        ran[0].family.TINY["intermediate_size"]
    assert ran[0].family.TINY["intermediate_size"] == \
        (512 if model_type == "internlm2" else 384)


def test_unknown_family_names_the_known():
    with pytest.raises(KeyError, match="internlm2"):
        harness.load_family("no_such_family")


def test_trace_starts_only_inside_the_window(tmp_path):
    """Drivers tick the context during set-up too; the profiler starts
    ``trace_start_s`` after the window opens and never before."""
    cell = harness.load_cell("rollout-longtail")
    ctx = harness.Context(cell, 1, jax.devices(), trace=True,
                          trace_dir=tmp_path / "trace", t0=0.0)
    ctx.tick(1e9)                       # set-up: the window is not open
    assert not ctx.tracing()
    t_open = ctx.open_window()
    ctx.tick(t_open + cell.traffic["trace_start_s"] / 2)
    assert not ctx.tracing()
