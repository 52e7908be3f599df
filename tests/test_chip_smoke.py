"""``chip_smoke.py``'s phases on the CPU at the reduced size, kernels
interpreted, so the chip script cannot rot between chip runs.  Its
``main`` (which demands a TPU) must refuse to run here."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.models import build_model

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_reduced_interpreted(smoke):
    model = build_model(smoke.ARCH, reduced=True)
    params = model.init(jax.random.PRNGKey(smoke.SEED))
    out = smoke.serve_phase(model, params, n_requests=3, prompt_len=20,
                            max_new=6)
    assert set(out) == {"float32", "int8"}
    for r in out.values():
        assert r["interpret"] is True
        assert not r["tpu_custom_call"]
        assert r["max_first_logprob_diff"] <= smoke.LOGPROB_ATOL
        assert 0.0 <= r["identical_sequences"] <= 1.0
    # fp32 KV on the CPU: the pallas backend is token-identical to jnp
    assert out["float32"]["identical_sequences"] == 1.0


def test_rl_phase_reduced_interpreted(smoke):
    model = build_model(smoke.ARCH, reduced=True)
    out = smoke.rl_phase(model, steps=2, batch=2, group=2, max_new=4)
    assert len(out["losses"]) == 2
    assert 0.0 < out["moved_share"] <= 1.0
    assert out["mux"]["mode"] == "stream"


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main() != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert captured.out == ""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(from_env, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins when set and the code sets nothing;
    otherwise the cache sits at a fixed directory of the checkout."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
        want = tmp_path if from_env else _PATH.parent / ".jax_cache"
        assert Path(got) == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
