"""A configuration's model family (``bench/families/<model_type>.py``):
internlm2-1.8b gets the same program config, weights and reference
through its family as the benchmark gave it before families existed, and
no module of the benchmark outside the families and the references names
a family."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np

from bench import harness, weights
from bench_tiny import tiny_conf

ROOT = Path(__file__).resolve().parents[2]
CONF = json.loads((ROOT / "bench/configs/internlm2-1.8b.json").read_text())
FAMILY = harness.load_family(CONF["model_type"])

# The weight tree's leaves in the order weights.make draws them (leaf i
# from fold_in(key, i)), and the sha256 of their bytes in that order at
# the tiny sizes in bfloat16 for seed 2**31 + 11, as the benchmark made
# them before the tree's layout moved into the family file.
LEAVES = ["['embed']", "['final_norm']", "['layers']['attn']['wk']",
          "['layers']['attn']['wo']", "['layers']['attn']['wq']",
          "['layers']['attn']['wv']", "['layers']['ln1']",
          "['layers']['ln2']", "['layers']['mlp']['wi_gate']",
          "['layers']['mlp']['wi_up']", "['layers']['mlp']['wo']",
          "['lm_head']"]
SHA256 = "2b428cc2b6f9cfabf12f3ae322696b69c2207cbc44ed04777ffd3a9162d7272d"


def test_internlm2_weights_are_bit_identical_to_before():
    w = weights.make(FAMILY, tiny_conf(CONF, "bfloat16"), 2**31 + 11)
    leaves = jax.tree_util.tree_flatten_with_path(w)[0]
    assert [jax.tree_util.keystr(p) for p, _ in leaves] == LEAVES
    h = hashlib.sha256()
    for _, a in leaves:
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == SHA256


def test_internlm2_program_config_at_published_sizes():
    cfg = FAMILY.program_config(CONF)
    got = {k: getattr(cfg, k) for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "rope_theta", "norm_eps", "qkv_bias",
        "tie_embeddings", "dtype")}
    assert got == dict(num_layers=24, d_model=2048, num_heads=16,
                       num_kv_heads=8, head_dim=128, d_ff=8192,
                       vocab_size=92544, rope_theta=1e6, norm_eps=1e-5,
                       qkv_bias=False, tie_embeddings=False,
                       dtype="bfloat16")


def test_no_module_outside_families_and_references_names_a_family():
    families = [p.stem for p in (ROOT / "bench/families").glob("*.py")]
    assert "internlm2" in families
    for p in (ROOT / "bench").rglob("*.py"):
        rel = p.relative_to(ROOT / "bench").parts
        if rel[0] in ("families", "reference"):
            continue
        text = p.read_text()
        assert "bench.reference" not in text, p
        assert not [f for f in families if f in text], p
