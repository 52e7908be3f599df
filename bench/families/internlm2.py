"""InternLM2 (arXiv:2403.17297): a dense, bias-free SwiGLU decoder with
grouped-query attention and an untied head, run by the program's dense
stack.

Found by the configuration file's published ``model_type``.  A family file
holds what the benchmark must know of one architecture: the program config
it derives from the file, the layout of the weight tree, the plain
reference, the work counts that differ by family and the sizes at which
its cells run on the CPU in a test.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

from bench.reference.internlm2 import Reference  # noqa: F401

# Sizes at which the family's cells run on the CPU in a test: the
# program's reduced widths (2 layers, d_model 256, vocab 512).
TINY = dict(hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=512, vocab_size=512)

NORM_GAINS = ("final_norm", "ln1", "ln2")    # stored as offsets from 1


class Dims(NamedTuple):
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int


def dims(conf: dict) -> Dims:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return Dims(layers=conf["num_hidden_layers"], d_model=d, heads=h,
                kv_heads=conf["num_key_value_heads"], head_dim=d // h,
                d_ff=conf["intermediate_size"], vocab=conf["vocab_size"])


def program_config(conf: dict):
    """The program's model config for the file's sizes.  A key the
    program cannot honour (another activation, biases, tied embeddings)
    is refused rather than run differently from what the file states."""
    from repro.configs import get_config
    if conf.get("hidden_act") != "silu" or conf.get("bias") \
            or conf.get("tie_word_embeddings"):
        raise ValueError("the program's dense stack is a bias-free SwiGLU "
                         "decoder with an untied head; this file states "
                         "otherwise")
    m = dims(conf)
    return dataclasses.replace(
        get_config(conf["arch"]), num_layers=m.layers, d_model=m.d_model,
        num_heads=m.heads, num_kv_heads=m.kv_heads, head_dim=m.head_dim,
        d_ff=m.d_ff, vocab_size=m.vocab,
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["rms_norm_eps"],
        qkv_bias=False, tie_embeddings=False, dtype=conf["torch_dtype"])


def shapes(conf: dict) -> dict:
    """Shape of every leaf of the program's tree for this configuration."""
    m = dims(conf)
    L, d, H, K, e, F, V = (m.layers, m.d_model, m.heads, m.kv_heads,
                           m.head_dim, m.d_ff, m.vocab)
    return {
        "embed": (V, d), "final_norm": (d,), "lm_head": (d, V),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, H, e), "wk": (L, d, K, e),
                     "wv": (L, d, K, e), "wo": (L, H, e, d)},
            "mlp": {"wi_gate": (L, d, F), "wi_up": (L, d, F),
                    "wo": (L, F, d)},
        },
    }


def matmul_params(conf: dict) -> int:
    """Weights that a token multiplies: q, k, v, o and the SwiGLU MLP of
    every layer, plus the output head.  The embedding is a lookup."""
    m = dims(conf)
    attn = m.d_model * m.heads * m.head_dim * 2 \
        + m.d_model * m.kv_heads * m.head_dim * 2
    mlp = 3 * m.d_model * m.d_ff
    return m.layers * (attn + mlp) + m.d_model * m.vocab


def attention_flops_per_key(conf: dict) -> float:
    """2 for q.k and 2 for p.v per head and head dimension in every
    layer."""
    m = dims(conf)
    return 4.0 * m.layers * m.heads * m.head_dim


def paged_attention_work(conf: dict, lengths, *, block_size: int,
                         itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) that one ``paged_decode_attention`` call (one layer)
    needs for live rows of KV ``lengths``: q.k and p.v over each row's
    live positions, the live KV blocks read once, q read and the output
    written once.  Dead slots are no work."""
    m = dims(conf)
    flops = bytes_ = 0.0
    for n in lengths:
        blocks = -(-int(n) // block_size)
        flops += 4.0 * m.heads * m.head_dim * n
        bytes_ += 2.0 * blocks * block_size * m.kv_heads * m.head_dim * itemsize
        bytes_ += 2.0 * m.heads * m.head_dim * itemsize       # q and out
    return flops, bytes_
