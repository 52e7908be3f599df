"""Operations and bytes that the work needs, computed from shapes.

These are the numerators of every share of a peak the benchmark reports:
model FLOPs for ``mfu.*`` and the least work of one
``paged_decode_attention`` call for its roofline.  They count what the
algorithm needs, not what the program happens to do: dead slots, padding
and recomputation are not work.  ``conf`` is a configuration file's dict
(Hugging Face key names).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
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


def matmul_params(conf: dict) -> int:
    """Weights that a token multiplies: q, k, v, o and the SwiGLU MLP of
    every layer, plus the output head.  The embedding is a lookup."""
    m = dims(conf)
    attn = m.d_model * m.heads * m.head_dim * 2 \
        + m.d_model * m.kv_heads * m.head_dim * 2
    mlp = 3 * m.d_model * m.d_ff
    return m.layers * (attn + mlp) + m.d_model * m.vocab


def forward_flops(conf: dict, tokens: int, keys: int) -> float:
    """FLOPs of a forward pass over ``tokens`` positions whose attention
    reads ``keys`` key positions in all (summed over the positions,
    each counting itself): 2 per weight per token, plus 2 for q.k and 2
    for p.v per key, head and head dimension in every layer."""
    m = dims(conf)
    return (2.0 * matmul_params(conf) * tokens
            + 4.0 * m.layers * m.heads * m.head_dim * keys)


def prefill_keys(prompt_len: int) -> int:
    """Keys read by a causal prefill of ``prompt_len`` positions."""
    return prompt_len * (prompt_len + 1) // 2


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


def roofline_seconds(flops: float, bytes_: float, peaks) -> tuple[float, str]:
    """The least time the chip's peaks allow for the work, and which bound
    sets it (``"compute"`` or ``"memory"``)."""
    t_c, t_m = flops / peaks.flops_bf16, bytes_ / peaks.hbm_bytes_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
