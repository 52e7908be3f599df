"""Operations and bytes that the work needs, computed from shapes.

These are the numerators of every share of a peak the benchmark reports:
model FLOPs for ``mfu.*`` and the least work of a kernel call for its
roofline.  They count what the algorithm needs, not what the program
happens to do: dead slots, padding and recomputation are not work.
``conf`` is a configuration file's dict (Hugging Face key names) and
``family`` its model family (``bench/families/<model_type>.py``), which
counts what differs by architecture: the weights a token multiplies, the
attention FLOPs per key position and each kernel's least work.
"""
from __future__ import annotations


def forward_flops(family, conf: dict, tokens: int, keys: int) -> float:
    """FLOPs of a forward pass over ``tokens`` positions whose attention
    reads ``keys`` key positions in all (summed over the positions,
    each counting itself): 2 per weight per token, plus the family's
    attention FLOPs per key."""
    return (2.0 * family.matmul_params(conf) * tokens
            + family.attention_flops_per_key(conf) * keys)


def prefill_keys(prompt_len: int) -> int:
    """Keys read by a causal prefill of ``prompt_len`` positions."""
    return prompt_len * (prompt_len + 1) // 2


def roofline_seconds(flops: float, bytes_: float, peaks) -> tuple[float, str]:
    """The least time the chip's peaks allow for the work, and which bound
    sets it (``"compute"`` or ``"memory"``)."""
    t_c, t_m = flops / peaks.flops_bf16, bytes_ / peaks.hbm_bytes_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
