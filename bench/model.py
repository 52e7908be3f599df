"""A configuration file's sizes, handed to the program as its model config.

The file uses the published ``config.json`` key names.  Each size the
program runs is set from the file here, and a key the program cannot
honour (another activation, biases, tied embeddings) is refused rather
than run differently from what the file states.
"""
from __future__ import annotations

import dataclasses


def program_config(conf: dict):
    from repro.configs import get_config
    if conf.get("hidden_act") != "silu" or conf.get("bias") \
            or conf.get("tie_word_embeddings"):
        raise ValueError("the program's dense stack is a bias-free SwiGLU "
                         "decoder with an untied head; this file states "
                         "otherwise")
    base = get_config(conf["arch"])
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=conf["num_key_value_heads"], head_dim=d // h,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["rms_norm_eps"],
        qkv_bias=False, tie_embeddings=False, dtype=conf["torch_dtype"])


def build(conf: dict):
    from repro.models import build_model
    return build_model(program_config(conf))
