"""Seeded random weights, made by the benchmark on the device.

The weights are the benchmark's, not the program's: one jitted call draws
every leaf from the run's seed, in the dtype the configuration serves,
laid out as the program's parameter tree.  The reference reads the same
tree through the mapping documented in ``bench/reference/internlm2.py``.

Every matrix and the embedding are N(0, initializer_range), the
published configuration's initialisation; RMSNorm gains are 1, which the
program's tree stores as the offset 0 (it computes ``x * (1 + w)``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import dims


def key_for(seed: int, *salt: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    words = np.random.SeedSequence([int(seed), *salt]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


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


NORM_GAINS = ("final_norm", "ln1", "ln2")    # stored as offsets from 1


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def make(conf: dict, seed: int, *, device=None) -> dict:
    """All weights for ``seed``, on ``device`` (default: the first), in the
    configuration's dtype."""
    dtype = jnp.dtype(conf["torch_dtype"])
    std = conf["initializer_range"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(conf), is_leaf=_is_shape)

    def draw(key):
        out = []
        for i, (path, shp) in enumerate(leaves):
            if path[-1].key in NORM_GAINS:
                out.append(jnp.zeros(shp, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shp, jnp.float32) * std
                            ).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    dev = device or jax.devices()[0]
    return jax.jit(draw, out_shardings=jax.sharding.SingleDeviceSharding(dev)
                   )(key_for(seed))
