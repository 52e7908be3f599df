"""Seeded random weights, made by the benchmark on the device.

The weights are the benchmark's, not the program's: one jitted call draws
every leaf from the run's seed, in the dtype the configuration serves,
laid out as the program's parameter tree, whose leaves and shapes the
family gives (``shapes``).  The family's reference reads the same tree
through the mapping its module documents.

Every matrix and the embedding are N(0, initializer_range), the
published configuration's initialisation; RMSNorm gains (the family's
``NORM_GAINS``) are 1, which the program's tree stores as the offset 0
(it computes ``x * (1 + w)``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int, *salt: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    words = np.random.SeedSequence([int(seed), *salt]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def make(family, conf: dict, seed: int, *, device=None) -> dict:
    """All weights for ``seed``, on ``device`` (default: the first), in the
    configuration's dtype, in the tree of ``family``."""
    dtype = jnp.dtype(conf["torch_dtype"])
    std = conf["initializer_range"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        family.shapes(conf), is_leaf=_is_shape)

    def draw(key):
        out = []
        for i, (path, shp) in enumerate(leaves):
            if path[-1].key in family.NORM_GAINS:
                out.append(jnp.zeros(shp, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shp, jnp.float32) * std
                            ).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    dev = device or jax.devices()[0]
    return jax.jit(draw, out_shardings=jax.sharding.SingleDeviceSharding(dev)
                   )(key_for(seed))
