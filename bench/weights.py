"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so the reference never reads
anything the program made.  The pytree's layout and each leaf's
shape and fan-in are the architecture's (``shapes`` in
``bench/archs/<name>.py``); a leaf's key is the next of one split of
the seed's key, in the tree's flattened order.

Scales: embedding N(0, 0.02); every projection N(0, 1/fan_in); norm
scales 1.  Everything is drawn in float32 and stored as bfloat16, the
type the model is served in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from model import arch_of

BF16 = jnp.bfloat16


def jax_seed(seed: int) -> int:
    """A 31-bit key seed from any whole-number ``--seed``."""
    return int(np.random.default_rng(int(seed) % (1 << 63)).integers(
        0, 2**31 - 1))


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_weights(dm, seed: int):
    """(target, draft) weights on the default device, bf16, in the layout
    of ``dm``'s architecture (its ``shapes``)."""
    spec = arch_of(dm).shapes(dm)
    leaves, tree = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, fan) in zip(keys, leaves):
            if fan is None:
                out.append(jnp.ones(shape, BF16))
            else:
                std = 0.02 if fan == "embed" else 1.0 / np.sqrt(fan)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(BF16))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(init)(jax.random.PRNGKey(jax_seed(seed)))
