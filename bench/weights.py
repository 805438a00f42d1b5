"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so the reference never reads
anything the program made.  The pytree is laid out as the program's
dense GQA model and EAGLE-3 draft take it: target ``{embed, final_norm,
decoder: {slots: [layer stack], rem: []}, head}`` (no ``head`` when the
embedding is tied) and draft ``{fuse, in_proj, layer, final_norm}``.

Scales: embedding N(0, 0.02); every projection N(0, 1/fan_in); norm
scales 1.  Everything is drawn in float32 and stored as bfloat16, the
type the model is served in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from model import Dims

BF16 = jnp.bfloat16


def jax_seed(seed: int) -> int:
    """A 31-bit key seed from any whole-number ``--seed``."""
    return int(np.random.default_rng(int(seed) % (1 << 63)).integers(
        0, 2**31 - 1))


def _layer_shapes(dm: Dims, stack: tuple = ()):
    d, f = dm.d, dm.ff
    s = stack
    return {
        "norm1": (s + (d,), None),
        "attn": {"wq": (s + (d, dm.q_width), d),
                 "wk": (s + (d, dm.kv_width), d),
                 "wv": (s + (d, dm.kv_width), d),
                 "wo": (s + (dm.q_width, d), dm.q_width)},
        "norm2": (s + (d,), None),
        "mlp": {"wi": (s + (d, f), d), "wg": (s + (d, f), d),
                "wo": (s + (f, d), f)},
    }


def shapes(dm: Dims):
    """(target, draft) pytrees of (shape, fan_in); fan_in None = ones,
    "embed" = N(0, 0.02)."""
    target = {"embed": ((dm.vocab, dm.d), "embed"),
              "final_norm": ((dm.d,), None),
              "decoder": {"slots": [_layer_shapes(dm, (dm.layers,))],
                          "rem": []}}
    if not dm.tied:
        target["head"] = ((dm.d, dm.vocab), dm.d)
    draft = {"fuse": ((3 * dm.d, dm.d), 3 * dm.d),
             "in_proj": ((2 * dm.d, dm.d), 2 * dm.d),
             "layer": _layer_shapes(dm),
             "final_norm": ((dm.d,), None)}
    return target, draft


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_weights(dm: Dims, seed: int):
    """(target, draft) weights on the default device, bf16."""
    spec = shapes(dm)
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
