"""Weights made by the benchmark from the seed, on the device.

The program is handed these; the reference reads the same arrays.  The
tree has the program's layout (its structure comes from the program's
own initializer run abstractly, so no value of it is used), and every
leaf is drawn here in one jitted call, in the type it is served in:

* the embedding: normal, std 0.02;
* norm scales: 1 + 0.1 * normal, so that a norm that drops its scale
  shows in the comparison;
* every projection (fan-in on axis -2): normal, std 1/sqrt(fan-in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one beyond 32 bits."""
    word = int(np.random.SeedSequence(seed).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def _draw(path, struct, k):
    name = jax.tree_util.keystr(path)
    z = jax.random.normal(k, struct.shape, jnp.float32)
    if name.endswith("['scale']"):
        out = 1.0 + 0.1 * z
    elif name == "['embed']":
        out = 0.02 * z
    else:
        out = z / np.sqrt(struct.shape[-2])
    return out.astype(struct.dtype)


def make(structure, seed: int, shardings=None):
    """Arrays shaped like ``structure`` (a tree of ShapeDtypeStructs),
    drawn from ``seed`` in one jitted call."""
    leaves = jax.tree_util.tree_leaves_with_path(structure)
    treedef = jax.tree_util.tree_structure(structure)

    def draw(k):
        ks = jax.random.split(k, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_draw(p, s, ks[i]) for i, (p, s) in enumerate(leaves)])

    return jax.jit(draw, out_shardings=shardings)(key(seed))
