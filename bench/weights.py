"""Random weights drawn on the device from ``--seed``, in one jitted call.

The benchmark makes the weights, not the program: the program's tree layout
is read with ``jax.eval_shape`` (shapes only, nothing runs), and every leaf
is drawn from a key derived from the seed and the leaf's path. The plain
reference (``bench/reference.py``) reads the same arrays, so both sides see
identical weights and neither takes anything the other made.

Scales follow GPT-2's initialisation: N(0, 0.02) for matrices and
embeddings, N(0, 0.02 / sqrt(2 L)) for the two projections that write the
residual stream (``wo``, ``w_out``), and small random biases and norm
scales so that every leaf carries signal.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    words = np.random.SeedSequence([int(seed) % 2**64, stream]) \
        .generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _leaf(key, name: str, shape, dtype, num_layers: int):
    base = name.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if base == "scale":                                  # norm gains
        x = 1.0 + 0.05 * z
    elif base in ("bq", "bk", "bv") or (base == "b" and len(shape) == 1):
        x = 0.02 * z
    elif base in ("wo", "w_out"):
        x = (0.02 / math.sqrt(2 * num_layers)) * z
    else:
        x = 0.02 * z
    return x.astype(dtype)


def make_params(shapes, seed: int, num_layers: int):
    """Draw a tree shaped like ``shapes`` (a pytree of ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_str(p) for p, _ in flat]

    def draw(key):
        leaves = [_leaf(jax.random.fold_in(key, zlib.crc32(n.encode())),
                        n, s.shape, s.dtype, num_layers)
                  for n, (_, s) in zip(names, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(seed_key(seed))
