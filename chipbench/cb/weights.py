"""Seeded random weights, made on the device in one jitted call per model.

The values follow only the benchmark's seed; the tree's layout (leaf names
and shapes) is the one the engine consumes.  Every matrix is LeCun-normal
over its input axis, the token embedding is N(0, 0.02^2), and every norm
scale is ``1 + N(0, 0.1^2)`` (stored as the offset from 1), so that a norm
left out or applied twice shows in the comparison with the reference.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def _leaf_init(key, path, spec):
    names = [getattr(k, "key", str(k)) for k in path]
    shape, dtype = spec.shape, spec.dtype
    stacked = "layers" in names          # leading axis = layer
    body = shape[1:] if stacked else shape
    if len(body) == 1:                   # a norm scale, stored as 1 + w
        return (0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if names[-1] == "tok":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    fan_in = body[-2]                    # [.., in, out]; experts [E, in, out]
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def make_params(specs, key_int: int, device=None):
    """A param tree shaped like ``specs`` (a ShapeDtypeStruct pytree), every
    leaf drawn from ``key_int`` and its path, in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf_init(k, p, s) for k, (p, s) in zip(keys, flat)])

    out = None
    if device is not None:
        out = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=out)(jax.random.PRNGKey(key_int))
