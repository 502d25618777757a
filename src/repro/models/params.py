"""Parameter pytree helpers.

Models are pure-JAX: ``init`` functions build pytrees whose leaves are
``P(value, axes)`` — the array plus its *logical* sharding axes (names like
"embed", "ff", "heads", "vocab", "experts"; ``None`` = replicated dim).
``split_params`` separates the tree into (values, axes) so apply functions
see plain arrays while the launcher resolves axes → PartitionSpec via
distributed/sharding.py.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class P(NamedTuple):
    value: jax.Array
    axes: Tuple[Optional[str], ...]


def is_p(x) -> bool:
    return isinstance(x, P)


def split_params(tree):
    values = jax.tree_util.tree_map(lambda p: p.value, tree, is_leaf=is_p)
    axes = jax.tree_util.tree_map(lambda p: p.axes, tree, is_leaf=is_p)
    return values, axes


def eval_shape_with_axes(init_fn):
    """eval_shape a P-pytree init function: returns (value structs, axes).

    The logical-axes tree (static strings) is captured via a side channel
    during the abstract trace so nothing is ever allocated."""
    box = {}

    def values_only():
        values, axes = split_params(init_fn())
        box["axes"] = axes
        return values

    struct = jax.eval_shape(values_only)
    return struct, box["axes"]


def dense_init(
    key: jax.Array,
    shape: Tuple[int, ...],
    axes: Tuple[Optional[str], ...],
    *,
    fan_in: Optional[int] = None,
    scale: float = 1.0,
    dtype=jnp.float32,
) -> P:
    """Truncated-normal init with 1/sqrt(fan_in) scaling."""
    fan_in = fan_in if fan_in is not None else shape[0]
    std = scale / np.sqrt(max(fan_in, 1))
    value = std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)
    return P(value, axes)


def zeros_init(shape, axes, dtype=jnp.float32) -> P:
    return P(jnp.zeros(shape, dtype), axes)


def ones_init(shape, axes, dtype=jnp.float32) -> P:
    return P(jnp.ones(shape, dtype), axes)


def const_init(value, axes) -> P:
    return P(jnp.asarray(value), axes)


def stack_layer_params(per_layer_trees):
    """Stack a list of identical param trees along a new leading 'layers' dim."""

    def stack(*ps):
        vals = jnp.stack([p.value for p in ps])
        return P(vals, ("layers",) + ps[0].axes)

    return jax.tree_util.tree_map(stack, *per_layer_trees, is_leaf=is_p)
