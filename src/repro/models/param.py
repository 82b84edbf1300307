"""Parameter specification trees.

A model definition is a nested dict of :class:`Spec` leaves.  From one spec
tree we derive (a) initialized parameter pytrees, (b) logical-axis pytrees
for sharding, and (c) ``ShapeDtypeStruct`` pytrees for allocation-free
lowering in the multi-pod dry-run.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    """Shape + logical axes (one name or None per dim) + init recipe."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones
    scale: Optional[float] = None  # default: 1/sqrt(fan_in)
    dtype: Optional[str] = None    # override model dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x) -> bool:
    return isinstance(x, Spec)


def tree_map_specs(fn, specs):
    return jax.tree.map(fn, specs, is_leaf=_is_spec)


def init_params(specs, key: jax.Array, dtype: str):
    """Initialize a parameter pytree from a spec tree.

    Every leaf gets an independent key derived from its path, so adding or
    removing parameters never reshuffles the others.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs, is_leaf=_is_spec)
    leaves = []
    for path, spec in flat:
        path_str = "/".join(str(p) for p in path)
        # crc32, not hash(): str hashes are salted per process, and
        # every process must draw the same weights from the same key
        salt = zlib.crc32(path_str.encode()) & 0x7FFFFFFF
        k = jax.random.fold_in(key, np.uint32(salt))
        dt = jnp.dtype(spec.dtype or dtype)
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, dt)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, dt)
        else:
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            scale = spec.scale if spec.scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
            arr = (jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(dt)
        leaves.append(arr)
    return jax.tree.unflatten(treedef, leaves)


def abstract_params(specs, dtype: str):
    """ShapeDtypeStruct tree — for .lower() without allocation."""
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype or dtype)), specs)


def axes_tree(specs):
    """Tree of logical-axis tuples, same structure as the param tree."""
    return tree_map_specs(lambda s: s.axes, specs)


def param_bytes(specs, dtype: str) -> int:
    total = 0
    for s in jax.tree.leaves(specs, is_leaf=_is_spec):
        total += int(np.prod(s.shape)) * jnp.dtype(s.dtype or dtype).itemsize
    return total
