"""Declarative parameter trees.

A model describes its parameters as a pytree of :class:`ParamDecl` leaves.  From
that single declaration we derive (a) initialized parameter arrays, (b)
PartitionSpec trees for pjit in/out shardings, and (c) ShapeDtypeStructs for
AOT lowering — guaranteeing the three never drift apart.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sharding import ShardingRules
from jax.sharding import Mesh, NamedSharding, PartitionSpec


@dataclasses.dataclass(frozen=True)
class ParamDecl:
    """Declaration of a single parameter tensor."""

    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]   # logical axis name per dim
    init: str = "normal"                  # normal | zeros | ones | scaled
    scale: float = 0.02
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def is_decl(x) -> bool:
    return isinstance(x, ParamDecl)


def _init_leaf(decl: ParamDecl, key: jax.Array) -> jax.Array:
    if decl.init == "zeros":
        return jnp.zeros(decl.shape, decl.dtype)
    if decl.init == "ones":
        return jnp.ones(decl.shape, decl.dtype)
    if decl.init == "scaled":
        # variance-scaled (fan-in) init for projections
        fan_in = decl.shape[-2] if len(decl.shape) >= 2 else decl.shape[-1]
        std = 1.0 / np.sqrt(max(fan_in, 1))
        return (std * jax.random.normal(key, decl.shape, jnp.float32)).astype(decl.dtype)
    return (decl.scale * jax.random.normal(key, decl.shape, jnp.float32)).astype(decl.dtype)


# A leaf this large is drawn under jit: XLA fuses the fp32 normal draw into
# the bf16 cast, so a full-width stacked leaf (24 x 3840 x 10240) never holds
# its ~3.8 GB fp32 copy on the device.  Smaller leaves keep the eager draw.
_JIT_INIT_ELEMS = 1 << 24
_init_leaf_jit = jax.jit(_init_leaf, static_argnums=0)


def init_params(decls, key: jax.Array):
    """Initialize a pytree of ParamDecl with per-leaf folded keys."""
    leaves, treedef = jax.tree.flatten(decls, is_leaf=is_decl)
    out = []
    for i, leaf in enumerate(leaves):
        init = (_init_leaf_jit if np.prod(leaf.shape) >= _JIT_INIT_ELEMS
                else _init_leaf)
        out.append(init(leaf, jax.random.fold_in(key, i)))
    return jax.tree.unflatten(treedef, out)


def param_specs(decls, rules: ShardingRules):
    """PartitionSpec tree matching the declaration tree."""
    return jax.tree.map(lambda d: rules.spec(d.logical), decls, is_leaf=is_decl)


def param_shardings(decls, mesh: Mesh, rules: ShardingRules):
    return jax.tree.map(
        lambda d: NamedSharding(mesh, rules.spec(d.logical)), decls, is_leaf=is_decl
    )


def param_structs(decls):
    """ShapeDtypeStruct tree (for AOT .lower without allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), decls, is_leaf=is_decl
    )


def param_structs_sharded(decls, mesh: Mesh, rules: ShardingRules):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(
            d.shape, d.dtype, sharding=NamedSharding(mesh, rules.spec(d.logical))
        ),
        decls,
        is_leaf=is_decl,
    )


def count_params(decls) -> int:
    leaves = jax.tree.leaves(decls, is_leaf=is_decl)
    return int(sum(np.prod(l.shape) for l in leaves))


def tree_bytes(tree) -> int:
    return int(
        sum(np.prod(x.shape) * jnp.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree))
    )
