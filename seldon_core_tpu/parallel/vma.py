"""Varying-manual-axes helpers (shard_map typing).

Inside shard_map, every value's aval carries the set of mesh axes it
varies over; scan carries and binary ops must agree on it. These helpers
let code promote values to a target variance without hand-maintaining
axis lists.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def pvary(x, axes):
    """Promote x to vary over `axes` (only the ones it doesn't already)."""
    from jax import lax

    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    axes = tuple(a for a in axes if a not in vma_of(x))
    if not axes:
        return x
    return lax.pcast(x, axes, to="varying")


def vma_of(x) -> frozenset:
    import jax

    return jax.typeof(x).vma


def tree_vma(tree) -> frozenset:
    import jax

    out: frozenset = frozenset()
    for leaf in jax.tree_util.tree_leaves(tree):
        out = out | vma_of(leaf)
    return out
