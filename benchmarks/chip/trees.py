"""Named leaves of a parameter tree: ``(path, index)`` where ``path`` is
the keys down the nested dicts and ``index`` an optional index into the
array found there (a layer of a stacked weight). Used by the adapters
and the references; imports nothing of the program."""

from __future__ import annotations


def get_leaf(tree, spec):
    path, index = spec
    for key in path:
        tree = tree[key]
    return tree if index is None else tree[tuple(index)]


def get_leaves(tree, specs: dict) -> dict:
    return {name: get_leaf(tree, spec) for name, spec in specs.items()}


def with_leaves(tree, specs: dict, leaves: dict):
    """``tree`` with each named leaf swapped in. Dicts are rebuilt along
    the paths only; every other array stays the same object."""
    def put(node, keys, index, value):
        if keys:
            return {**node, keys[0]: put(node[keys[0]], keys[1:], index,
                                         value)}
        return value if index is None else node.at[tuple(index)].set(value)
    for name, (path, index) in specs.items():
        tree = put(tree, tuple(path), index, leaves[name])
    return tree


def as_shapes(tree, sharding):
    """The tree's arrays as ``jax.ShapeDtypeStruct``s with ``sharding``:
    what a compile without devices takes for arguments."""
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)
