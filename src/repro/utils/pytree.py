"""Pytree utilities used throughout the framework.

The federated core treats model parameters as either
  * a pytree of arrays (one node), or
  * a *stacked* pytree whose leaves carry a leading node axis ``(N, ...)``.

Everything here is pure JAX and jit/vmap friendly.
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

PyTree = Any


def tree_vector_size(tree: PyTree) -> int:
    """Total number of scalar parameters in the tree."""
    return int(sum(math.prod(l.shape) for l in jax.tree.leaves(tree)))


def tree_to_vector(tree: PyTree) -> jnp.ndarray:
    """Flatten a pytree of arrays into a single 1-D vector (row-major)."""
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([jnp.ravel(l) for l in leaves]) if leaves else jnp.zeros((0,))


def vector_to_tree(vec: jnp.ndarray, like: PyTree) -> PyTree:
    """Inverse of :func:`tree_to_vector` given a template tree."""
    leaves, treedef = jax.tree.flatten(like)
    out, pos = [], 0
    for l in leaves:
        n = math.prod(l.shape)
        out.append(jnp.reshape(vec[pos : pos + n], l.shape).astype(l.dtype))
        pos += n
    return jax.tree.unflatten(treedef, out)


def tree_stack(trees: list[PyTree]) -> PyTree:
    """Stack a list of identically-structured pytrees along a new axis 0."""
    return jax.tree.map(lambda *ls: jnp.stack(ls, axis=0), *trees)


def tree_unstack(tree: PyTree) -> list[PyTree]:
    """Inverse of :func:`tree_stack`."""
    n = jax.tree.leaves(tree)[0].shape[0]
    return [jax.tree.map(lambda l, i=i: l[i], tree) for i in range(n)]


def tree_index(tree: PyTree, i) -> PyTree:
    """Index the leading (node) axis of a stacked pytree."""
    return jax.tree.map(lambda l: l[i], tree)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree.map(lambda l: l * s, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_l2_norm(tree: PyTree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(tree)))


def tree_mean(tree: PyTree, axis: int = 0) -> PyTree:
    """Mean over the leading (node) axis of a stacked pytree."""
    return jax.tree.map(lambda l: jnp.mean(l, axis=axis), tree)


def tree_weighted_mix(stacked: PyTree, mix: jnp.ndarray) -> PyTree:
    """Apply a row-stochastic mixing matrix to a stacked pytree.

    ``stacked`` leaves have shape ``(N, ...)``; ``mix`` is ``(N, N)`` with
    row n holding node n's averaging weights.  Returns the mixed stacked
    tree: ``out[n] = sum_m mix[n, m] * stacked[m]``.

    This is the dense tree backend of the paper's gossip step; the
    Pallas kernel in ``repro.kernels.gossip_mix`` computes the same
    contraction blocked for VMEM.  The einsum runs at ``HIGHEST``
    precision: f32-exact on the TPU, as the sparse gather-sum is (the
    default would round every parameter to bfloat16 each round), and
    what f32 dots already do on the CPU.
    """

    def mix_leaf(l: jnp.ndarray) -> jnp.ndarray:
        flat = l.reshape(l.shape[0], -1)
        mixed = jnp.einsum(
            "nm,md->nd", mix.astype(jnp.float32), flat.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return mixed.astype(l.dtype).reshape(l.shape)

    return jax.tree.map(mix_leaf, stacked)


def tree_map_with_path_names(fn: Callable[[str, jnp.ndarray], Any], tree: PyTree) -> PyTree:
    """tree.map with a '/'-joined string path as first argument."""

    def _fn(path, leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        return fn(name, leaf)

    return jax.tree_util.tree_map_with_path(_fn, tree)
