"""Gossip parameter mixing — the paper's Step 2+3 as one primitive.

Given stacked node parameters (leaves ``(N, ...)``) and the round's
row-stochastic mixing matrix ``M`` (from ``topology.mixing_matrix``),
compute ``W <- M @ W``.

Three interchangeable implementations:
  * ``gossip_mix_tree``    — jnp einsum per leaf at ``HIGHEST`` precision
                             (f32-exact on the TPU; the one-device
                             default below the dense/sparse crossover),
  * ``gossip_mix_kernel``  — Pallas blocked kernel (repro.kernels),
  * ``sharded_gossip_mix`` — shard_map over a node-sharded axis
                             (repro.core.distributed) for fleet scale.

Each has a ``*_sparse`` twin taking ``core.topology.neighbor_table``'s
(N, B+1) ``(idx, wgt)`` representation instead of the dense (N, N)
matrix — same math to float tolerance (bitwise for inactive rows, which
take a where-select copy), O(N·B·D) FLOPs instead of O(N²·D), but B+1
gathered copies of the parameters where the dense form reads them once.
"""
from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from repro.utils.pytree import tree_weighted_mix

PyTree = Any


def gossip_mix_tree(stacked_params: PyTree, mix: jnp.ndarray) -> PyTree:
    """Dense implementation: one f32-exact einsum per leaf."""
    return tree_weighted_mix(stacked_params, mix)


def gossip_mix_kernel(stacked_params: PyTree, mix: jnp.ndarray, active=None) -> PyTree:
    """Pallas-kernel implementation; identical math, VMEM-blocked."""
    from repro.kernels.ops import gossip_mix as _kernel_mix

    import jax

    def mix_leaf(l):
        flat = l.reshape(l.shape[0], -1)
        out = _kernel_mix(mix, flat, active)
        return out.reshape(l.shape).astype(l.dtype)

    return jax.tree.map(mix_leaf, stacked_params)


def gossip_mix_dp_kernel(
    stacked_params: PyTree, noise: PyTree, mix: jnp.ndarray, active=None
) -> PyTree:
    """Fused local-DP gossip (Pallas): noise-broadcast + mix +
    clean-self-restore in ONE pass per leaf —
    ``out = mix @ (w + noise) - diag(mix) * noise`` — instead of the
    three tree_map passes of the composed path.  ``noise`` is a pytree
    shaped like ``stacked_params`` (already scaled by sigma)."""
    from repro.kernels.ops import gossip_mix_dp as _kernel_dp

    import jax

    def mix_leaf(l, z):
        flat = l.reshape(l.shape[0], -1)
        out = _kernel_dp(mix, flat, z.reshape(z.shape[0], -1), active)
        return out.reshape(l.shape).astype(l.dtype)

    return jax.tree.map(mix_leaf, stacked_params, noise)


def sharded_gossip_mix(stacked_params: PyTree, mix: jnp.ndarray, active=None, **kw) -> PyTree:
    """Device-parallel implementation (re-export; see
    :func:`repro.core.distributed.sharded_gossip_mix`)."""
    from repro.core.distributed import sharded_gossip_mix as _sharded

    return _sharded(stacked_params, mix, active, **kw)


def gossip_mix_sparse_tree(
    stacked_params: PyTree, idx: jnp.ndarray, wgt: jnp.ndarray, active=None
) -> PyTree:
    """Sparse reference implementation: gather the B+1 referenced rows
    per output row and weight-sum them — ``out[n] = Σ_b wgt[n,b] ·
    w[idx[n,b]]``.  With ``active`` given, inactive rows take a
    where-select copy (bit-exact even against NaN/Inf in active rows);
    without it the table's identity rows (wgt ``[1, 0, ...]``) already
    make them float-exact copies."""
    import jax

    def mix_leaf(l):
        flat = l.reshape(l.shape[0], -1).astype(jnp.float32)
        out = jnp.einsum("nb,nbd->nd", wgt.astype(jnp.float32), flat[idx])
        if active is not None:
            out = jnp.where((active > 0)[:, None], out, flat)
        return out.reshape(l.shape).astype(l.dtype)

    return jax.tree.map(mix_leaf, stacked_params)


def gossip_mix_sparse_kernel(
    stacked_params: PyTree, idx: jnp.ndarray, wgt: jnp.ndarray, active=None
) -> PyTree:
    """Pallas sparse gather-mix per leaf (repro.kernels.ops)."""
    from repro.kernels.ops import gossip_mix_sparse as _kernel_sparse

    import jax

    def mix_leaf(l):
        flat = l.reshape(l.shape[0], -1)
        out = _kernel_sparse(idx, wgt, flat, active)
        return out.reshape(l.shape).astype(l.dtype)

    return jax.tree.map(mix_leaf, stacked_params)


def gossip_mix_sparse_dp_kernel(
    stacked_params: PyTree,
    noise: PyTree,
    idx: jnp.ndarray,
    wgt: jnp.ndarray,
    active=None,
) -> PyTree:
    """Fused sparse local-DP gossip (Pallas): noised-neighbour gather +
    clean-self-restore in one pass per leaf — ``out[n] = Σ_b
    wgt[n,b]·(w+z)[idx[n,b]] − wgt[n,0]·z[n]`` (slot 0 is self, so
    ``wgt[:, 0]`` IS the densified diagonal)."""
    from repro.kernels.ops import gossip_mix_sparse_dp as _kernel_dp

    import jax

    def mix_leaf(l, z):
        flat = l.reshape(l.shape[0], -1)
        out = _kernel_dp(idx, wgt, flat, z.reshape(z.shape[0], -1), active)
        return out.reshape(l.shape).astype(l.dtype)

    return jax.tree.map(mix_leaf, stacked_params, noise)


def sharded_gossip_mix_sparse(
    stacked_params: PyTree, idx: jnp.ndarray, wgt: jnp.ndarray, active=None, **kw
) -> PyTree:
    """Device-parallel sparse implementation (re-export; see
    :func:`repro.core.distributed.sharded_gossip_mix_sparse`)."""
    from repro.core.distributed import sharded_gossip_mix_sparse as _sharded

    return _sharded(stacked_params, idx, wgt, active, **kw)


def sharded_gossip_mix_gather(
    stacked_params: PyTree, idx: jnp.ndarray, wgt: jnp.ndarray, active=None, **kw
) -> PyTree:
    """Fully sharded gather-table implementation (re-export; see
    :func:`repro.core.distributed.sharded_gossip_mix_gather`) — the
    ``gossip_impl="gather"`` schedule with no gathered (N, D) spike."""
    from repro.core.distributed import sharded_gossip_mix_gather as _sharded

    return _sharded(stacked_params, idx, wgt, active, **kw)


def gossip_mix_masked(mixed: PyTree, idx: jnp.ndarray, wgt: jnp.ndarray, key) -> PyTree:
    """Secure-aggregation wrapper (``gossip_impl="masked"``): add the
    pairwise-mask cancellation term of ``core.secure_agg`` to an
    already-mixed state.  The term is EXACTLY ``+0.0`` everywhere (the
    uniform-row-weight masks pair up as exact IEEE negations), so the
    result is bit-identical to ``mixed`` while the per-edge mask
    generation — the priced overhead — stays live in the program.
    ``(idx, wgt)`` is the round's ``(N, B+1)`` neighbor table and ``key``
    the round's mask stream key; works after ANY base mixer (tree /
    kernel / sharded, dense or sparse)."""
    import jax

    from repro.core.secure_agg import masked_mix_zero

    zero = masked_mix_zero(mixed, idx, wgt, key)
    return jax.tree.map(jnp.add, mixed, zero)
