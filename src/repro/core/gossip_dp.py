"""Gossip data-parallelism — the paper's algorithm promoted to datacenter
scale (beyond-paper; DESIGN.md §4).

The assigned large architectures train data-parallel over the mesh's
"data" (and "pod") axes.  Standard DP all-reduces gradients every step;
GossipDP instead treats each data-parallel group as a FEDERATED NODE
running GluADFL:

    every step:   local optimizer step on the node's shard of the batch
    every K steps: gossip mix of PARAMETERS across nodes using the
                   paper's topology mixing matrix (ring/cluster/random),
                   with the paper's active-mask asynchrony

This is exactly Algorithm 1 with "patient phone" -> "DP shard group", and
it is the collective-bound hillclimb lever in EXPERIMENTS.md §Perf: a
ring mix moves 2/N of the bytes of an all-reduce per mixing round, and
mixing every K steps amortizes it K-fold, at the cost of parameter
divergence between mixes (bounded by the topology's spectral gap).

Implementation: parameters keep their tensor-parallel sharding on
"model"; the gossip mix is expressed with ``jax.lax`` collectives over
the node axes inside shard_map, so the same code lowers single-pod
(nodes = 16 data groups) and multi-pod (nodes = 2x16 = 32 groups).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.topology import mixing_matrix, round_adjacency

PyTree = Any


def node_count(mesh: Mesh, node_axes: tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in node_axes]))


def _psum_all_combine(contrib, axis, idx):
    """Baseline combine: full psum of the (N, ...) stacked contributions,
    each node slices its own row — every device holds the N-fold temp."""
    summed = jax.lax.psum(contrib, axis)  # (N, ...) mixed for all nodes
    return summed[idx]


def _psum_scatter_combine(contrib, axis, idx):
    """Memory-scaled combine: reduce-scatter along the stacked node dim —
    with one node per shard group the (1, ...) result IS this node's row."""
    out = jax.lax.psum_scatter(contrib, axis, scatter_dimension=0, tiled=True)
    return out[0]


# combine-schedule registry, mirroring distributed._DENSE_WIRE_SCHEDULES:
# "masked" aliases the allgather combine (secure aggregation's mask
# cancellation is a trainer-level wrapper; the DP-mix wire underneath is
# the full-psum one).  The sparse-only "gather" schedule has no entry —
# gossip-DP nodes hold replicated full params, there is no row block to
# halo-rotate.
_DP_COMBINE = {
    "allgather": _psum_all_combine,
    "masked": _psum_all_combine,
    "psum": _psum_scatter_combine,
}


def gossip_mix_params(
    params: PyTree,
    mix: jnp.ndarray,
    mesh: Mesh,
    node_axes: tuple[str, ...],
    *,
    impl: str = "allgather",
):
    """Mix REPLICATED-over-node-axes parameters by M via psum weighting.

    In gossip-DP each node holds the FULL parameters, fully replicated
    over the mesh (leaves enter and leave as ``P()``) — tensor-parallel
    ("model"-sharded) parameters must go through :func:`ring_mix_params`
    with explicit ``specs`` instead.  The mix
    for node n is sum_m M[n,m] w_m: with w replicated, each participant
    contributes its own column-weighted copy and the contributions are
    summed over the node axes.  ``impl`` picks the collective:

      * ``"allgather"`` — BASELINE schedule: full ``psum`` of the
        (N, ...) stacked contributions, then each node slices its own
        row.  Every device holds an N-times-parameters temp (the same
        memory cliff as an all-gather, hence the shared knob name).
      * ``"psum"``      — memory-scaled: ``psum_scatter`` hands each
        node ONLY its own mixed row, so the temp never exceeds one
        parameter copy per device beyond the local shard.

    (The ring fast path in ``ring_mix_params`` cuts this to 2 permutes.)
    """
    if impl not in _DP_COMBINE:
        raise ValueError(f"impl {impl!r} not in {tuple(_DP_COMBINE)}")
    combine = _DP_COMBINE[impl]
    axis = node_axes if len(node_axes) > 1 else node_axes[0]

    def leaf(w):
        def body(w_local, mix_local):
            # node id along the (possibly compound) axis
            idx = jax.lax.axis_index(axis)
            # contribution of THIS node to everyone: w * M[:, idx]
            col = mix_local[:, idx]
            contrib = w_local[None, ...] * col.reshape((-1,) + (1,) * w_local.ndim)
            return combine(contrib, axis, idx)

        # node-replicated leaves: P() on both sides (tensor-parallel
        # sharding goes through ring_mix_params' explicit `specs`)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P()),
            out_specs=P(),
            check_vma=False,
        )(w, mix)

    return jax.tree.map(leaf, params)


def ring_mix_params(params: PyTree, mesh: Mesh, node_axes: tuple[str, ...],
                    specs: PyTree | None = None):
    """Ring gossip of node-replicated params: two collective_permutes of
    each device's LOCAL tensor-parallel shard + local average — the
    cheapest mixing schedule (2 neighbour transfers of P_local, equal to
    one ring all-reduce's wire at K=1 and K-fold cheaper amortized).

    ``specs``: PartitionSpec tree for the params' tensor-parallel
    sharding (e.g. from ``arch.sharding.param_pspecs``).  Without it the
    leaves are treated as replicated, which forces shard_map to
    all-gather tensor-sharded params first — 20x the wire (§Perf H3
    iteration 1, refuted variant).
    """
    axis = node_axes if len(node_axes) > 1 else node_axes[0]
    n = node_count(mesh, node_axes)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]

    def leaf(w, spec):
        spec = spec if spec is not None else P(*(None,) * w.ndim)

        def body(w_local):
            if n <= 1:
                return w_local
            w_prev = jax.lax.ppermute(w_local, axis, fwd)
            if n == 2:
                # fwd and bwd would deliver the SAME single peer — the
                # three-way average would weight it 2/3 instead of the
                # uniform 1/2 over {self, peer} that
                # mixing_matrix(ring_adjacency(2), ...) produces
                return (w_local + w_prev) / 2.0
            w_next = jax.lax.ppermute(w_local, axis, bwd)
            return (w_local + w_prev + w_next) / 3.0

        return jax.shard_map(
            body, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
        )(w)

    p_leaves, treedef = jax.tree.flatten(params)
    if specs is None:
        s_leaves = [None] * len(p_leaves)
    else:
        s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        if len(s_leaves) != len(p_leaves):
            raise ValueError(
                f"specs tree has {len(s_leaves)} leaves but params has "
                f"{len(p_leaves)} — a zip would silently truncate; pass "
                f"one PartitionSpec per parameter leaf"
            )
    return jax.tree.unflatten(
        treedef, [leaf(w, s) for w, s in zip(p_leaves, s_leaves)]
    )


class GossipDPSchedule:
    """Host-side schedule: which rounds mix, and with which matrix.

    ``schedule`` picks the participation process the mixing matrix is
    drawn under — ``"bernoulli"`` (iid per round, the default) or
    ``"markov"`` (sticky busy/free: ``async_sched.markov_active`` with
    the previous round's mask carried across ``next_mix`` calls) — the
    same two schedules the trainer's sweep axis batches."""

    def __init__(self, topology: str, num_nodes: int, comm_batch: int = 7,
                 mix_every: int = 1, inactive_ratio: float = 0.0, seed: int = 0,
                 schedule: str = "bernoulli", p_stay_active: float = 0.9,
                 p_stay_inactive: float = 0.7):
        if schedule not in ("bernoulli", "markov"):
            raise ValueError(f"unknown schedule {schedule!r}")
        self.topology = topology
        self.num_nodes = num_nodes
        self.comm_batch = comm_batch
        self.mix_every = mix_every
        self.inactive_ratio = inactive_ratio
        self.schedule = schedule
        self.p_stay_active = p_stay_active
        self.p_stay_inactive = p_stay_inactive
        self.key = jax.random.PRNGKey(seed)
        # the chain starts all-active, matching the trainer's convention
        # (fresh FLState staleness is all zeros)
        self.prev_active = jnp.ones((num_nodes,), jnp.float32)

    def should_mix(self, step: int) -> bool:
        return (step + 1) % self.mix_every == 0

    def next_mix(self) -> jnp.ndarray:
        self.key, k_top, k_act = jax.random.split(self.key, 3)
        from repro.core.async_sched import bernoulli_active, markov_active

        if self.schedule == "markov":
            active = markov_active(
                k_act, self.prev_active, self.p_stay_active, self.p_stay_inactive
            )
        else:
            active = bernoulli_active(k_act, self.num_nodes, self.inactive_ratio)
        self.prev_active = active
        adj = round_adjacency(
            self.topology, self.num_nodes, k_top, self.comm_batch
        )
        return mixing_matrix(adj, active, self.comm_batch)
