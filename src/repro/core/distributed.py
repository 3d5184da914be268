"""Distributed gossip — the paper's communication step on a device mesh.

At fleet scale the federation axis N is sharded over the mesh's node axis
(single-pod: "data"; multi-pod: "pod"+"data").  The per-round mix
``W <- M_t @ W`` then needs real collectives.  Topology-aware lowering:

  * ring      — each node needs only neighbours i±1: TWO
                ``jax.lax.ppermute`` (collective-permute) hops, cost
                O(D) per link — the cheapest possible gossip;
  * cluster / random / star / full — general row-stochastic mix, with two
                interchangeable schedules behind ``impl=``:
      - ``"allgather"``  the node axis is all-gathered and contracted
                locally (MXU matmul).  For node counts in this paper's
                range (<= 256 shards) a single all-gather beats emulated
                point-to-point sends on TPU ICI (dense collectives are
                what the fabric is good at) — but every device
                materializes the full (N, D) federation, so per-device
                memory does NOT shrink as the mesh grows;
      - ``"psum"``       psum-of-local-contributions: each shard
                contracts its LOCAL rows against its column block of the
                mixing matrix and the partial products are summed with a
                reduce-scatter (``jax.lax.psum_scatter``), which hands
                each device only its own (N/shards, D) output rows.  No
                device ever holds the gathered node axis, so per-device
                working set scales O(N/shards · D) — the multi-host /
                big-model schedule.

All paths are ``shard_map``s so the collective schedule is explicit and
the dry-run can count its bytes.

Representation: the general paths above contract a dense (N, N) mixing
matrix.  ``gossip_repr="sparse"`` (:func:`sharded_gossip_mix_sparse`)
replaces it with ``core.topology.neighbor_table``'s (N, B+1) index/weight
table — same all-gather wire, but the local contraction gathers only the
B+1 referenced rows per output row, dropping per-device flops from
O(N/shards · N · D) to O(N/shards · B · D) and eliminating every (N, N)
operand.

Sweep batching: every shard body below is written dim-relative (ellipsis
einsums, gather/scatter on the second-to-last axis), so the SAME bodies
run under a 2-D ``("grid", "node")`` sweep mesh
(``launch.mesh.make_sweep_mesh``): the grid axis BATCHES — each shard
holds a ``(G/grid, N/node, D)`` block and no collective ever crosses
``"grid"`` — while the node axis keeps carrying the gossip collectives.
:func:`sharded_gossip_mix` accepts grid-stacked ``(G, N, ...)`` inputs
and issues one shard_map with ``P("grid", ...)`` in_specs; the trainer's
swept-sharded path reaches the identical lowering through
``jax.vmap(..., spmd_axis_name="grid")`` over the per-scenario call.

Multi-host: every shard body above indexes the node axis GLOBALLY — the
mixing-matrix row/column blocks are sliced by shard position on the mesh,
not by process — so the same programs lower unchanged when the federation
mesh spans ``jax.distributed`` processes (the all-gather / psum-scatter /
ppermute become cross-host transfers).  What IS per-process is data
residence: :func:`addressable_node_rows` names the contiguous global row
interval whose shards live on the calling process, which is the contract
``launch.multihost.place_federation`` fulfills when it materializes each
host's CGM windows.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


PyTree = Any

# interchangeable schedules for the general (non-ring) sharded mix.
# "masked" is pairwise-masked secure aggregation (core.secure_agg): on
# the wire it rides the allgather schedule — the mask cancellation term
# is added OUTSIDE the collective by the trainer, so every shard body
# below stays schedule-only.  "gather" is the sparse-only gather-table
# schedule (:func:`sharded_gossip_mix_gather`): a ppermute halo rotation
# that never materializes the gathered (N, D) federation
GOSSIP_IMPLS = ("allgather", "psum", "masked", "gather")

# mixing-operator representations: dense (N, N) matrix vs (N, B+1)
# neighbor table (core.topology.neighbor_table)
GOSSIP_REPRS = ("dense", "sparse")


def ring_gossip_shard(w, active, *, axis: str, n_shards: int, self_w: float = 1.0 / 3.0):
    """shard_map body: ring mix via two collective-permutes.

    ``w``: local block of stacked params, node dim second-to-last with
    ``k = nodes-per-shard`` CONSECUTIVE global rows (1 when fully
    sharded; a leading grid-block dim batches through).  ``active``: the
    matching (..., k, 1) activity-flag block.  Inactive nodes keep their
    row; active nodes average self with *active* ring neighbours.
    ``n_shards`` is static (the ppermute source/target lists need a
    Python int — the caller reads it off the mesh).

    When ``k > 1`` a row's ring neighbours ``i±1`` mostly live INSIDE
    the same block — only the block-boundary rows talk to the adjacent
    shards.  The shifted views are therefore built by an intra-block
    roll stitched to a single-row boundary exchange (``k``'s worth of
    ppermute traffic would be wrong AND wasteful: permuting whole blocks
    would hand row ``i`` the params of row ``i±k``).
    """
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    bwd = [((i + 1) % n_shards, i) for i in range(n_shards)]

    def ring_shift(v):
        """(v_prev, v_next): row i's view of global rows i-1 and i+1."""
        prev_last = jax.lax.ppermute(v[..., -1:, :], axis, fwd)
        next_first = jax.lax.ppermute(v[..., :1, :], axis, bwd)
        v_prev = jnp.concatenate([prev_last, v[..., :-1, :]], axis=-2)
        v_next = jnp.concatenate([v[..., 1:, :], next_first], axis=-2)
        return v_prev, v_next

    w_prev, w_next = ring_shift(w)
    a_prev, a_next = ring_shift(active)
    num = w + a_prev * w_prev + a_next * w_next
    den = 1.0 + a_prev + a_next
    mixed = num / den
    return jnp.where(active > 0, mixed, w)


def general_gossip_shard(w, mix_rows, *, axis: str):
    """shard_map body: general mix. ``mix_rows`` is this shard's
    (..., N/s, N) rows of the mixing matrix; the node axis of ``w`` is
    all-gathered and contracted against them.  The gather yields
    (..., N, D) with D FULL — the in_specs shard only the node axis, so
    the trailing parameter dim is never split.  Leading dims (the sweep
    mesh's local grid block) batch straight through the ellipsis."""
    w_all = jax.lax.all_gather(w, axis, tiled=True, axis=w.ndim - 2)
    return jnp.einsum(
        "...km,...md->...kd", mix_rows, w_all.astype(jnp.float32)
    ).astype(w.dtype)


def psum_gossip_shard(w, mix_cols, *, axis: str):
    """shard_map body: memory-scaled general mix.  ``mix_cols`` is this
    shard's (..., N, N/s) COLUMN block of the mixing matrix; ``w`` its
    local (..., N/s, D) rows.  The shard's contribution to EVERY output
    row is one local matmul, and the partial products are combined with
    a reduce-scatter that leaves each shard holding only its own rows —
    the node axis is never gathered on any device.  Leading dims (the
    sweep mesh's local grid block) batch straight through.

    fp32 accumulation matches ``general_gossip_shard`` so the two impls
    agree to float tolerance on the same mixing matrix.
    """
    contrib = jnp.einsum("...nm,...md->...nd", mix_cols, w.astype(jnp.float32))
    out = jax.lax.psum_scatter(
        contrib, axis, scatter_dimension=contrib.ndim - 2, tiled=True
    )
    return out.astype(w.dtype)


def sparse_gossip_shard(w, idx, wgt, *, axis: str):
    """shard_map body: neighbor-table (sparse) mix.  ``idx``/``wgt`` are
    this shard's (..., N/s, B+1) table rows; the node axis of ``w`` is
    all-gathered (same wire as ``general_gossip_shard``) but the local
    contraction gathers only the B+1 referenced rows per output row —
    O(N/s · B · D) flops instead of O(N/s · N · D).  Per-device MEMORY
    still holds the gathered (N, D) federation, like the allgather impl;
    the flop (and dense-matrix storage) saving is the point.  Leading
    dims (the sweep mesh's local grid block) batch straight through:
    every index below is dim-relative."""
    w_all = jax.lax.all_gather(w, axis, tiled=True, axis=w.ndim - 2)
    # (..., 1, N, D) gathered rows indexed by (..., k, B+1, 1) -> (..., k, B+1, D)
    rows = jnp.take_along_axis(
        w_all.astype(jnp.float32)[..., None, :, :], idx[..., None], axis=-2
    )
    return jnp.einsum("...kb,...kbd->...kd", wgt, rows).astype(w.dtype)


# wire-schedule registry for the dense sharded mix: impl knob value ->
# (shard body, which mixing-matrix block each shard holds).  "masked"
# deliberately aliases the allgather row entry — secure aggregation is a
# trainer-level wrapper (core.secure_agg adds the exact-zero mask
# cancellation after the mix) and its wire schedule IS the gathered-rows
# one, so both knob values lower to the identical program.  New dense
# schedules register here; sparse-only ones (gather tables) have their
# own entry points.
_DENSE_WIRE_SCHEDULES = {
    "allgather": (general_gossip_shard, "rows"),
    "masked": (general_gossip_shard, "rows"),
    "psum": (psum_gossip_shard, "cols"),
}


def gather_tables_gossip_shard(w, idx, wgt, *, axis: str, n_shards: int):
    """shard_map body: gather-table (sparse, fully sharded) mix.

    ``idx``/``wgt`` are this shard's (..., k, B+1) neighbor-table rows
    (``k = N / n_shards`` CONSECUTIVE global rows, matching the mesh's
    row-block placement) and ``w`` its local (..., k, D) parameter rows.
    Instead of all-gathering the node axis (the ``sparse_gossip_shard``
    wire, per-device O(N · D) memory), the LOCAL block ring-rotates
    through every shard via ``n_shards - 1`` collective-permutes: at step
    ``t`` this shard holds the rows of global shard ``(me + t) %
    n_shards`` and contracts exactly the table entries that reference
    that block — each (row, slot) pair lands in precisely one step, so
    the fp32 step-sums add up to the full B+1 contraction.  Per-device
    working set is two row blocks (resident + in-flight), O(N/shards ·
    D), with no gathered (N, D) spike anywhere — the schedule that takes
    the federation past the 10k-node wall.

    ``n_shards`` is static (ppermute needs Python-int source/target
    pairs); leading dims (the sweep mesh's local grid block) batch
    through — every index below is dim-relative.  One shard degenerates
    to the purely local contraction with zero collectives.
    """
    k = w.shape[-2]
    me = jax.lax.axis_index(axis)
    perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]
    block = w.astype(jnp.float32)
    wgt32 = wgt.astype(jnp.float32)
    acc = jnp.zeros(idx.shape[:-1] + (w.shape[-1],), jnp.float32)
    for t in range(n_shards):
        src = (me + t) % n_shards          # whose global rows `block` holds now
        local = idx - src * k              # (..., k, B+1) block-relative
        in_block = (local >= 0) & (local < k)
        safe = jnp.where(in_block, local, 0)
        # (..., 1, k, D) block rows indexed by (..., k, B+1, 1) -> (..., k, B+1, D)
        rows = jnp.take_along_axis(block[..., None, :, :], safe[..., None], axis=-2)
        acc = acc + jnp.einsum(
            "...kb,...kbd->...kd", jnp.where(in_block, wgt32, 0.0), rows
        )
        if t + 1 < n_shards:
            block = jax.lax.ppermute(block, axis, perm)
    return acc.astype(w.dtype)


def process_row_slice(sharding: NamedSharding, global_shape: tuple) -> slice:
    """The contiguous block of axis-0 GLOBAL rows whose shards live on
    THIS process's devices.  Federation meshes order devices by process,
    so each host's rows are one contiguous [lo, hi) interval; anything
    else (interleaved placement) is a bug worth failing loudly on."""
    idx = sharding.addressable_devices_indices_map(tuple(global_shape))
    if not idx:
        raise ValueError(
            f"process {jax.process_index()} owns no shards of the "
            f"federation mesh (width {sharding.mesh.shape}) — pick a node "
            f"count whose mesh width spreads over every process"
        )
    rows = sorted(
        {(s[0].start or 0, s[0].stop if s[0].stop is not None else global_shape[0])
         for s in idx.values()}
    )
    lo, hi = rows[0][0], rows[-1][1]
    covered = sum(b - a for a, b in rows)
    if covered != hi - lo:
        raise ValueError(f"non-contiguous per-process rows: {rows}")
    return slice(lo, hi)


def addressable_node_rows(mesh: Mesh, num_nodes: int) -> slice:
    """The contiguous [lo, hi) interval of GLOBAL federation rows whose
    shards are addressable from this process under ``mesh``'s first
    (node) axis.  Single-process meshes own everything; multi-host
    meshes split the interval at process boundaries (device order is by
    process, so each host's rows are contiguous — asserted by
    :func:`process_row_slice`)."""
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    return process_row_slice(sharding, (num_nodes,))


_FED_MESH_CACHE: dict = {}


def _default_federation_mesh(num_nodes: int) -> Mesh:
    """Mesh for ``sharded_gossip_mix`` when the caller passes none —
    built once per (N, device-count) pair (mesh construction at trace
    time is cheap but not free inside a scanned round body)."""
    key = (num_nodes, jax.device_count())
    if key not in _FED_MESH_CACHE:
        from repro.launch.mesh import make_federation_mesh

        _FED_MESH_CACHE[key] = make_federation_mesh(num_nodes)
    return _FED_MESH_CACHE[key]


def sharded_gossip_mix(
    stacked_params: PyTree,
    mix: jnp.ndarray,
    active: jnp.ndarray | None = None,
    *,
    mesh: Mesh | None = None,
    node_axes: tuple[str, ...] | None = None,
    grid_axis: str | None = None,
    impl: str = "allgather",
) -> PyTree:
    """Device-parallel gossip mix — drop-in peer of ``gossip_mix_tree`` /
    ``gossip_mix_kernel`` (same ``(stacked, mix[, active])`` signature).

    The federation axis N is sharded over the mesh's node axes: each
    device holds N/devices rows of every leaf plus a block of the (N, N)
    mixing matrix.  ``impl`` selects the collective schedule:

      * ``"allgather"`` — gather the node axis once per leaf and contract
        locally against this shard's mix ROWS (``general_gossip_shard``);
        cheapest latency on ICI but per-device memory stays O(N · D);
      * ``"psum"``      — contract local rows against this shard's mix
        COLUMNS and reduce-scatter the partial products
        (``psum_gossip_shard``); per-device memory O(N/shards · D);
      * ``"masked"``    — pairwise-masked secure aggregation: the wire
        schedule is allgather, and the trainer adds the mask
        cancellation term (``core.secure_agg``) outside this collective.

    With no ``mesh`` a cached 1-axis ``("node",)`` mesh over the largest
    device count dividing N is used (``launch.mesh.make_federation_mesh``).

    Grid batching (the sweep engine's second engine): pass grid-stacked
    leaves ``(G, N, ...)``, per-scenario mixing matrices ``(G, N, N)``
    (+ ``(G, N)`` active masks) and a 2-D ``("grid", "node")`` mesh from
    ``launch.mesh.make_sweep_mesh`` — auto-detected when the mesh has a
    ``"grid"`` axis and ``mix`` is 3-D, or forced via ``grid_axis=``.
    The single shard_map then carries ``P("grid", ...)`` in_specs: the
    grid axis purely BATCHES (no collective ever crosses it) while the
    node-axis collectives run per scenario block.  Scenario/state shape
    mismatches fail here at trace time (leading-dim assertion below)
    instead of inside the collective.

    Jit/scan friendly: mesh resolution happens at trace time, so the
    whole FL round — including this collective — compiles into one
    program (the trainer's ``mixer="sharded"`` path).
    """
    if impl not in _DENSE_WIRE_SCHEDULES:
        raise ValueError(
            f"impl {impl!r} not in {tuple(_DENSE_WIRE_SCHEDULES)} "
            f"(dense wire schedules; 'gather' is sparse-only — "
            f"sharded_gossip_mix_gather)"
        )
    shard_body, mix_block = _DENSE_WIRE_SCHEDULES[impl]
    if mesh is None:
        mesh = _default_federation_mesh(mix.shape[0])
    axes = node_axes or tuple(
        a for a in mesh.axis_names if a not in ("model", "grid")
    )
    axis = axes if len(axes) > 1 else axes[0]
    if grid_axis is None and mix.ndim == 3 and "grid" in mesh.axis_names:
        grid_axis = "grid"
    g = (grid_axis,) if grid_axis else ()
    lead = 1 + len(g)  # stacked leading dims: [grid,] node
    if mix.ndim != 1 + lead:
        raise ValueError(
            f"mixing matrix must be {1 + lead}-D "
            f"({'(G, N, N)' if g else '(N, N)'}) for grid_axis={grid_axis!r}, "
            f"got shape {mix.shape}"
        )

    def leaf(l):
        flat = l.reshape(l.shape[:lead] + (-1,))
        if flat.shape[0] != mix.shape[0]:
            # fail at TRACE time with the shapes in hand — a mismatched
            # scenario grid inside the collective is far harder to read
            raise ValueError(
                f"stacked leading dim {flat.shape[0]} != mixing-matrix "
                f"leading dim {mix.shape[0]} (leaf {l.shape}, mix {mix.shape})"
            )
        # the schedule's declared matrix blocking picks the mix in_spec:
        # "rows" shards the leading matrix dim (each shard holds its
        # output rows), "cols" the trailing one (each shard holds the
        # column block its local params multiply)
        mix_spec = P(*g, None, axes) if mix_block == "cols" else P(*g, axes, None)
        out = jax.shard_map(
            partial(shard_body, axis=axis),
            mesh=mesh,
            in_specs=(P(*g, axes), mix_spec),
            out_specs=P(*g, axes),
        )(flat, mix)
        if active is not None:
            # jnp.where, not arithmetic blending: inactive rows stay
            # bit-exact even if the gathered params carry NaN/Inf
            a = (active > 0).reshape(active.shape + (1,) * (flat.ndim - active.ndim))
            out = jnp.where(a, out, flat.astype(out.dtype))
        return out.reshape(l.shape).astype(l.dtype)

    return jax.tree.map(leaf, stacked_params)


def sharded_gossip_mix_sparse(
    stacked_params: PyTree,
    idx: jnp.ndarray,
    wgt: jnp.ndarray,
    active: jnp.ndarray | None = None,
    *,
    mesh: Mesh | None = None,
    node_axes: tuple[str, ...] | None = None,
    grid_axis: str | None = None,
) -> PyTree:
    """Sharded gossip from a neighbor table — ``gossip_repr="sparse"``
    sibling of :func:`sharded_gossip_mix` (same contract, the (N, N)
    matrix replaced by ``core.topology.neighbor_table``'s (N, B+1)
    ``(idx, wgt)``).

    Each device holds N/shards table rows next to its parameter rows;
    the node axis is all-gathered once per leaf (the existing collective)
    and each local row gathers just its B+1 referenced rows
    (``sparse_gossip_shard``) — per-device cost O(N/shards · B · D)
    instead of the dense O(N/shards · N · D), with no (N, N) operand
    anywhere.  The gathered (N, D) temp remains, as in the dense
    allgather impl; federations too big for it should shrink D per call
    (leaf-wise mixing already does) before reaching for psum-style
    scatters.

    Grid batching works exactly as in the dense sibling: grid-stacked
    ``(G, N, B+1)`` tables + a ``("grid", "node")`` mesh are auto-detected
    (table 3-D + ``"grid"`` axis present) or forced via ``grid_axis=``.
    """
    if mesh is None:
        mesh = _default_federation_mesh(idx.shape[-2])
    axes = node_axes or tuple(
        a for a in mesh.axis_names if a not in ("model", "grid")
    )
    axis = axes if len(axes) > 1 else axes[0]
    if grid_axis is None and idx.ndim == 3 and "grid" in mesh.axis_names:
        grid_axis = "grid"
    g = (grid_axis,) if grid_axis else ()
    lead = 1 + len(g)  # stacked leading dims: [grid,] node
    if idx.ndim != 1 + lead:
        raise ValueError(
            f"neighbor table must be {1 + lead}-D "
            f"({'(G, N, B+1)' if g else '(N, B+1)'}) for grid_axis={grid_axis!r}, "
            f"got shape {idx.shape}"
        )
    if idx.shape != wgt.shape:
        raise ValueError(f"idx {idx.shape} != wgt {wgt.shape}")

    def leaf(l):
        flat = l.reshape(l.shape[:lead] + (-1,))
        if flat.shape[0] != idx.shape[0]:
            raise ValueError(
                f"stacked leading dim {flat.shape[0]} != neighbor-table "
                f"leading dim {idx.shape[0]} (leaf {l.shape}, idx {idx.shape})"
            )
        # check_vma=False: under the swept engine's
        # ``vmap(..., spmd_axis_name="grid")`` the gather's index
        # clamping compares grid-varying indices against replicated
        # bounds, which the replication checker rejects even though the
        # grid axis purely batches here (no collective crosses it)
        out = jax.shard_map(
            partial(sparse_gossip_shard, axis=axis),
            mesh=mesh,
            in_specs=(P(*g, axes), P(*g, axes, None), P(*g, axes, None)),
            out_specs=P(*g, axes),
            check_vma=False,
        )(flat, idx.astype(jnp.int32), wgt.astype(jnp.float32))
        if active is not None:
            a = (active > 0).reshape(active.shape + (1,) * (flat.ndim - active.ndim))
            out = jnp.where(a, out, flat.astype(out.dtype))
        return out.reshape(l.shape).astype(l.dtype)

    return jax.tree.map(leaf, stacked_params)


def sharded_gossip_mix_gather(
    stacked_params: PyTree,
    idx: jnp.ndarray,
    wgt: jnp.ndarray,
    active: jnp.ndarray | None = None,
    *,
    mesh: Mesh | None = None,
    node_axes: tuple[str, ...] | None = None,
    grid_axis: str | None = None,
) -> PyTree:
    """Fully sharded gossip from a neighbor table — ``gossip_impl=
    "gather"`` (backend ``sharded_gather_tables``).  Same call contract
    as :func:`sharded_gossip_mix_sparse`, different wire: the (N, B+1)
    tables AND the node rows stay sharded over the node mesh axes and
    the local row block ring-rotates via ``ppermute``
    (:func:`gather_tables_gossip_shard`), so only referenced remote rows
    are ever read and NO device materializes the gathered (N, D)
    federation — per-device memory O(N/shards · D) flat in N/shards,
    the population-scale (100k-node) schedule.

    Requires the node count to divide evenly over the node-axis width
    (the same divisibility ``launch.mesh.make_federation_mesh``
    guarantees).  Grid batching works as in the sparse sibling:
    grid-stacked ``(G, N, B+1)`` tables + a ``("grid", "node")`` mesh
    are auto-detected or forced via ``grid_axis=``.
    """
    if mesh is None:
        mesh = _default_federation_mesh(idx.shape[-2])
    axes = node_axes or tuple(
        a for a in mesh.axis_names if a not in ("model", "grid")
    )
    axis = axes if len(axes) > 1 else axes[0]
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if grid_axis is None and idx.ndim == 3 and "grid" in mesh.axis_names:
        grid_axis = "grid"
    g = (grid_axis,) if grid_axis else ()
    lead = 1 + len(g)  # stacked leading dims: [grid,] node
    if idx.ndim != 1 + lead:
        raise ValueError(
            f"neighbor table must be {1 + lead}-D "
            f"({'(G, N, B+1)' if g else '(N, B+1)'}) for grid_axis={grid_axis!r}, "
            f"got shape {idx.shape}"
        )
    if idx.shape != wgt.shape:
        raise ValueError(f"idx {idx.shape} != wgt {wgt.shape}")
    n = idx.shape[-2]
    if n % n_shards:
        raise ValueError(
            f"gather-table gossip needs num_nodes divisible by the node-"
            f"axis width, got N={n} over {n_shards} shards"
        )

    def leaf(l):
        flat = l.reshape(l.shape[:lead] + (-1,))
        if flat.shape[0] != idx.shape[0]:
            raise ValueError(
                f"stacked leading dim {flat.shape[0]} != neighbor-table "
                f"leading dim {idx.shape[0]} (leaf {l.shape}, idx {idx.shape})"
            )
        # check_vma=False for the same reason as the sparse sibling: the
        # in-block index clamp compares grid-varying indices against
        # replicated bounds under the swept engine's spmd vmap
        out = jax.shard_map(
            partial(gather_tables_gossip_shard, axis=axis, n_shards=n_shards),
            mesh=mesh,
            in_specs=(P(*g, axes), P(*g, axes, None), P(*g, axes, None)),
            out_specs=P(*g, axes),
            check_vma=False,
        )(flat, idx.astype(jnp.int32), wgt.astype(jnp.float32))
        if active is not None:
            # jnp.where keeps inactive rows bit-exact, matching every
            # other sparse mix path
            a = (active > 0).reshape(active.shape + (1,) * (flat.ndim - active.ndim))
            out = jnp.where(a, out, flat.astype(out.dtype))
        return out.reshape(l.shape).astype(l.dtype)

    return jax.tree.map(leaf, stacked_params)


def make_sharded_gossip(
    mesh: Mesh,
    node_axes: tuple[str, ...],
    topology: str,
    *,
    gossip_impl: str = "allgather",
):
    """Returns gossip_fn(stacked_tree, mix or active) running under ``mesh``.

    The stacked node axis is sharded over ``node_axes`` (e.g. ("data",) or
    ("pod", "data")).  Parameters' trailing dims stay as they were.
    ``gossip_impl`` selects the general-topology collective schedule
    (see :func:`sharded_gossip_mix`); the ring fast path ignores it
    (two ppermutes are already O(D) per link).
    """
    axis = node_axes if len(node_axes) > 1 else node_axes[0]
    n_shards = 1
    for a in node_axes:
        n_shards *= mesh.shape[a]

    if topology == "ring":

        def gossip(stacked: PyTree, active: jnp.ndarray) -> PyTree:
            def leaf(l):
                flat = l.reshape(l.shape[0], -1)
                out = jax.shard_map(
                    partial(ring_gossip_shard, axis=axis, n_shards=n_shards),
                    mesh=mesh,
                    in_specs=(P(node_axes), P(node_axes)),
                    out_specs=P(node_axes),
                )(flat, active.reshape(-1, 1))
                return out.reshape(l.shape).astype(l.dtype)

            return jax.tree.map(leaf, stacked)

        return gossip

    def gossip(stacked: PyTree, mix: jnp.ndarray) -> PyTree:
        return sharded_gossip_mix(
            stacked, mix, mesh=mesh, node_axes=node_axes, impl=gossip_impl
        )

    return gossip
