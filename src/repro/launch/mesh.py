"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests run with
the real single device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Every mesh in the repo is built here, with all axes ``Auto``.

    ``jax.make_mesh`` defaults every axis to ``Explicit``; the federation
    code is written against Auto-axis semantics (``shard_map`` bodies,
    ``with_sharding_constraint`` and ``vmap(..., spmd_axis_name=)``), so
    the axis types are stated rather than left to the default."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16 data x 16 model).  Multi-pod: 2 pods of
    256 (pod x data x model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(devices: int | None = None):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = devices or len(jax.devices())
    if n >= 4:
        return make_mesh((n // 2, 2), ("data", "model"))
    return make_mesh((n, 1), ("data", "model"))


def make_federation_mesh(num_nodes: int, *, devices: int | None = None):
    """Node-sharded 1-axis mesh for device-parallel gossip: the stacked
    federation axis N is split over the largest available device count
    that divides it (shard_map needs N % devices == 0).  Falls back to a
    single-device mesh, which degenerates to the local contraction.

    Multi-host aware: after ``launch.multihost.initialize`` the device
    pool is GLOBAL (``jax.devices()`` spans every process, ordered by
    process index), so the node axis spans hosts and the gossip
    collectives lower to real cross-host transfers.  The mesh is built
    from an explicitly BALANCED device list — width/processes devices
    drawn from every process — because ``jax.make_mesh`` alone takes the
    FIRST ``width`` global devices, which for width < device count would
    strand the later processes with zero shards (and no federation
    rows to place).  Degenerate node counts that no balanced width
    divides fall back to the first-k mesh; placement then fails loudly
    on the stranded processes (``multihost.process_row_slice``)."""
    avail = devices or len(jax.devices())
    procs = jax.process_count()
    divisors = [k for k in range(1, avail + 1) if num_nodes % k == 0]
    if procs > 1 and devices is None:
        by_proc: dict[int, list] = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, []).append(d)
        per_proc = min(len(v) for v in by_proc.values())
        aligned = [k for k in divisors if k % procs == 0 and k // procs <= per_proc]
        if aligned:
            width = max(aligned)
            picked = [
                d for p in sorted(by_proc) for d in by_proc[p][: width // procs]
            ]
            return make_mesh((width,), ("node",), devices=picked)
    return make_mesh((max(divisors),), ("node",))


def _sweep_mesh_widths(num_scenarios: int, num_nodes: int, avail: int) -> tuple[int, int]:
    """(grid_width, node_width) for :func:`make_sweep_mesh`'s default
    search: both must divide their extents; maximize devices used, then
    prefer the wider node axis (the memory-scaled one)."""
    best = (1, 1)
    for gw in (d for d in range(1, avail + 1) if num_scenarios % d == 0):
        for nw in (d for d in range(1, avail // gw + 1) if num_nodes % d == 0):
            if (gw * nw, nw) > (best[0] * best[1], best[1]):
                best = (gw, nw)
    return best


def make_sweep_mesh(
    num_scenarios: int,
    num_nodes: int,
    *,
    devices: int | None = None,
    grid_width: int | None = None,
    node_width: int | None = None,
):
    """2-D ``("grid", "node")`` mesh for the swept-sharded engine: the
    grid axis BATCHES scenarios (pure data parallelism — no gossip
    collective ever crosses it) while the node axis carries the
    federation collectives, exactly as on the 1-D federation mesh.

    Widths must divide their global extents (shard_map blocks are
    whole): ``grid_width | num_scenarios`` and ``node_width | num_nodes``.
    The default search maximizes devices used, tie-breaking toward the
    node axis — that is the memory-scaled one (psum keeps per-device
    state at O(G/grid · N/node · D), so widening "node" shrinks what a
    single device must hold of each scenario).  Degenerate extents fall
    back gracefully: a (1, 1) mesh on one device is the local
    contraction, batched.

    Single-process only (scenario sweeps are — multi-host runs sweep
    via serial ``train()`` per scenario); pass explicit widths to pin a
    layout in tests."""
    avail = devices or len(jax.devices())
    if grid_width is None or node_width is None:
        grid_width, node_width = _sweep_mesh_widths(num_scenarios, num_nodes, avail)
    if num_scenarios % grid_width or num_nodes % node_width:
        raise ValueError(
            f"sweep mesh widths must divide the grid: "
            f"G={num_scenarios} % grid_width={grid_width} and "
            f"N={num_nodes} % node_width={node_width} must both be 0"
        )
    return make_mesh((grid_width, node_width), ("grid", "node"))


# the auto-knob policies (choose_gossip_impl / choose_gossip_repr and
# their budget constants) are plan-resolution policies and live with the
# plan in core.gossip_plan; re-exported here for call-site back-compat
from repro.core.gossip_plan import (  # noqa: E402,F401
    DEFAULT_GATHER_BUDGET_BYTES,
    SPARSE_GOSSIP_FACTOR,
    choose_gossip_impl,
    choose_gossip_repr,
)


def make_gossip_dp_mesh(*, nodes: int = 4, multi_pod: bool = False):
    """Mesh view for gossip data-parallelism (DESIGN.md §4): the data
    axis is split into (node, data) so each federated node is a
    ``data/node``-way data-parallel group.  Same device order as the
    production mesh."""
    if multi_pod:
        # nodes = 2 pods x (nodes // 2) groups
        per_pod = max(nodes // 2, 1)
        return make_mesh(
            (2, per_pod, 16 // per_pod, 16), ("pod", "node", "data", "model")
        )
    return make_mesh((nodes, 16 // nodes, 16), ("node", "data", "model"))
