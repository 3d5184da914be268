"""GossipPlan — the mixing pipeline, resolved ONCE instead of dispatched
per call.

The trainer composes five orthogonal gossip knobs (``mixer`` tree /
kernel / sharded × ``gossip_impl`` allgather / psum / masked / gather ×
``gossip_repr`` dense / sparse × local-DP sigma × sweep batching).
Historically their interaction lived in ~17 nested ``if self.mixer ==
...`` branches spread over ``gluadfl.py`` / ``gossip.py`` /
``distributed.py`` / ``gossip_dp.py``; adding ONE new backend meant
editing every branch site.  This module collapses the maze:

* **Backend registry** — each mix backend is a registered callable with
  the uniform signature ``mix(stacked, mix_repr, *, key, mesh,
  grid_axis)`` plus declared capabilities (:class:`BackendCaps`:
  ``supports_sparse`` / ``supports_sweep_grid`` / ``supports_multihost``
  / ``memory_class`` / ``fused_dp``).  The registry is the single source
  of truth: the ARCHITECTURE.md knob matrix is GENERATED from it
  (``tools/gen_knob_matrix.py``) and the plan-totality test iterates it.
* **Resolution** — :func:`resolve_gossip_plan` turns ``(mixer,
  gossip_impl, gossip_repr, dp, masked, mesh)`` into a
  :class:`GossipPlan` at ``GluADFL.__init__`` (and again at
  ``train_sweep`` setup via :meth:`GossipPlan.require_sweep`): every
  refusal — unknown knob value, ``gather`` off the sharded mixer,
  kernel × sweep, non-sharded × multihost — raises HERE with a readable
  message, never mid-trace.
* **Pipeline** — a resolved plan is the explicit four-stage pipeline
  ``build_repr → [mask_wrap] → mix_backend → [dp_fuse]``:
  :meth:`GossipPlan.build_repr` makes the round's mixing operator
  (dense (N, N) matrix or sparse (N, B+1) neighbor table),
  :meth:`GossipPlan.mix` is the resolved noise-free contraction, and
  :meth:`GossipPlan.gossip` runs the full round step — optional local-DP
  fusion/composition first, the pairwise-mask cancellation term last —
  reproducing the pre-plan trainer BITWISE on every existing knob
  combination (the parity suites are the oracle).

``tools/check_gossip_dispatch.py`` keeps the refactor from regressing:
string-dispatch on the gossip knobs (``mixer == "..."`` and friends) is
linted out of ``core/`` everywhere but this module.

The policies ``choose_gossip_impl`` / ``choose_gossip_repr`` (formerly
``launch.mesh``) live here too: they are plan-resolution policies — the
``"auto"`` knob values defer to them, and ``launch.mesh`` re-exports
them for back-compat.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.gossip import (
    gossip_mix_dp_kernel,
    gossip_mix_kernel,
    gossip_mix_masked,
    gossip_mix_sparse_dp_kernel,
    gossip_mix_sparse_kernel,
    gossip_mix_sparse_tree,
    gossip_mix_tree,
    sharded_gossip_mix,
    sharded_gossip_mix_gather,
    sharded_gossip_mix_sparse,
)
from repro.core.topology import mixing_matrix, neighbor_candidates, neighbor_table
from repro.utils.rng import split_like

PyTree = Any

# the mixer knob's legal values (the backend registry below may hold
# MORE backends than mixers: `gossip_impl="gather"` reroutes the sharded
# mixer to the sharded_gather_tables backend)
MIXERS = ("tree", "kernel", "sharded")


class GossipPlanError(ValueError):
    """A knob combination the registry declares unsupported.  Subclasses
    ``ValueError`` so pre-plan call sites (and their tests) that caught
    ``ValueError`` keep working."""


@dataclass(frozen=True)
class MixRepr:
    """The round's mixing operator in its resolved representation.

    ``kind`` is ``"dense"`` (``operand`` = (N, N) row-stochastic matrix,
    identity rows already encode inactivity) or ``"sparse"`` (``operand``
    = the ``(idx, wgt)`` (N, B+1) neighbor table, slot 0 = self).
    ``active`` is the round's (N,) activity vector — the sparse paths
    use it for a bit-exact inactive-row where-select."""

    kind: str
    operand: Any
    active: Any = None


@dataclass(frozen=True)
class BackendCaps:
    """Declared capabilities of one registered mix backend — consumed by
    plan resolution (refusals), the generated knob matrix, and the
    plan-totality test."""

    supports_sparse: bool
    supports_dense: bool
    supports_sweep_grid: bool
    supports_multihost: bool
    memory_class: str        # per-device working set of the contraction
    fused_dp: bool           # noise+mix+self-restore fused in one pass
    uses_mesh: bool          # runs under a device mesh (shard_map)


@dataclass(frozen=True)
class MixBackend:
    """One registered mix backend: the uniform-signature callable plus
    its capabilities and knob routing.

    ``build(impl, default_mesh)`` returns the callable
    ``mix(stacked, mix_repr, *, key=None, mesh=None, grid_axis=None)``
    with the wire schedule and fallback mesh already bound — resolution
    calls it once, so the hot path holds a plain closure."""

    name: str
    mixer: str                   # the mixer knob value this backend serves
    impls: tuple[str, ...]       # wire schedules it accepts
    caps: BackendCaps
    build: Callable
    summary: str                 # one-line doc, surfaces in the knob matrix
    sweep_refusal: str | None = None   # message when supports_sweep_grid=False


# --------------------------------------------------------------------------
# the registry — the single source of truth for what composes with what
# --------------------------------------------------------------------------

_REGISTRY: dict[str, MixBackend] = {}


def register_mix_backend(backend: MixBackend) -> MixBackend:
    """Register a mix backend (latest registration wins — tests may
    shadow a backend with an instrumented twin)."""
    _REGISTRY[backend.name] = backend
    return backend


def mix_backends() -> dict[str, MixBackend]:
    """A copy of the backend registry, keyed by backend name."""
    return dict(_REGISTRY)


def _build_tree(impl, default_mesh):
    def mix(stacked, rep: MixRepr, *, key=None, mesh=None, grid_axis=None):
        if rep.kind == "sparse":
            idx, wgt = rep.operand
            return gossip_mix_sparse_tree(stacked, idx, wgt, rep.active)
        return gossip_mix_tree(stacked, rep.operand)

    return mix


def _build_kernel(impl, default_mesh):
    def mix(stacked, rep: MixRepr, *, key=None, mesh=None, grid_axis=None):
        if rep.kind == "sparse":
            idx, wgt = rep.operand
            return gossip_mix_sparse_kernel(stacked, idx, wgt, rep.active)
        return gossip_mix_kernel(stacked, rep.operand)

    return mix


def _build_sharded(impl, default_mesh):
    def mix(stacked, rep: MixRepr, *, key=None, mesh=None, grid_axis=None):
        if rep.kind == "sparse":
            idx, wgt = rep.operand
            return sharded_gossip_mix_sparse(
                stacked, idx, wgt, rep.active,
                mesh=mesh or default_mesh, grid_axis=grid_axis,
            )
        # dense identity rows already encode inactivity — no active mask
        return sharded_gossip_mix(
            stacked, rep.operand,
            mesh=mesh or default_mesh, impl=impl, grid_axis=grid_axis,
        )

    return mix


def _build_gather_tables(impl, default_mesh):
    def mix(stacked, rep: MixRepr, *, key=None, mesh=None, grid_axis=None):
        idx, wgt = rep.operand
        return sharded_gossip_mix_gather(
            stacked, idx, wgt, rep.active,
            mesh=mesh or default_mesh, grid_axis=grid_axis,
        )

    return mix


register_mix_backend(MixBackend(
    name="tree",
    mixer="tree",
    # the wire schedule only matters to the sharded mixer; tree/kernel
    # accept every schedule knob value and ignore it (masked composes
    # through the trainer-level cancellation wrapper either way)
    impls=("allgather", "psum", "masked"),
    caps=BackendCaps(
        supports_sparse=True, supports_dense=True,
        supports_sweep_grid=True, supports_multihost=False,
        memory_class="replicated O(N·D)", fused_dp=False, uses_mesh=False,
    ),
    build=_build_tree,
    summary="reference einsum per leaf (CPU default)",
))

register_mix_backend(MixBackend(
    name="kernel",
    mixer="kernel",
    impls=("allgather", "psum", "masked"),
    caps=BackendCaps(
        supports_sparse=True, supports_dense=True,
        supports_sweep_grid=False, supports_multihost=False,
        memory_class="replicated O(N·D), VMEM-blocked", fused_dp=True,
        uses_mesh=False,
    ),
    build=_build_kernel,
    summary="Pallas VMEM-blocked kernel; fuses the local-DP pass",
    sweep_refusal=(
        "train_sweep batches the tree or sharded mixer; "
        "mixer='kernel' (Pallas) is a per-scenario program — "
        "use serial train() for it"
    ),
))

register_mix_backend(MixBackend(
    name="sharded",
    mixer="sharded",
    impls=("allgather", "psum", "masked"),
    caps=BackendCaps(
        supports_sparse=True, supports_dense=True,
        supports_sweep_grid=True, supports_multihost=True,
        memory_class="allgather O(N·D) / psum O(N/shards·D) per device",
        fused_dp=False, uses_mesh=True,
    ),
    build=_build_sharded,
    summary="shard_map collectives over the node mesh axis",
))

register_mix_backend(MixBackend(
    name="sharded_gather_tables",
    mixer="sharded",
    impls=("gather",),
    caps=BackendCaps(
        supports_sparse=True, supports_dense=False,
        supports_sweep_grid=False, supports_multihost=True,
        memory_class="halo O(N/shards·D) per device, no gathered (N·D)",
        fused_dp=False, uses_mesh=True,
    ),
    build=_build_gather_tables,
    summary=(
        "sharded (N, B+1) tables + ppermute halo rotation — gathers only "
        "referenced remote rows (the 100k-node backend)"
    ),
    sweep_refusal=(
        "train_sweep batches the tree or sharded allgather/psum "
        "schedules; gossip_impl='gather' (sharded gather tables) is the "
        "single-run scale-out schedule — use allgather/psum for swept-"
        "sharded runs"
    ),
))


def _backend_for(mixer: str, gossip_impl: str) -> MixBackend:
    """Route (mixer, impl) to a registered backend, or raise the
    documented capability error."""
    for backend in _REGISTRY.values():
        if backend.mixer == mixer and gossip_impl in backend.impls:
            return backend
    # the only impl not universally accepted is the gather-tables one
    takers = sorted(b.mixer for b in _REGISTRY.values() if gossip_impl in b.impls)
    raise GossipPlanError(
        f"gossip_impl {gossip_impl!r} has no backend for mixer={mixer!r}"
        + (f" (it needs mixer in {takers})" if takers else "")
    )


# --------------------------------------------------------------------------
# plan-resolution policies (the "auto" knob values; formerly launch.mesh)
# --------------------------------------------------------------------------

# per-device budget for the gathered (N, D) federation before the
# allgather mixer's memory cliff outweighs its ICI-friendly schedule;
# ~1 GiB leaves headroom for the model step on current HBM/host parts
DEFAULT_GATHER_BUDGET_BYTES = 1 << 30


def choose_gossip_impl(
    num_nodes: int,
    param_bytes_per_node: int,
    *,
    shards: int | None = None,
    budget_bytes: int = DEFAULT_GATHER_BUDGET_BYTES,
    secure: bool = False,
) -> str:
    """Memory-scaled gossip-impl selection (``--gossip-impl auto``).

    The ``"allgather"`` mixer materializes the full federation —
    ``num_nodes * param_bytes_per_node`` — on EVERY device, regardless of
    how many shards the mesh has; ``"psum"`` keeps the per-device working
    set at O(N/shards · D) via reduce-scatter.  Below ``budget_bytes``
    the gathered form wins (one dense collective, what the ICI fabric is
    best at); above it, psum is the only schedule that fits.  ``shards``
    defaults to the federation mesh width for ``num_nodes``.

    ``secure=True`` requests pairwise-masked secure aggregation
    (``core.secure_agg``): the choice is then ``"masked"`` regardless of
    memory — its wire schedule rides allgather, so it is only offered
    while the gathered federation fits the budget; past that this raises
    rather than silently dropping the privacy layer (psum has no masked
    sibling: the reduce-scatter never materializes per-neighbor wires to
    mask).
    """
    if shards is None:
        from repro.launch.mesh import make_federation_mesh

        shards = make_federation_mesh(num_nodes).shape["node"]
    gathered = num_nodes * param_bytes_per_node
    if secure:
        if shards > 1 and gathered > budget_bytes:
            raise GossipPlanError(
                f"secure (masked) gossip rides the allgather schedule, but "
                f"the gathered federation ({gathered} bytes) exceeds the "
                f"per-device budget ({budget_bytes}); shrink the model or "
                f"raise budget_bytes"
            )
        return "masked"
    if shards <= 1:
        return "allgather"  # single shard: gather is a no-op copy
    return "allgather" if gathered <= budget_bytes else "psum"


# sparse tables win once the kept row (B+1 entries) is a small fraction
# of N.  Across a node axis wider than one device this is the FLOP
# heuristic: 4x covers the gather/top_k bookkeeping the dense matmul
# doesn't pay (never timed on chips).
SPARSE_GOSSIP_FACTOR = 4
# The tree mixer with the node axis on one device: the dense contraction
# reads and writes each node's parameters once, while the table's gather
# copies them B+1 times, so dense wins past the FLOP break-even.  Timed
# on one TPU v5e (build + mix a round, B = 7, D = 66,689, random graph):
# dense was 2.3x to 4.5x faster at every N from 226 to 4096, the largest
# timed (PERF.md, PR 17), so the boundary sits there: N = 4096 at B = 7.
ONE_DEVICE_SPARSE_GOSSIP_FACTOR = 512


def _node_axis_width(mesh) -> int:
    """Total node-axis width of a federation/sweep mesh — the product of
    every axis the gossip collectives run over (same convention as
    ``core.distributed``: everything except "model"/"grid")."""
    width = 1
    for name in mesh.axis_names:
        if name not in ("model", "grid"):
            width *= mesh.shape[name]
    return max(width, 1)


def choose_gossip_repr(
    num_nodes: int,
    comm_batch: int,
    *,
    factor: int | None = None,
    mesh=None,
    budget_bytes: int = DEFAULT_GATHER_BUDGET_BYTES,
) -> str:
    """Mixing-operator representation selection (``--gossip-repr auto``).

    Every mixing row has at most ``comm_batch + 1`` nonzeros (Algorithm 1
    caps each node at B neighbours), so the sparse neighbor table
    (``core.topology.neighbor_table``) does ``N / (B+1)``-fold fewer
    FLOPs than the dense (N, N) matrix — but it gathers B+1 copies of
    every node's parameters, where the dense contraction reads them
    once.  Pick sparse once ``num_nodes >= factor * (comm_batch + 1)``.
    An explicit ``factor`` is used as given (``resolve_gossip_plan``
    passes ``SPARSE_GOSSIP_FACTOR`` for every backend but the tree
    mixer); ``None`` picks it from where the node axis sits:

    * on one device (no ``mesh``, or a node-axis width of 1):
      ``ONE_DEVICE_SPARSE_GOSSIP_FACTOR``, the tree backend's
      dense/sparse crossover on one chip.  At the paper's N=226 / B=7
      this picks dense;
    * across devices: ``SPARSE_GOSSIP_FACTOR`` (4), so N >= 32 at B = 7
      goes sparse.

    Mesh-aware (the sharded mixer's path): with a ``mesh``, the dense
    representation additionally keeps an ``(N/shards, N)`` row block of
    the mixing matrix resident on every device — once that block alone
    outgrows ``budget_bytes`` only the ``(N/shards, B+1)`` table fits,
    so sparse is forced regardless of the factor."""
    shards = 1 if mesh is None else _node_axis_width(mesh)
    if factor is None:
        factor = ONE_DEVICE_SPARSE_GOSSIP_FACTOR if shards == 1 else SPARSE_GOSSIP_FACTOR
    if num_nodes >= factor * (comm_batch + 1):
        return "sparse"
    if mesh is not None:
        per_device_matrix = (num_nodes // shards) * num_nodes * 4  # f32
        if per_device_matrix > budget_bytes:
            return "sparse"
    return "dense"


# --------------------------------------------------------------------------
# the resolved plan
# --------------------------------------------------------------------------


@dataclass(frozen=True, eq=False)
class GossipPlan:
    """One resolved mixing pipeline: ``build_repr → [mask_wrap] →
    mix_backend → [dp_fuse]``, with every knob decision already taken.

    Resolved once per trainer (``GluADFL.__init__``) and re-checked at
    ``train_sweep`` setup (:meth:`require_sweep`) — the round body only
    ever calls :meth:`build_repr` / :meth:`mask_table` /
    :meth:`gossip`."""

    mixer: str               # resolved mixer knob value
    backend: str             # registered backend name serving it
    gossip_impl: str
    gossip_repr: str         # resolved: "dense" | "sparse" (never "auto")
    dp_noise_sigma: float
    masked: bool             # gossip_impl == "masked" resolved at build
    use_kernel: bool         # back-compat introspection flag
    uses_mesh: bool
    comm_batch: int
    caps: BackendCaps
    mesh: Any = None
    neighbor_cand: Any = None    # host-built static-topology candidates
    _build_repr: Callable = None
    _mask_table: Callable = None
    _mix: Callable = None
    _dp: Callable = None
    _sweep_refusal: str | None = None

    # -- stage 1: the round's mixing operator --------------------------
    def build_repr(self, adj, active, comm_batch: int | None = None) -> Any:
        """Dense (N, N) ``mixing_matrix`` or sparse ``(idx, wgt)``
        neighbor table (densifying the latter reproduces the former
        bitwise)."""
        b = self.comm_batch if comm_batch is None else comm_batch
        return self._build_repr(adj, active, b)

    def mask_table(self, operand, adj, active, comm_batch: int | None = None):
        """The (N, B+1) neighbor table the pairwise-mask wrapper needs:
        the operand itself under the sparse representation, or a table
        built alongside the dense matrix purely for mask bookkeeping."""
        b = self.comm_batch if comm_batch is None else comm_batch
        return self._mask_table(operand, adj, active, b)

    # -- stage 3: the resolved noise-free contraction ------------------
    def mix(self, stacked: PyTree, operand: Any, active=None, *,
            key=None, mesh=None, grid_axis=None) -> PyTree:
        """The plain mix on the resolved backend.  ``mesh`` overrides
        the plan's mesh for this call (the swept-sharded path threads
        its 2-D (grid, node) mesh down here)."""
        rep = MixRepr(kind=self.gossip_repr, operand=operand, active=active)
        return self._mix(stacked, rep, key=key, mesh=mesh, grid_axis=grid_axis)

    # -- the full pipeline ---------------------------------------------
    def gossip(self, premix: PyTree, operand: Any, active, k_dp, *,
               mesh=None, mask_ctx=None, dp_sigma=None) -> PyTree:
        """One round's mixing step: plain mix or the local-DP
        composition (stage 4 — fused into the kernel backend's single
        pass, composed as noise-add → mix → clean-self-restore
        elsewhere), then the pairwise-mask cancellation term (stage 2's
        wrapper) added to the FINAL mixed state — after the DP
        composition too, so masked runs stay bitwise twins of their
        unmasked counterparts on every backend/repr/DP combination.

        ``dp_sigma`` overrides the plan's ``dp_noise_sigma``: a python
        float (config path) keeps the concrete ``<= 0`` shortcut; a
        TRACED per-scenario scalar (the sweep's DP axis) always takes
        the noise path — a ``sigma=0`` scenario then contracts
        exact-zero noise, which the DP-off property test pins as
        bitwise-clean."""
        rep = MixRepr(kind=self.gossip_repr, operand=operand, active=active)
        if dp_sigma is None:
            dp_sigma = self.dp_noise_sigma
        concrete_off = isinstance(dp_sigma, (int, float)) and dp_sigma <= 0.0
        if k_dp is None or concrete_off:
            out = self._mix(stacked=premix, rep=rep, mesh=mesh)
        else:
            noise_keys = split_like(k_dp, premix)
            noise = jax.tree.map(
                lambda w, k_: dp_sigma * jax.random.normal(k_, w.shape, w.dtype),
                premix, noise_keys,
            )
            out = self._dp(premix, noise, rep, mesh=mesh)
        if mask_ctx is not None:
            k_mask, (t_idx, t_wgt) = mask_ctx
            out = gossip_mix_masked(out, t_idx, t_wgt, k_mask)
        return out

    # -- capability checks ---------------------------------------------
    def require_sweep(self) -> None:
        """Raise the documented refusal unless this plan's backend can
        batch under the sweep engine's grid vmap."""
        if not self.caps.supports_sweep_grid:
            raise NotImplementedError(
                self._sweep_refusal
                or f"backend {self.backend!r} does not support train_sweep"
            )

    def require_multihost(self) -> None:
        """Raise unless this plan's backend spans ``jax.distributed``
        processes (the node axis must be a real mesh axis)."""
        if not self.caps.supports_multihost:
            raise ValueError(
                f"multi-host training needs mixer='sharded' (the node "
                f"axis must span processes), got mixer={self.mixer!r}"
            )


def _resolve_dp_stage(backend: MixBackend, gossip_repr: str, mix_fn: Callable):
    """Stage 4 (``dp_fuse``): the kernel backend fuses noise-broadcast +
    mix + clean-self-restore into its single pass; every other backend
    composes — neighbours mix the NOISED view and each node re-adds its
    own clean self-contribution (it never needs to noise itself)."""
    if backend.caps.fused_dp:
        if gossip_repr == "sparse":
            def dp(premix, noise, rep: MixRepr, *, mesh=None):
                idx, wgt = rep.operand
                return gossip_mix_sparse_dp_kernel(
                    premix, noise, idx, wgt, rep.active
                )
        else:
            def dp(premix, noise, rep: MixRepr, *, mesh=None):
                return gossip_mix_dp_kernel(premix, noise, rep.operand, rep.active)
        return dp
    if gossip_repr == "sparse":
        def dp(premix, noise, rep: MixRepr, *, mesh=None):
            shared = jax.tree.map(jnp.add, premix, noise)
            mixed_noisy = mix_fn(shared, rep, mesh=mesh)
            # slot 0 is always self: wgt[:, 0] IS the densified diagonal.
            # the plain mix already where-selected inactive rows back to
            # the noised view, so restore them to the clean premix too.
            self_w = rep.operand[1][:, 0]
            out = jax.tree.map(
                lambda mn, z: mn - self_w.reshape((-1,) + (1,) * (z.ndim - 1)) * z,
                mixed_noisy, noise,
            )
            a = rep.active > 0
            return jax.tree.map(
                lambda o, p: jnp.where(a.reshape((-1,) + (1,) * (o.ndim - 1)), o, p),
                out, premix,
            )
        return dp

    def dp(premix, noise, rep: MixRepr, *, mesh=None):
        shared = jax.tree.map(jnp.add, premix, noise)
        mixed_noisy = mix_fn(shared, rep, mesh=mesh)
        self_w = jnp.diagonal(rep.operand)  # (N,)
        return jax.tree.map(
            lambda mn, z: mn - self_w.reshape((-1,) + (1,) * (z.ndim - 1)) * z,
            mixed_noisy, noise,
        )

    return dp


def resolve_gossip_plan(
    *,
    mixer: str | None = None,
    use_kernel: bool = False,
    gossip_impl: str = "allgather",
    gossip_repr: str = "dense",
    dp_noise_sigma: float = 0.0,
    mesh=None,
    num_nodes: int,
    comm_batch: int,
    topology: str | None = None,
    cluster_size: int = 4,
) -> GossipPlan:
    """Resolve the gossip knobs into one :class:`GossipPlan`.

    Every refusal raises here with the knob's name in the message:
    unknown ``mixer`` / ``gossip_impl`` / ``gossip_repr`` values are
    plain ``ValueError``s; combinations the registry declares
    unsupported (``gather`` off the sharded mixer or the dense repr)
    raise :class:`GossipPlanError`.  ``gossip_repr="auto"`` defers to
    the mesh-aware :func:`choose_gossip_repr` policy.

    ``use_kernel`` is the DEPRECATED pre-``mixer`` spelling of
    ``mixer="kernel"`` — it still maps through (and still conflicts
    loudly with a contradicting ``mixer``), but warns."""
    from repro.core.distributed import GOSSIP_IMPLS, GOSSIP_REPRS

    if use_kernel:
        warnings.warn(
            "use_kernel is deprecated; pass mixer='kernel' instead "
            "(the flag maps through for now and will be removed)",
            DeprecationWarning,
            stacklevel=3,
        )
        if mixer is None:
            mixer = "kernel"
        elif mixer != "kernel":
            raise ValueError(
                f"use_kernel=True contradicts mixer={mixer!r}; pass one or the other"
            )
    if mixer is None:
        mixer = "tree"
    if mixer not in MIXERS:
        raise ValueError(f"mixer {mixer!r} not in {MIXERS}")
    if gossip_impl not in GOSSIP_IMPLS:
        raise ValueError(f"gossip_impl {gossip_impl!r} not in {GOSSIP_IMPLS}")
    backend = _backend_for(mixer, gossip_impl)
    if gossip_repr == "auto":
        # the one-device crossover is the tree backend's, whose sparse
        # form copies B+1 rows per node; the other backends keep the
        # FLOP rule
        factor = None if backend.name == "tree" else SPARSE_GOSSIP_FACTOR
        gossip_repr = choose_gossip_repr(num_nodes, comm_batch, factor=factor, mesh=mesh)
    if gossip_repr not in GOSSIP_REPRS:
        raise ValueError(
            f"gossip_repr {gossip_repr!r} not in {GOSSIP_REPRS}; 'auto' "
            f"resolves via the mesh-aware choose_gossip_repr policy before "
            f"this check"
        )

    if gossip_repr == "sparse" and not backend.caps.supports_sparse:
        raise GossipPlanError(
            f"backend {backend.name!r} does not support gossip_repr='sparse'"
        )
    if gossip_repr == "dense" and not backend.caps.supports_dense:
        raise GossipPlanError(
            f"gossip_impl {gossip_impl!r} (backend {backend.name!r}) needs "
            f"gossip_repr='sparse': the gather-table schedule shards the "
            f"(N, B+1) neighbor tables — there is no dense (N, N) variant"
        )

    mix_fn = backend.build(gossip_impl, mesh)
    dp_fn = _resolve_dp_stage(backend, gossip_repr, mix_fn)
    if gossip_repr == "sparse":
        build_repr = lambda adj, active, b: neighbor_table(adj, active, b)
        mask_tab = lambda operand, adj, active, b: operand
        # static-topology candidate lists, host-built once: the sparse
        # config-driven path builds its (N, B+1) table straight from
        # these — no (N, N) array ever exists (the population-scale
        # unlock).  None for "random" (per-round graphs go through
        # neighbor_table) and for topology-free resolutions.
        cand = (
            neighbor_candidates(topology, num_nodes, cluster_size)
            if topology is not None
            else None
        )
    else:
        build_repr = lambda adj, active, b: mixing_matrix(adj, active, b)
        # dense rounds build the (N, B+1) table alongside the matrix
        # purely for mask bookkeeping — the mix stays on the dense repr
        mask_tab = lambda operand, adj, active, b: neighbor_table(adj, active, b)
        cand = None

    return GossipPlan(
        mixer=mixer,
        backend=backend.name,
        gossip_impl=gossip_impl,
        gossip_repr=gossip_repr,
        dp_noise_sigma=dp_noise_sigma,
        masked=gossip_impl == "masked",
        use_kernel=backend.caps.fused_dp,
        uses_mesh=backend.caps.uses_mesh,
        comm_batch=comm_batch,
        caps=backend.caps,
        mesh=mesh,
        neighbor_cand=cand,
        _build_repr=build_repr,
        _mask_table=mask_tab,
        _mix=mix_fn,
        _dp=dp_fn,
        _sweep_refusal=backend.sweep_refusal,
    )


def supported_cells() -> list[dict]:
    """Every (mixer, gossip_impl, gossip_repr) cell the registry
    resolves, with its backend name and capabilities — the machine-
    readable form the knob-matrix generator and the totality test share."""
    from repro.core.distributed import GOSSIP_IMPLS, GOSSIP_REPRS

    cells = []
    for mixer in MIXERS:
        for impl in GOSSIP_IMPLS:
            for repr_ in GOSSIP_REPRS:
                try:
                    plan = resolve_gossip_plan(
                        mixer=mixer, gossip_impl=impl, gossip_repr=repr_,
                        num_nodes=8, comm_batch=2,
                    )
                except (GossipPlanError, ValueError):
                    continue
                cells.append({
                    "mixer": mixer,
                    "gossip_impl": impl,
                    "gossip_repr": repr_,
                    "backend": plan.backend,
                    "sweep": plan.caps.supports_sweep_grid,
                    "multihost": plan.caps.supports_multihost,
                    "memory_class": plan.caps.memory_class,
                })
    return cells
