"""GluADFL — Algorithm 1 of the paper, vectorized over the federation.

Faithfulness notes (numbered lines refer to the paper's Algorithm 1):
  * Line 3  — per-node random init (different seed per node).
  * Line 5  — broadcasting is implicit in the mixing matrix: only ACTIVE
    nodes' parameters reach neighbours, and only ACTIVE nodes mix.
  * Lines 7-9 — uniform average over {self} ∪ ≤B active neighbours,
    implemented as a row-stochastic matrix (topology.mixing_matrix) and a
    single gossip-mix contraction (gossip.py / Pallas kernel).
  * Lines 11-13 — local SGD step; per the paper's update rule
    ``w_t = ŵ_{t-1} - γ ∇J(·, w_{t-1})`` the gradient is evaluated at the
    PRE-MIX parameters and applied to the mixed ones (SWIFT-style).
    ``grad_at="mixed"`` gives the conventional DSGD variant (beyond-paper
    ablation).
  * Lines 15-16 — population model = uniform average of all node models.

The whole federation is a stacked pytree (leaves ``(N, ...)``); one round
is a single pure function: mixing-matrix build -> gossip mix -> vmapped
local step, all masked by the round's active vector.  Nodes therefore
simulate wall-clock asynchrony exactly (inactive nodes are frozen), while
the host sees a deterministic, reproducible program.

Engine design (the training hot path)
-------------------------------------
``_round`` is a pure ``FLState -> (FLState, loss)`` body, which makes the
multi-round engine a compiler problem rather than a host loop:

  * **Chunked scan** — :meth:`train_chunk` runs ``chunk`` rounds as ONE
    XLA program (``jax.lax.scan`` over ``_round``) and returns the
    stacked ``(chunk,)`` per-round losses, so the host synchronizes once
    per chunk instead of once per round.  The carried ``FLState`` buffers
    are donated (``donate_argnums``), so N-node parameter/optimizer
    state is updated in place across the whole chunk — no per-round
    host dispatch, no per-round device<->host ``float(loss)`` sync, no
    re-entry through the jit cache.
  * **Streaming eval** — cheap metrics no longer force the host between
    rounds: with ``eval_every > 0`` the scanned ``_round`` body carries a
    ``jax.lax.cond``-guarded eval branch on ``round % eval_every`` that
    computes val RMSE of the population model on a pre-batched
    validation set (scan constants), and ``train_chunk`` returns the
    stacked ``(chunk,)`` eval records next to the losses.  Rounds that
    don't hit the boundary pay only the cond's predicate.
  * **Loop fallback** — the original per-round Python loop survives ONLY
    behind the explicit ``engine="loop"`` debug flag (host callbacks with
    side effects, pdb between rounds).  Same numerics, one dispatch per
    round; it is never selected automatically.
  * **Mixer modes** — the gossip contraction dispatches on ``mixer``:
      - ``"tree"``     einsum per leaf at ``HIGHEST`` precision (f32-exact
        on the TPU too), the default,
      - ``"kernel"``   Pallas VMEM-blocked kernel (interpret on CPU); the
        local-DP path fuses noise-broadcast + mix + clean-self-restore
        into the kernel's single pass over the (N, D) matrix
        (``gossip_mix_dp_kernel``) instead of three tree_maps,
      - ``"sharded"``  ``core.distributed.sharded_gossip_mix`` under a
        node-sharded mesh (``launch.mesh.make_federation_mesh``): the N
        federation rows split across devices and the mix runs as a real
        collective — the fleet-scale path, and it scans like the rest.
  * **Gossip representation** — orthogonal to the mixer,
    ``gossip_repr`` picks the mixing operator's storage: ``"dense"``
    contracts the (N, N) ``topology.mixing_matrix``; ``"sparse"`` uses
    the (N, B+1) neighbor table (``topology.neighbor_table``) whose
    densification is bitwise the same matrix, cutting the contraction
    from O(N²·D) FLOPs to O(N·B·D), at the price of gathering B+1
    copies of every node's parameters.  Static topologies build the
    table from host-side candidate lists so no (N, N) array exists
    anywhere — federations of 10k+ nodes train where the dense path
    OOMs (``sparse-gossip-10k`` bench row).  ``"auto"`` defers to
    ``launch.mesh.choose_gossip_repr``: the tree mixer on one device
    stays dense below its dense/sparse crossover (dense at the paper's
    N=226); across devices, and for the other mixers, sparse once
    N >= 4·(B+1).  Every mixer has a sparse twin,
    including the fused DP kernel.

All RNG is threaded through ``FLState.key`` so every engine/mixer
combination consumes the identical key stream: ``train_chunk(chunk=k)``
matches k sequential ``_round`` calls to float tolerance (tested in
``tests/test_train_engine.py``), and inactive nodes stay bitwise frozen
across a chunk.

Scenario-sweep engine (the paper's ablation grids as ONE program)
-----------------------------------------------------------------
The Fig-4/Fig-5 ablations run the same trainer under many
(topology, inactive-ratio, seed) configurations.  :meth:`train_sweep`
batches that grid with ``jax.vmap`` over the scanned chunk instead of a
serial Python loop: a :class:`SweepGrid` carries every per-scenario knob
as DATA (stacked adjacency matrices + a per-round-resample flag from
``topology.stacked_adjacency``, ``(G,)`` inactive ratios, ``(G, 2)``
seed keys), so one compile executes all G scenarios and the streaming
eval branch returns a ``(G, chunk)`` record stack.  Scenario ``g``
consumes the identical key stream as ``train(PRNGKey(seed_g))`` under
the same config — swept results ARE the serial results, just batched
(``tests/test_sweep.py`` pins the parity; ``benchmarks/rounds_per_sec``
prices the speedup as the ``sweep-scan`` row).

Beyond the classic three axes the grid optionally sweeps the scenario
dimensions the paper's robustness story turns on, each as a traced
``(G,)`` array that arms independently (``SweepGrid.build(...,
schedules=, skews=, dp_sigmas=)``):

  * **Markov-sticky staleness** — per-scenario schedule choice between
    iid bernoulli participation and ``async_sched.markov_active``'s
    sticky busy/free chain.  Both schedules read the same single
    ``uniform(k_act, (N,))`` draw, so the choice is a ``jnp.where``
    select with zero key-stream drift; the serial twin is
    ``FLConfig(schedule="markov")``.
  * **Non-IID data skew** — node ``i`` trains on batches shifted by
    ``skew_g * data.synth.node_skew_offsets(N)[i]``; bitwise equal to
    training on host-pre-shifted arrays (gather commutes with the add),
    so the serial twin is a plain ``train()`` on skewed data
    (``FLConfig(data_skew=...)`` for the config-driven path).
  * **DP noise level** — the local-DP sigma as a traced scalar fed to
    ``_gossip_base``; the DP key split arms uniformly across a dp-armed
    grid so every scenario (including sigma=0) keeps one key stream,
    and the serial twin is ``GluADFL(dp_noise_sigma=sigma_g)``.

``tests/test_sweep_axes.py`` pins each axis against its serial twin
(losses, params, eval records, bitwise key chains) — those tests fail
if any axis' plumbing is reverted.

The sweep has a second engine for fleet scale: with ``mixer="sharded"``
the grid axis becomes a REAL mesh axis — the ``(G, N, ...)`` stacked
state lives on a 2-D ``("grid", "node")`` mesh
(``launch.mesh.make_sweep_mesh``), the vmap binds the scenario axis to
``"grid"`` (``spmd_axis_name``), and the gossip collectives inside the
round body stay scoped to ``"node"`` — so the memory-scaled psum
schedule keeps per-device state at O(G/grid · N/node · D) across the
whole grid (``sweep-sharded-psum`` bench row).
"""
from __future__ import annotations

import inspect
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FLConfig
from repro.core.async_sched import bernoulli_active, markov_active, staleness_update
from repro.core.gossip_plan import MIXERS, resolve_gossip_plan
from repro.core.secure_agg import MASK_STREAM_TAG
from repro.core.topology import (
    neighbor_table_from_candidates,
    random_adjacency,
    round_adjacency,
    stacked_adjacency,
)
from repro.data.synth import node_skew_offsets
from repro.models.base import Model
from repro.optim import Optimizer
from repro.utils.pytree import tree_mean

PyTree = Any

# default scan-chunk length: long enough to amortize dispatch + the
# once-per-chunk loss sync, short enough that the first-compile cost and
# the host-side history granularity stay reasonable
DEFAULT_CHUNK = 32

# the stages of one round, each a ``jax.named_scope("round.<stage>")``
# around the ops it emits: the names reach the compiled ops' ``op_name``
# metadata only (the program is otherwise the same op for op), from
# which ``bench/trace_stages.py`` attributes device time to a stage.
# Scopes never nest.
ROUND_STAGES = ("neighbours", "gossip", "local_step", "adam", "select")


def _stage(name: str):
    return jax.named_scope(f"round.{name}")


def _to_host(v):
    """numpy copy of a per-chunk device output (losses, eval records).
    Multi-host global arrays are not fully addressable, so they come
    back through their replicated local shard instead of np.asarray."""
    if isinstance(v, jax.Array) and not v.is_fully_addressable:
        from repro.launch.multihost import fetch_replicated

        return fetch_replicated(v)
    return np.asarray(v)


@jax.tree_util.register_dataclass
@dataclass
class FLState:
    params: PyTree          # stacked (N, ...)
    opt_state: PyTree       # stacked (N, ...)
    staleness: jnp.ndarray  # (N,)
    round: jnp.ndarray      # scalar int
    key: jnp.ndarray


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "adjacency", "resample", "inactive_ratio", "init_keys",
        "markov", "skew", "dp_sigma",
    ),
    meta_fields=("labels",),
)
@dataclass
class SweepGrid:
    """A batch of G training scenarios for :meth:`GluADFL.train_sweep`.

    Every per-scenario knob the round body consumes is DATA, not
    structure, so the whole grid runs under one ``jax.vmap``:

      * ``adjacency``      — (G, N, N) static adjacency per scenario
                             (zeros placeholder for per-round-resampled
                             topologies), from ``topology.stacked_adjacency``;
      * ``resample``       — (G,) {0,1}: 1 = re-draw the graph each round
                             from that round's key ("random" topology);
      * ``inactive_ratio`` — (G,) Fig-5 asynchrony ratio per scenario;
      * ``init_keys``      — (G, 2) per-scenario PRNG init keys
                             (``PRNGKey(seed)`` — the exact key a serial
                             ``train(PRNGKey(seed), ...)`` run would use);
      * ``labels``         — static tuple naming scenario g for the host
                             side: ``(topology, ratio, seed)`` for a
                             classic 3-axis grid, ``(topology, ratio,
                             schedule, skew, dp_sigma, seed)`` once any
                             optional axis is armed (:meth:`label_dict`
                             normalizes either form).

    The optional scenario axes are each ``None`` (axis unarmed — the
    round body compiles the identical program as before the axis
    existed) or a ``(G,)`` array:

      * ``markov``         — {0,1}: 1 = Markov-sticky participation
                             (``async_sched.markov_active``) instead of
                             the bernoulli schedule;
      * ``skew``           — non-IID data-skew strength: node ``i``
                             trains on batches shifted by
                             ``skew * node_skew_offsets(N)[i]``;
      * ``dp_sigma``       — local-DP gossip noise sigma (traced; the
                             key stream arms the DP split for EVERY
                             scenario of a dp-armed grid).
    """

    adjacency: jnp.ndarray
    resample: jnp.ndarray
    inactive_ratio: jnp.ndarray
    init_keys: jnp.ndarray
    labels: tuple
    markov: jnp.ndarray | None = None
    skew: jnp.ndarray | None = None
    dp_sigma: jnp.ndarray | None = None

    @property
    def size(self) -> int:
        return len(self.labels)

    def label_dict(self, g: int) -> dict:
        """Scenario ``g``'s knobs as a dict, normalizing 3-tuple
        (classic grid) and 6-tuple (axis-armed grid) labels."""
        lab = self.labels[g]
        if len(lab) == 3:
            topo, ratio, seed = lab
            sched, skew, dp = "bernoulli", 0.0, 0.0
        else:
            topo, ratio, sched, skew, dp, seed = lab
        return {
            "topology": topo,
            "inactive_ratio": ratio,
            "schedule": sched,
            "skew": skew,
            "dp_sigma": dp,
            "seed": seed,
        }

    @classmethod
    def build(
        cls,
        topologies,
        inactive_ratios,
        seeds=(0,),
        *,
        num_nodes: int,
        cluster_size: int = 4,
        schedules=None,
        skews=None,
        dp_sigmas=None,
    ) -> "SweepGrid":
        """Cross-product grid (topology-major, then ratio, then
        schedule/skew/dp_sigma, seed innermost) — the paper's Fig-5
        layout: ``build(("ring","cluster","random"),
        (0.0, 0.3, 0.5, 0.7, 0.9), num_nodes=N)``.

        Each optional axis arms independently: ``None`` (default) keeps
        it out of the cross product AND out of the compiled program, so
        a classic grid stays bitwise the pre-axis engine.  Labels stay
        3-tuples unless some axis is armed (then 6-tuples)."""
        sched_ax = tuple(str(s) for s in schedules) if schedules else None
        if sched_ax is not None:
            bad = [s for s in sched_ax if s not in ("bernoulli", "markov")]
            if bad:
                raise ValueError(f"unknown schedule(s) {bad!r}")
        skew_ax = tuple(float(v) for v in skews) if skews else None
        dp_ax = tuple(float(v) for v in dp_sigmas) if dp_sigmas else None
        armed = any(ax is not None for ax in (sched_ax, skew_ax, dp_ax))
        scenarios = [
            (str(t), float(r), sc, sk, dp, int(s))
            for t in topologies
            for r in inactive_ratios
            for sc in (sched_ax or ("bernoulli",))
            for sk in (skew_ax or (0.0,))
            for dp in (dp_ax or (0.0,))
            for s in seeds
        ]
        if not scenarios:
            raise ValueError("empty sweep grid")
        adjacency, resample = stacked_adjacency(
            [t for t, *_ in scenarios], num_nodes, cluster_size
        )
        return cls(
            adjacency=adjacency,
            resample=resample,
            inactive_ratio=jnp.asarray([r for _, r, *_ in scenarios], jnp.float32),
            init_keys=jnp.stack(
                [jax.random.PRNGKey(s) for *_, s in scenarios]
            ),
            labels=tuple(
                scenarios
                if armed
                else [(t, r, s) for t, r, _, _, _, s in scenarios]
            ),
            markov=(
                None
                if sched_ax is None
                else jnp.asarray(
                    [1.0 if sc == "markov" else 0.0 for _, _, sc, _, _, _ in scenarios],
                    jnp.float32,
                )
            ),
            skew=(
                None
                if skew_ax is None
                else jnp.asarray([sk for _, _, _, sk, _, _ in scenarios], jnp.float32)
            ),
            dp_sigma=(
                None
                if dp_ax is None
                else jnp.asarray([dp for _, _, _, _, dp, _ in scenarios], jnp.float32)
            ),
        )


class GluADFL:
    """Asynchronous decentralized FL trainer (the paper's contribution)."""

    def __init__(
        self,
        model: Model,
        optimizer: Optimizer,
        cfg: FLConfig,
        *,
        grad_at: str = "premix",
        use_kernel: bool = False,
        mixer: str | None = None,
        gossip_impl: str = "allgather",
        gossip_repr: str = "dense",
        dp_noise_sigma: float = 0.0,
        loss_fn: Callable | None = None,
        mesh=None,
    ):
        assert grad_at in ("premix", "mixed")
        # every gossip knob resolves HERE, once, into an explicit mixing
        # pipeline (core.gossip_plan): unknown values, unsupported
        # combinations and the deprecated use_kernel spelling all
        # surface at construction with the knob's name in the message
        self.plan = resolve_gossip_plan(
            mixer=mixer,
            use_kernel=use_kernel,
            gossip_impl=gossip_impl,
            gossip_repr=gossip_repr,
            dp_noise_sigma=dp_noise_sigma,
            mesh=mesh,
            num_nodes=cfg.num_nodes,
            comm_batch=cfg.comm_batch,
            topology=cfg.topology,
            cluster_size=cfg.cluster_size,
        )
        self.model = model
        self.optimizer = optimizer
        self.cfg = cfg
        self.grad_at = grad_at
        # resolved-knob mirrors, kept for back-compat introspection (the
        # plan is the source of truth)
        self.mixer = self.plan.mixer
        self.use_kernel = self.plan.use_kernel
        self.gossip_impl = self.plan.gossip_impl
        self.gossip_repr = self.plan.gossip_repr
        self._neighbor_cand = self.plan.neighbor_cand
        self.mesh = mesh                     # optional explicit mesh for "sharded"
        # BEYOND-PAPER: local differential privacy on the broadcast —
        # Gaussian noise is added to the parameters a node SHARES (its
        # own copy stays clean), so neighbours only ever see a noised
        # view.  sigma is in parameter units; the paper motivates privacy
        # but shares exact parameters — this closes that gap optionally.
        self.dp_noise_sigma = dp_noise_sigma
        self.loss_fn = loss_fn or (
            lambda p, x, y: jnp.mean(jnp.square(model.apply(p, x) - y))
        )
        self._round_jit = jax.jit(
            self._round,
            static_argnames=("batch_size", "eval_every", "eval_fn", "mesh"),
        )
        self._chunk_jit = jax.jit(
            self._train_chunk,
            static_argnames=("batch_size", "chunk", "eval_every", "eval_fn", "mesh"),
            donate_argnums=(0,),
        )
        self._sweep_chunk_jit = jax.jit(
            self._sweep_chunk,
            static_argnames=("batch_size", "chunk", "eval_every", "eval_fn", "mesh"),
            donate_argnums=(0,),
        )
        self._sweep_init_jit = jax.jit(jax.vmap(self.init))
        self._sweep_pop_jit = jax.jit(jax.vmap(tree_mean))
        # canonical eval fns are jit-static: keep them identity-stable so
        # repeated train() calls hit the compile cache
        self._eval_wrappers: dict[int, Callable] = {}

    # ------------------------------------------------------------------
    def init(self, key, example_x=None) -> FLState:
        """Fresh federation state (``example_x`` is unused — the models
        init from shapes in their own config — and kept only for
        call-site back-compat)."""
        n = self.cfg.num_nodes
        keys = jax.random.split(key, n + 1)
        params = jax.vmap(self.model.init)(keys[:n])
        opt_state = jax.vmap(self.optimizer.init)(params)
        return FLState(
            params=params,
            opt_state=opt_state,
            staleness=jnp.zeros((n,), jnp.float32),
            round=jnp.zeros((), jnp.int32),
            key=keys[n],
        )

    def state_shardings(self, mesh) -> FLState:
        """NamedShardings for every ``FLState`` leaf under a node-sharded
        federation mesh: node-stacked leaves (params/opt-state leaves the
        vmapped init gave a leading ``(N, ...)`` axis, staleness) split
        over the mesh's first axis; the round counter and RNG key are
        replicated UNCONDITIONALLY (the key is shape ``(2,)`` and must
        never trip the leading-dim heuristic when ``num_nodes == 2``)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = self.cfg.num_nodes
        axis = mesh.axis_names[0]
        node = NamedSharding(mesh, P(axis))
        repl = NamedSharding(mesh, P())
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        stacked = lambda tree: jax.tree.map(
            lambda s: node if s.ndim >= 1 and s.shape[0] == n else repl, tree
        )
        return FLState(
            params=stacked(shapes.params),
            opt_state=stacked(shapes.opt_state),
            staleness=node,
            round=repl,
            key=repl,
        )

    def init_sharded(self, key, mesh) -> FLState:
        """Multi-host-safe init: the state is BORN node-sharded on the
        (possibly process-spanning) federation mesh — every process runs
        the same compiled init from a replicated key and only ever
        materializes its own node rows.  Single-process meshes work too
        (it is then just an explicitly-placed :meth:`init`)."""
        from repro.launch.multihost import replicate

        shardings = self.state_shardings(mesh)
        return jax.jit(self.init, out_shardings=shardings)(
            replicate(mesh, np.asarray(key))
        )

    def _sweep_state_shardings(self, mesh) -> FLState:
        """NamedShardings for the ``(G, N, ...)`` grid-stacked ``FLState``
        on a 2-D (grid, node) sweep mesh: node-stacked leaves split over
        BOTH axes, per-scenario scalars (round counter, key chain,
        non-node optimizer leaves) over the grid axis only.  Field-wise
        like :meth:`state_shardings` — the ``(G, 2)`` key must never
        trip the node heuristic when ``num_nodes == 2``."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = self.cfg.num_nodes
        g_ax, n_ax = mesh.axis_names
        gn = NamedSharding(mesh, P(g_ax, n_ax))
        g_only = NamedSharding(mesh, P(g_ax))
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        stacked = lambda tree: jax.tree.map(
            lambda s: gn if s.ndim >= 1 and s.shape[0] == n else g_only, tree
        )
        return FLState(
            params=stacked(shapes.params),
            opt_state=stacked(shapes.opt_state),
            staleness=gn,
            round=g_only,
            key=g_only,
        )

    def _place_sweep_data(self, mesh, grid: SweepGrid, x, y, counts, val_x, val_y):
        """Place the scenario grid + federation data for the swept-
        sharded engine: per-scenario arrays split over the grid axis,
        the (shared) federation data over the node axis, validation set
        replicated — so no device ever materializes rows it doesn't
        own before the compiled program even starts."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        g_ax, n_ax = mesh.axis_names
        g_only = NamedSharding(mesh, P(g_ax))
        node = NamedSharding(mesh, P(n_ax))
        repl = NamedSharding(mesh, P())
        put_ax = lambda v: None if v is None else jax.device_put(v, g_only)
        grid = SweepGrid(
            adjacency=jax.device_put(grid.adjacency, g_only),
            resample=jax.device_put(grid.resample, g_only),
            inactive_ratio=jax.device_put(grid.inactive_ratio, g_only),
            init_keys=jax.device_put(grid.init_keys, g_only),
            labels=grid.labels,
            markov=put_ax(grid.markov),
            skew=put_ax(grid.skew),
            dp_sigma=put_ax(grid.dp_sigma),
        )
        x, y, counts = (jax.device_put(v, node) for v in (x, y, counts))
        if val_x is not None:
            val_x, val_y = (jax.device_put(v, repl) for v in (val_x, val_y))
        return grid, x, y, counts, val_x, val_y

    # ------------------------------------------------------------------
    def _sample_batch(self, key, x_node, y_node, count, batch_size):
        """Uniform with-replacement batch from one node's (padded) data."""
        idx = jax.random.randint(key, (batch_size,), 0, jnp.maximum(count, 1))
        return x_node[idx], y_node[idx]

    def _local_step(
        self, key, params_premix, params_mixed, opt_state, x, y, count,
        shift=None, *, batch_size,
    ):
        """One (or more) local SGD steps for a single node.

        ``shift`` (a per-node scalar, or ``None``) is the non-IID skew
        axis: it offsets the sampled batch — inputs AND targets — in
        (normalized) glucose units.  Shifting the gathered batch is
        bitwise-identical to gathering from host-pre-shifted arrays
        (``(x + c)[idx] == x[idx] + c``), which is what makes a skewed
        scenario's serial twin a plain ``train()`` on shifted data."""

        def one_step(carry, k):
            p_for_grad, p_apply, st = carry
            with _stage("local_step"):
                bx, by = self._sample_batch(k, x, y, count, batch_size)
                if shift is not None:
                    bx = bx + shift
                    by = by + shift
                loss, grads = jax.value_and_grad(self.loss_fn)(p_for_grad, bx, by)
            with _stage("adam"):
                new_p, new_st = self.optimizer.update(grads, st, p_apply)
            # subsequent local steps are ordinary SGD at the new params
            return (new_p, new_p, new_st), loss

        with _stage("local_step"):
            keys = jax.random.split(key, self.cfg.local_steps)
        first_grad_p = params_premix if self.grad_at == "premix" else params_mixed
        (p, _, st), losses = jax.lax.scan(
            one_step, (first_grad_p, params_mixed, opt_state), keys
        )
        with _stage("local_step"):
            loss = jnp.mean(losses)
        return p, st, loss

    # ------------------------------------------------------------------
    def _mix_repr(self, adj: jnp.ndarray, active) -> Any:
        """The round's mixing operator in the plan's representation:
        dense (N, N) ``mixing_matrix`` or sparse ``(idx, wgt)``
        neighbor table (densifying the latter reproduces the former
        bitwise)."""
        return self.plan.build_repr(adj, active)

    def _plain_mix(self, stacked: PyTree, mix: Any, mesh=None, active=None) -> PyTree:
        """The plan's noise-free contraction.  ``mix`` is the dense
        matrix or the sparse ``(idx, wgt)`` table per the plan's repr;
        dense identity rows already encode inactivity, the sparse paths
        take ``active`` for a bit-exact where-select.  ``mesh`` overrides
        the plan's mesh — the swept-sharded path threads its 2-D
        (grid, node) mesh down here."""
        return self.plan.mix(stacked, mix, active, mesh=mesh)

    def _gossip(
        self, premix: PyTree, mix: Any, active, k_dp, mesh=None, mask_ctx=None,
        dp_sigma=None,
    ) -> PyTree:
        """Steps 2+3 (+ optional local-DP broadcast noise, + optional
        pairwise-masked secure aggregation) — the plan's full pipeline.
        ``mask_ctx`` is the ``(mask_key, (idx, wgt))`` pair ``_round``
        builds for ``gossip_impl="masked"``."""
        return self.plan.gossip(
            premix, mix, active, k_dp,
            mesh=mesh, mask_ctx=mask_ctx, dp_sigma=dp_sigma,
        )

    def _gossip_base(
        self, premix: PyTree, mix: Any, active, k_dp, mesh=None, dp_sigma=None
    ) -> PyTree:
        """The unmasked gossip: the plan pipeline without the mask stage
        (kept as a named seam — the parity tests drive it directly)."""
        return self.plan.gossip(premix, mix, active, k_dp, mesh=mesh,
                                dp_sigma=dp_sigma)

    # ------------------------------------------------------------------
    def _default_eval_metrics(self, pop_params, val_x, val_y):
        """Built-in streaming-eval metric: val RMSE of the population
        model on the pre-batched validation set (scan constants)."""
        pred = self.model.apply(pop_params, val_x)
        return {"val_rmse": jnp.sqrt(jnp.mean(jnp.square(pred - val_y)))}

    def _resolve_eval_fn(self, eval_fn: Callable | None) -> Callable:
        """Normalize to the canonical ``f(pop_params, val_x, val_y) ->
        dict`` signature.  ``None`` -> built-in val-RMSE; a legacy 1-arg
        ``f(pop_params)`` is wrapped (wrapper cached per fn so the jit
        static-arg cache keeps hitting)."""
        if eval_fn is None:
            return self._default_eval_metrics
        try:
            n_params = len(inspect.signature(eval_fn).parameters)
        except (TypeError, ValueError):
            n_params = 3
        if n_params != 1:
            return eval_fn
        key = id(eval_fn)
        if key not in self._eval_wrappers:
            # bounded: each cached wrapper pins its eval_fn (which also
            # keeps the id stable) and is a distinct jit static arg, so a
            # long-lived sweep over fresh lambdas must not grow forever
            if len(self._eval_wrappers) >= 64:
                self._eval_wrappers.clear()
            self._eval_wrappers[key] = lambda pop, vx, vy: eval_fn(pop)
        return self._eval_wrappers[key]

    def _eval_metrics(self, params, new_round, val_x, val_y, eval_every, eval_fn):
        """The cond-guarded in-scan eval branch: at ``new_round %
        eval_every == 0`` boundaries compute ``eval_fn(population, val_x,
        val_y)``; off-boundary rounds return the same dict filled with
        NaN (the host-side sentinel) and pay only the predicate — the
        population average itself lives INSIDE the true branch, so
        off-boundary rounds skip the O(N·D) reduction too."""

        def run_eval(op):
            p, vx, vy = op
            return eval_fn(tree_mean(p), vx, vy)

        operand = (params, val_x, val_y)
        shapes = jax.eval_shape(run_eval, operand)
        if not isinstance(shapes, dict):
            raise TypeError(
                f"streaming eval_fn must return a dict of float scalars, "
                f"got {type(shapes).__name__}"
            )
        for k, s in shapes.items():
            if not (jnp.issubdtype(s.dtype, jnp.floating) and s.shape == ()):
                raise TypeError(
                    f"streaming eval_fn output {k!r} must be a floating "
                    f"SCALAR (NaN is the off-boundary sentinel and the "
                    f"history records floats), got {s.dtype}{s.shape}"
                )
        return jax.lax.cond(
            new_round % eval_every == 0,
            run_eval,
            lambda op: jax.tree.map(
                lambda s: jnp.full(s.shape, jnp.nan, s.dtype), shapes
            ),
            operand,
        )

    # ------------------------------------------------------------------
    def _round(
        self,
        state: FLState,
        x,
        y,
        counts,
        val_x=None,
        val_y=None,
        scenario=None,
        *,
        batch_size: int,
        eval_every: int = 0,
        eval_fn: Callable | None = None,
        mesh=None,
    ):
        """One FL round as a pure ``FLState -> (FLState, aux)`` body —
        directly scannable (train_chunk) and jit-able (loop engine).
        ``aux`` is the scalar loss, or ``(loss, metrics_dict)`` when the
        streaming-eval branch is armed (``eval_every > 0`` with an
        ``eval_fn``).

        ``scenario`` is ``None`` for the config-driven path, or a traced
        ``(adjacency (N,N), resample scalar, inactive_ratio scalar)``
        triple — optionally extended to a 6-tuple ``(..., markov, skew,
        dp_sigma)`` whose last three entries are each ``None`` (axis
        unarmed; the identical program as the triple) or a traced
        per-scenario scalar — overriding the config's topology/
        asynchrony/heterogeneity/privacy knobs.  The sweep engine vmaps
        this body over a stacked grid of such tuples.  The key stream is
        IDENTICAL either way: every round splits the same four subkeys;
        the markov and bernoulli schedules consume the SAME single
        ``uniform(k_act, (N,))`` draw, and the DP split is armed
        uniformly across a dp-armed grid — so a swept scenario
        reproduces its serial twin (``schedule=cfg.schedule``,
        ``data_skew``, ``dp_noise_sigma=sigma_g``) exactly.

        ``mesh`` (static) overrides the sharded mixer's mesh — the
        swept-sharded path threads the 2-D (grid, node) sweep mesh down
        to the gossip contraction; ``None`` keeps ``self.mesh`` /
        the default federation mesh."""
        cfg = self.cfg
        n = cfg.num_nodes
        sc_markov = sc_skew = sc_dp = None
        if scenario is not None and len(scenario) == 6:
            adj_static, resample, inactive_ratio, sc_markov, sc_skew, sc_dp = scenario
        elif scenario is not None:
            adj_static, resample, inactive_ratio = scenario

        with _stage("neighbours"):
            key, k_act, k_top, k_batch = jax.random.split(state.key, 4)
            # a node that ended last round with staleness 0 participated in
            # it — the markov chain's previous state, derivable in the swept
            # and serial paths alike (staleness is carried in FLState)
            prev_active = (state.staleness == 0).astype(jnp.float32)
            adj = None  # stays None on the sparse static-topology fast path
            if scenario is None:
                if cfg.schedule == "markov":
                    active = markov_active(
                        k_act, prev_active, cfg.p_stay_active, cfg.p_stay_inactive
                    )
                else:
                    active = bernoulli_active(k_act, n, cfg.inactive_ratio)
                if self._neighbor_cand is not None:
                    # sparse static topology: table straight from the host-
                    # built candidate lists — no (N, N) array in the program
                    cand_idx, cand_valid = self._neighbor_cand
                    mix = neighbor_table_from_candidates(
                        cand_idx, cand_valid, active, cfg.comm_batch
                    )
                else:
                    adj = round_adjacency(
                        cfg.topology, n, k_top, cfg.comm_batch, cfg.cluster_size
                    )
                    mix = self._mix_repr(adj, active)
            else:
                if sc_markov is None:
                    active = bernoulli_active(k_act, n, inactive_ratio)
                else:
                    # per-scenario schedule choice as a select: both masks
                    # read the SAME uniform(k_act, (N,)) draw, so arming the
                    # axis never shifts the main key chain
                    active = jnp.where(
                        sc_markov > 0,
                        markov_active(
                            k_act, prev_active, cfg.p_stay_active, cfg.p_stay_inactive
                        ),
                        bernoulli_active(k_act, n, inactive_ratio),
                    )
                # both graph flavours are cheap relative to the local step,
                # so the data-dependent choice is a select, not a cond:
                # random topologies draw from the SAME k_top a serial run
                # would use
                adj = jnp.where(
                    resample > 0,
                    random_adjacency(k_top, n, min(cfg.comm_batch, n - 1)),
                    adj_static,
                )
                mix = self._mix_repr(adj, active)
            k_dp = None
            if sc_dp is not None or self.dp_noise_sigma > 0.0:
                key, k_dp = jax.random.split(key)

        premix = state.params
        with _stage("gossip"):
            mask_ctx = None
            if self.plan.masked:
                # the mask stream is FOLDED off the round key, never split:
                # enabling secure aggregation must not perturb the
                # activity/topology/batch/DP key chain (the bitwise-parity
                # contract)
                k_mask = jax.random.fold_in(state.key, MASK_STREAM_TAG)
                mask_ctx = (k_mask, self.plan.mask_table(mix, adj, active))
            mixed = self._gossip(premix, mix, active, k_dp, mesh, mask_ctx, sc_dp)

        with _stage("local_step"):
            node_keys = jax.random.split(k_batch, n)
            args = (node_keys, premix, mixed, state.opt_state, x, y, counts)
            if sc_skew is not None or cfg.data_skew != 0.0:
                # per-node batch shift: the offsets are a trace-time
                # constant table, scaled by the (possibly traced) scenario
                # skew
                skew = cfg.data_skew if sc_skew is None else sc_skew
                args += (skew * jnp.asarray(node_skew_offsets(n)),)
        # the local step's scopes sit inside it, under the vmap and scan
        step = partial(self._local_step, batch_size=batch_size)
        new_params, new_opt, losses = jax.vmap(step)(*args)

        with _stage("select"):
            # inactive nodes keep their stale params / optimizer state.
            # jnp.where (not arithmetic blending) so inactive rows are
            # BITWISE copies and integer leaves (optimizer step) keep their
            # dtype — the scan carry must be type-stable across rounds.
            def mask(new, old):
                bshape = (n,) + (1,) * (new.ndim - 1)
                a = active.reshape(bshape) > 0
                return jnp.where(a, new, old)

            params = jax.tree.map(mask, new_params, premix)
            opt_state = jax.tree.map(
                lambda nw, od: mask(nw, od) if nw.ndim >= 1 and nw.shape[:1] == (n,) else nw,
                new_opt,
                state.opt_state,
            )
            loss = jnp.sum(losses * active) / jnp.maximum(jnp.sum(active), 1.0)
            new_round = state.round + 1
            staleness = staleness_update(state.staleness, active)
        aux = loss
        if eval_every and eval_fn is not None:
            metrics = self._eval_metrics(
                params, new_round, val_x, val_y, eval_every, eval_fn
            )
            aux = (loss, metrics)
        return (
            FLState(
                params=params,
                opt_state=opt_state,
                staleness=staleness,
                round=new_round,
                key=key,
            ),
            aux,
        )

    # ------------------------------------------------------------------
    def _train_chunk(
        self,
        state: FLState,
        x,
        y,
        counts,
        val_x=None,
        val_y=None,
        scenario=None,
        *,
        batch_size: int,
        chunk: int,
        eval_every: int = 0,
        eval_fn: Callable | None = None,
        mesh=None,
    ):
        def body(st, _):
            return self._round(
                st, x, y, counts, val_x, val_y, scenario,
                batch_size=batch_size, eval_every=eval_every, eval_fn=eval_fn,
                mesh=mesh,
            )

        return jax.lax.scan(body, state, None, length=chunk)

    def _sweep_chunk(
        self,
        states: FLState,
        adjacency,
        resample,
        inactive_ratio,
        extras,
        x,
        y,
        counts,
        val_x=None,
        val_y=None,
        *,
        batch_size: int,
        chunk: int,
        eval_every: int = 0,
        eval_fn: Callable | None = None,
        mesh=None,
    ):
        """``chunk`` rounds of EVERY scenario as one vmapped scan: the
        grid axis G batches the whole ``_train_chunk`` program (states,
        adjacencies, resample flags and inactive ratios all carry a
        leading G), while the federation data/validation set broadcast
        unbatched.  ``extras`` is a dict holding whichever optional
        scenario axes are armed (``"markov"``/``"skew"``/``"dp_sigma"``,
        each ``(G,)``) — an empty dict compiles the identical program as
        the classic 3-axis grid.  Returns ``(states, losses (G, chunk))``
        — plus a metrics dict of ``(G, chunk)`` records when eval is
        armed.

        Mixer dispatch: the tree mixer is a plain ``jax.vmap``.  The
        SHARDED mixer instead binds the vmapped axis to the 2-D sweep
        mesh's ``"grid"`` axis (``spmd_axis_name``): the per-scenario
        shard_map collectives inside ``_round`` keep their node-only
        axis names, and the batching rule turns them into ONE shard_map
        over the (grid, node) mesh whose in_specs gain a leading
        ``P("grid", ...)`` — the grid axis batches, the node axis
        communicates, and no collective crosses scenarios."""

        def one(state, adj, rs, ratio, extra):
            sc = (
                adj, rs, ratio,
                extra.get("markov"), extra.get("skew"), extra.get("dp_sigma"),
            )
            return self._train_chunk(
                state, x, y, counts, val_x, val_y, sc,
                batch_size=batch_size, chunk=chunk,
                eval_every=eval_every, eval_fn=eval_fn, mesh=mesh,
            )

        if self.plan.uses_mesh:
            return jax.vmap(one, spmd_axis_name=mesh.axis_names[0])(
                states, adjacency, resample, inactive_ratio, extras
            )
        return jax.vmap(one)(states, adjacency, resample, inactive_ratio, extras)

    def train_chunk(
        self,
        state: FLState,
        x,
        y,
        counts,
        *,
        batch_size: int = 64,
        chunk: int = DEFAULT_CHUNK,
        val_x=None,
        val_y=None,
        eval_every: int = 0,
        eval_fn: Callable | None = None,
    ) -> tuple[FLState, Any]:
        """Run ``chunk`` rounds as one compiled ``lax.scan`` program.

        Returns ``(new_state, losses)`` with ``losses.shape == (chunk,)``
        (per-round mean active loss, still on device — the caller decides
        when to sync).  With the streaming-eval branch armed
        (``eval_every > 0`` and an ``eval_fn``), returns
        ``(new_state, (losses, metrics))`` where ``metrics`` is a dict of
        ``(chunk,)`` arrays that hold the eval values at
        ``round % eval_every == 0`` boundaries and NaN elsewhere —
        eval never leaves the compiled program.  ``eval_fn`` must be the
        canonical traceable ``f(pop_params, val_x, val_y) -> dict``
        (see :meth:`_resolve_eval_fn`).  The input ``state``'s buffers
        are DONATED: do not reuse it after the call.  Recompiles once per
        distinct ``(batch_size, chunk, eval_every, eval_fn)`` tuple.
        """
        return self._chunk_jit(
            state, x, y, counts, val_x, val_y,
            batch_size=batch_size, chunk=chunk,
            eval_every=eval_every, eval_fn=eval_fn,
        )

    # ------------------------------------------------------------------
    def train(
        self,
        key,
        x,
        y,
        counts,
        *,
        batch_size: int = 64,
        rounds: int | None = None,
        eval_every: int = 0,
        eval_fn: Callable | None = None,
        val_data: tuple | None = None,
        chunk: int | None = None,
        engine: str = "scan",
    ):
        """Run T rounds; returns (population_params, history, state).

        Engine selection:

        * ``engine="scan"`` (default — the one production path): chunked
          ``train_chunk`` programs, one host sync per chunk, WITH OR
          WITHOUT eval.  ``eval_every > 0`` arms the in-scan streaming
          eval branch: ``eval_fn`` must be pure/traceable —
          ``f(pop_params, val_x, val_y) -> dict`` of float scalars (a
          legacy 1-arg ``f(pop_params)`` is auto-wrapped); with
          ``eval_fn=None`` and ``val_data=(val_x, val_y)`` the built-in
          population val-RMSE is used.  Eval values surface in the
          history at each boundary, same as the loop engine's records.
        * ``engine="loop"`` — explicit DEBUG fallback only (never
          selected automatically): per-round Python loop, one jit
          dispatch + host sync per round; ``eval_fn`` may be an
          arbitrary host callback (side effects, non-traceable code).
          Single-process only (its eval callback runs EAGERLY on the
          population params, which are not addressable across hosts).

        Multi-host: after ``launch.multihost.initialize`` this method is
        process-count aware — it requires ``mixer="sharded"``, places
        host-side ``x/y/counts`` node-sharded on the global federation
        mesh (each process materializes only its own rows; pre-placed
        global ``jax.Array`` inputs are used as-is), replicates the
        validation set, and inits the state with :meth:`init_sharded`.
        Every process runs the identical program and assembles the
        identical history from the replicated per-round losses.

        History is identical either way: one record per round, eval keys
        merged into the boundary rounds' records.
        """
        assert engine in ("scan", "loop"), engine
        rounds = rounds if rounds is not None else self.cfg.rounds
        multihost = jax.process_count() > 1
        if multihost:
            if engine == "loop":
                raise NotImplementedError(
                    "engine='loop' is the single-process debug fallback; "
                    "multi-host runs use the scan engine"
                )
            self.plan.require_multihost()
            from repro.core.distributed import _default_federation_mesh
            from repro.launch.multihost import place_federation

            mesh = self.mesh or _default_federation_mesh(self.cfg.num_nodes)
            if not (isinstance(x, jax.Array) and not x.is_fully_addressable):
                x, y, counts, val_data = place_federation(
                    mesh, x, y, counts, val_data
                )
            val_x, val_y = val_data if val_data is not None else (None, None)
            state = self.init_sharded(key, mesh)
        else:
            x, y = jnp.asarray(x), jnp.asarray(y)
            counts = jnp.asarray(counts)
            val_x = val_y = None
            if val_data is not None:
                val_x, val_y = (jnp.asarray(v) for v in val_data)
            state = self.init(key)
        do_eval = bool(eval_every) and (eval_fn is not None or val_data is not None)
        history: list[dict] = []

        if engine == "loop":
            resolved = self._resolve_eval_fn(eval_fn) if do_eval else None
            if do_eval and eval_fn is None:
                # the built-in metric is traceable: compile it, as the scan
                # engine does — eager op-by-op dispatch and XLA's fused
                # program differ in the last bits of the RMSE
                resolved = jax.jit(resolved)
            for t in range(rounds):
                state, loss = self._round_jit(state, x, y, counts, batch_size=batch_size)
                rec = {"round": t, "loss": float(loss)}
                if do_eval and (t + 1) % eval_every == 0:
                    out = resolved(self.population(state), val_x, val_y)
                    rec.update(
                        {k: (float(v) if hasattr(v, "item") else v)
                         for k, v in out.items()}
                    )
                history.append(rec)
            return self.population(state), history, state

        chunk = max(1, min(chunk or DEFAULT_CHUNK, rounds))
        full, rem = divmod(rounds, chunk)
        t = 0
        if do_eval:
            resolved = self._resolve_eval_fn(eval_fn)
            # the tail also runs as a (shorter) scan so eval stays inside
            # the compiled program for every round
            for c in [chunk] * full + ([rem] if rem else []):
                state, (losses, metrics) = self.train_chunk(
                    state, x, y, counts, batch_size=batch_size, chunk=c,
                    val_x=val_x, val_y=val_y,
                    eval_every=eval_every, eval_fn=resolved,
                )
                # ONE host sync per chunk, eval records included
                losses = _to_host(losses)
                metrics = {k: _to_host(v) for k, v in metrics.items()}
                for i in range(c):
                    rec = {"round": t + i, "loss": float(losses[i])}
                    if (t + i + 1) % eval_every == 0:
                        rec.update({k: float(v[i]) for k, v in metrics.items()})
                    history.append(rec)
                t += c
            return self.population(state), history, state

        for _ in range(full):
            state, losses = self.train_chunk(
                state, x, y, counts, batch_size=batch_size, chunk=chunk
            )
            # ONE host sync per chunk (vs one per round in the loop engine)
            for i, lv in enumerate(_to_host(losses).tolist()):
                history.append({"round": t + i, "loss": lv})
            t += chunk
        if rem and multihost:
            # the tail must stay a compiled scan: the per-round jit's
            # float(loss) sync can't read a cross-process scalar eagerly
            state, losses = self.train_chunk(
                state, x, y, counts, batch_size=batch_size, chunk=rem
            )
            for i, lv in enumerate(_to_host(losses).tolist()):
                history.append({"round": t + i, "loss": lv})
            t += rem
        elif rem:
            # drain the tail through the per-round jit: rem < chunk rounds
            # are not worth compiling a second whole-scan program for
            for _ in range(rem):
                state, loss = self._round_jit(state, x, y, counts, batch_size=batch_size)
                history.append({"round": t, "loss": float(loss)})
                t += 1
        return self.population(state), history, state

    # ------------------------------------------------------------------
    def train_sweep(
        self,
        x,
        y,
        counts,
        *,
        grid: SweepGrid,
        batch_size: int = 64,
        rounds: int | None = None,
        chunk: int | None = None,
        eval_every: int = 0,
        eval_fn: Callable | None = None,
        val_data: tuple | None = None,
    ):
        """Train EVERY scenario of ``grid`` as one batched device
        program; returns ``(populations, histories, states)``.

        This is the scenario-sweep engine: the per-round body is vmapped
        over the grid axis G — topologies enter as stacked per-scenario
        adjacency matrices (+ a resample flag for per-round random
        graphs), inactive ratios and seeds as plain ``(G,)``/``(G, 2)``
        arrays — so the whole Fig-4/Fig-5 grid compiles ONCE per chunk
        shape and executes as a single XLA program instead of G serial
        ``train()`` runs.  What is vmapped vs scan-carried:

          * vmapped (leading G): FLState leaves, adjacency, resample,
            inactive ratio, every per-round loss/eval record;
          * scan-carried (inside each scenario): the round counter, RNG
            key chain, staleness — exactly as in :meth:`train_chunk`;
          * broadcast (no G axis): the federation data ``x/y/counts``
            and the pre-batched validation set.

        Scenario ``g`` consumes the IDENTICAL key stream as a serial
        ``train(PRNGKey(seed_g), ...)`` run of the same config — the
        parity test pins this — so the sweep is a pure re-batching, not
        a re-definition, of the experiment.

        Returns:
          * ``populations`` — population params stacked ``(G, ...)``
            (index one out with ``utils.pytree.tree_index``);
          * ``histories`` — list of G per-scenario history lists, each
            record-compatible with :meth:`train` (eval keys merged into
            boundary rounds);
          * ``states`` — final ``FLState`` stacked ``(G, ...)``.

        Mixer dispatch — the sweep has two engines:

          * ``mixer="tree"`` — plain ``jax.vmap`` of the reference
            einsum path (the single-device default);
          * ``mixer="sharded"`` — the grid becomes a REAL mesh axis: the
            ``(G, N, ...)`` stacked state is placed on a 2-D
            ``("grid", "node")`` mesh (``self.mesh`` if given, else
            ``launch.mesh.make_sweep_mesh``), scenarios batch over
            ``"grid"`` while the gossip collectives (all-gather /
            psum-scatter, per ``gossip_impl``) stay scoped to
            ``"node"`` — per-device memory O(G/grid · N/node · D) with
            the psum schedule, so paper-scale federations sweep without
            any device holding the whole grid.

        Single-process only; the Pallas kernel mixer is a per-scenario
        program and still refuses (run it through serial :meth:`train`).
        """
        if jax.process_count() > 1:
            raise NotImplementedError(
                "train_sweep batches scenarios on ONE process; multi-host "
                "runs sweep via serial train() per scenario"
            )
        self.plan.require_sweep()
        n = self.cfg.num_nodes
        if grid.adjacency.shape[-1] != n:
            raise ValueError(
                f"grid built for N={grid.adjacency.shape[-1]} nodes but "
                f"cfg.num_nodes={n}"
            )
        rounds = rounds if rounds is not None else self.cfg.rounds
        x, y = jnp.asarray(x), jnp.asarray(y)
        counts = jnp.asarray(counts)
        val_x = val_y = None
        if val_data is not None:
            val_x, val_y = (jnp.asarray(v) for v in val_data)
        do_eval = bool(eval_every) and (eval_fn is not None or val_data is not None)
        resolved = self._resolve_eval_fn(eval_fn) if do_eval else None

        mesh = None
        if self.plan.uses_mesh:
            from repro.launch.mesh import make_sweep_mesh

            mesh = self.mesh or make_sweep_mesh(grid.size, n)
            if mesh.axis_names != ("grid", "node"):
                # the names are the contract: the gossip layer scopes its
                # collectives to "node" and batches over "grid" by name
                raise ValueError(
                    f"swept-sharded training needs a 2-D ('grid', 'node') "
                    f"mesh (launch.mesh.make_sweep_mesh), got axes "
                    f"{mesh.axis_names}"
                )
            g_ax, n_ax = mesh.axis_names
            if grid.size % mesh.shape[g_ax] or n % mesh.shape[n_ax]:
                raise ValueError(
                    f"sweep mesh {dict(mesh.shape)} does not divide the grid: "
                    f"G={grid.size}, N={n}"
                )

        states = self._sweep_init_jit(grid.init_keys)
        if mesh is not None:
            states = jax.device_put(states, self._sweep_state_shardings(mesh))
            grid, x, y, counts, val_x, val_y = self._place_sweep_data(
                mesh, grid, x, y, counts, val_x, val_y
            )
        g_count = grid.size
        # only armed axes enter the program: an unarmed grid's extras
        # dict is empty and the compiled sweep is the classic one
        extras = {
            k: v
            for k, v in (
                ("markov", grid.markov),
                ("skew", grid.skew),
                ("dp_sigma", grid.dp_sigma),
            )
            if v is not None
        }
        histories: list[list[dict]] = [[] for _ in range(g_count)]
        chunk = max(1, min(chunk or DEFAULT_CHUNK, rounds))
        full, rem = divmod(rounds, chunk)
        t = 0
        for c in [chunk] * full + ([rem] if rem else []):
            states, aux = self._sweep_chunk_jit(
                states, grid.adjacency, grid.resample, grid.inactive_ratio,
                extras, x, y, counts, val_x, val_y,
                batch_size=batch_size, chunk=c,
                eval_every=eval_every if do_eval else 0,
                eval_fn=resolved, mesh=mesh,
            )
            # ONE host sync per chunk for the WHOLE grid
            if do_eval:
                losses, metrics = aux
                metrics = {k: np.asarray(v) for k, v in metrics.items()}
            else:
                losses, metrics = aux, {}
            losses = np.asarray(losses)  # (G, c)
            for g in range(g_count):
                for i in range(c):
                    rec = {"round": t + i, "loss": float(losses[g, i])}
                    if do_eval and (t + i + 1) % eval_every == 0:
                        rec.update(
                            {k: float(v[g, i]) for k, v in metrics.items()}
                        )
                    histories[g].append(rec)
            t += c
        return self._sweep_pop_jit(states.params), histories, states

    # ------------------------------------------------------------------
    @staticmethod
    def population(state: FLState) -> PyTree:
        """Algorithm 1 lines 15-16: uniform average of all node models.

        Multi-host-safe: node-sharded params are reduced inside a jit
        (eager jnp ops refuse arrays that are not fully addressable);
        the result is replicated, so every process can fetch it."""
        leaves = jax.tree.leaves(state.params)
        if leaves and isinstance(leaves[0], jax.Array) and not leaves[0].is_fully_addressable:
            return jax.jit(tree_mean)(state.params)
        return tree_mean(state.params)
