"""Sparse (neighbor-table) gossip parity — the ``gossip_repr="sparse"``
representation must match the dense ``mixing_matrix`` contraction to
float tolerance (bitwise for inactive rows) at every layer: raw
contraction, trainer rounds at the paper's N=226 across all five
topologies (with active masks and DP noise), the sweep grid, and the
sharded mixer on a forced-8-device mesh (``multidevice`` marker)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig
from repro.core import GluADFL
from repro.core.gossip import gossip_mix_sparse_tree, gossip_mix_tree
from repro.core.topology import (
    densify_neighbor_table,
    neighbor_table,
    random_adjacency,
)
from repro.models import LSTMModel
from repro.optim import sgd
from repro.utils.pytree import tree_l2_norm, tree_sub

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the one-device auto boundary at B = 7: ONE_DEVICE_SPARSE_GOSSIP_FACTOR * 8
CROSS = 4096


def _run(src: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# contraction-level parity
# ---------------------------------------------------------------------------


def test_sparse_tree_matches_dense_tree():
    n, d = 30, 130
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    adj = random_adjacency(k[0], n, 5)
    active = (jax.random.uniform(k[1], (n,)) > 0.4).astype(jnp.float32)
    idx, wgt = neighbor_table(adj, active, 5)
    w = {"a": jax.random.normal(k[2], (n, d)), "b": jnp.ones((n, 3, 7))}
    sparse = gossip_mix_sparse_tree(w, idx, wgt, active)
    dense = gossip_mix_tree(w, densify_neighbor_table(idx, wgt))
    for kk in w:
        np.testing.assert_allclose(
            np.asarray(sparse[kk]), np.asarray(dense[kk]), atol=1e-5
        )
        for i in np.where(np.asarray(active) == 0)[0]:
            np.testing.assert_array_equal(
                np.asarray(sparse[kk])[i], np.asarray(w[kk])[i]
            )


def test_dense_tree_gossip_lowers_at_highest_precision():
    """The dense contraction's dots run at ``HIGHEST`` precision — f32-
    exact on the TPU, where the default is one bfloat16 pass.  CPU f32
    dots are exact either way, so the lowering is what shows it."""
    w = {"a": jnp.ones((6, 3, 4)), "b": jnp.ones((6, 2))}
    text = jax.jit(gossip_mix_tree).lower(w, jnp.eye(6)).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 2
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots)


# ---------------------------------------------------------------------------
# trainer-level parity at the paper's scale (N=226, REPLACE-BG)
# ---------------------------------------------------------------------------

N226 = 226


def _federation(n, windows=16, hist=12, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, windows, hist)).astype(np.float32)
    y = (x @ rng.normal(size=(hist,)).astype(np.float32)).astype(np.float32)
    return x, y, np.full((n,), windows, np.int32)


def _chunk_losses(cfg, x, y, counts, *, gossip_repr, mixer="tree", sigma=0.0,
                  rounds=2):
    tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg, mixer=mixer,
                 dp_noise_sigma=sigma, gossip_repr=gossip_repr)
    state = tr.init(jax.random.PRNGKey(0))
    state, losses = tr.train_chunk(state, x, y, counts, batch_size=4,
                                   chunk=rounds)
    return state, np.asarray(losses)


@pytest.mark.parametrize("topology", ["ring", "cluster", "star", "full", "random"])
def test_trainer_sparse_matches_dense_n226(topology):
    """Paper-scale parity: 2 rounds at N=226 with a 30% inactive mask and
    DP broadcast noise — sparse and dense trainers consume the identical
    key stream, so losses match to float tolerance and the final params
    differ only by contraction reassociation."""
    x, y, counts = _federation(N226)
    cfg = FLConfig(topology=topology, num_nodes=N226, rounds=2, comm_batch=7,
                   inactive_ratio=0.3)
    sd, ld = _chunk_losses(cfg, x, y, counts, gossip_repr="dense", sigma=0.05)
    ss, ls = _chunk_losses(cfg, x, y, counts, gossip_repr="sparse", sigma=0.05)
    np.testing.assert_allclose(ld, ls, atol=1e-5)
    assert float(tree_l2_norm(tree_sub(sd.params, ss.params))) < 1e-4


def test_trainer_sparse_kernel_matches_dense_kernel_n226():
    """The fused sparse DP kernel path against the fused dense DP kernel
    at N=226 (mixer="kernel" exercises ops.py padding + Pallas body)."""
    x, y, counts = _federation(N226)
    cfg = FLConfig(topology="random", num_nodes=N226, rounds=2, comm_batch=7,
                   inactive_ratio=0.3)
    sd, ld = _chunk_losses(cfg, x, y, counts, gossip_repr="dense",
                           mixer="kernel", sigma=0.05)
    ss, ls = _chunk_losses(cfg, x, y, counts, gossip_repr="sparse",
                           mixer="kernel", sigma=0.05)
    np.testing.assert_allclose(ld, ls, atol=1e-5)
    assert float(tree_l2_norm(tree_sub(sd.params, ss.params))) < 1e-4


def test_trainer_inactive_rows_bitwise_frozen_sparse():
    """Inactive nodes' params are BITWISE identical between sparse and
    dense runs (both freeze them with a where-select)."""
    n = 32
    x, y, counts = _federation(n)
    cfg = FLConfig(topology="ring", num_nodes=n, rounds=3, comm_batch=7,
                   inactive_ratio=0.6)
    sd, _ = _chunk_losses(cfg, x, y, counts, gossip_repr="dense", rounds=3)
    ss, _ = _chunk_losses(cfg, x, y, counts, gossip_repr="sparse", rounds=3)
    # staleness > 0 marks nodes inactive in the LAST round: their rows
    # were frozen that round, so both reprs carry the same bits forward
    stale = np.asarray(sd.staleness) > 0
    np.testing.assert_array_equal(np.asarray(sd.staleness),
                                  np.asarray(ss.staleness))
    assert stale.any(), "want at least one inactive node in the last round"
    for a, b in zip(jax.tree.leaves(sd.params), jax.tree.leaves(ss.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_sweep_grid_sparse_matches_dense_n226():
    """The sweep engine under ``gossip_repr="sparse"``: all five
    topologies as one vmapped grid at N=226 match the dense sweep's
    losses scenario-for-scenario."""
    from repro.core import SweepGrid

    x, y, counts = _federation(N226, windows=8)
    topos = ["ring", "cluster", "star", "full", "random"]
    grid = SweepGrid.build(topos, [0.4], [0], num_nodes=N226)

    def sweep(repr_):
        cfg = FLConfig(topology="ring", num_nodes=N226, rounds=2, comm_batch=7)
        tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                     gossip_repr=repr_)
        return tr.train_sweep(x, y, counts, grid=grid, batch_size=4, chunk=2)

    pops_d, h_dense, _ = sweep("dense")
    pops_s, h_sparse, _ = sweep("sparse")
    assert float(tree_l2_norm(tree_sub(pops_d, pops_s))) < 1e-4
    for g, label in enumerate(grid.labels):
        for rd, rs in zip(h_dense[g], h_sparse[g]):
            assert abs(rd["loss"] - rs["loss"]) < 1e-5, (label, rd, rs)


def test_sparse_ring_scales_without_dense_matrix():
    """Population-scale smoke: a 1 000-node ring federation trains a
    round through the candidate-list path (the trainer holds a (N, 3)
    table; no (N, N) array exists in the round program)."""
    n = 1000
    x, y, counts = _federation(n, windows=2, hist=6)
    cfg = FLConfig(topology="ring", num_nodes=n, rounds=1, comm_batch=7,
                   inactive_ratio=0.2)
    tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                 gossip_repr="sparse")
    assert tr._neighbor_cand is not None
    assert tr._neighbor_cand[0].shape == (n, 2)  # ring: 2 candidates/node
    state = tr.init(jax.random.PRNGKey(0))
    state, loss = tr.train_chunk(state, x, y, counts, batch_size=2, chunk=1)
    assert np.isfinite(np.asarray(loss)).all()


# ---------------------------------------------------------------------------
# knob resolution
# ---------------------------------------------------------------------------


def test_auto_gossip_repr_resolution():
    from repro.launch.mesh import choose_gossip_repr

    # node axis on one device: dense below the dense/sparse crossover
    assert choose_gossip_repr(226, 7) == "dense"    # paper scale
    assert choose_gossip_repr(16, 7) == "dense"     # smoke scale
    assert choose_gossip_repr(CROSS, 7) == "sparse"  # boundary: FACTOR*(7+1)
    assert choose_gossip_repr(CROSS - 1, 7) == "dense"
    assert choose_gossip_repr(32, 7) == "dense"     # the across-device 4*(7+1)

    cfg = FLConfig(topology="ring", num_nodes=226, rounds=1, comm_batch=7)
    tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                 gossip_repr="auto")
    assert tr.gossip_repr == "dense"
    cfg16 = FLConfig(topology="ring", num_nodes=16, rounds=1, comm_batch=7)
    tr16 = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg16,
                   gossip_repr="auto")
    assert tr16.gossip_repr == "dense"
    cfg_big = FLConfig(topology="ring", num_nodes=CROSS, rounds=1, comm_batch=7)
    tr_big = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg_big,
                     gossip_repr="auto")
    assert tr_big.gossip_repr == "sparse"


def _gather_rows(trainer, n):
    """Leading dims of every gather in the compiled round at ``n`` nodes
    (random graph, 30% inactive)."""
    import functools
    import re

    x, y, counts = _federation(n)
    state = trainer.init(jax.random.PRNGKey(0))
    round_fn = jax.jit(functools.partial(trainer._round, batch_size=4))
    text = round_fn.lower(state, x, y, counts).compile().as_text()
    return {int(m) for m in re.findall(r"= [a-z0-9]+\[(\d+)[\],][^=]*\bgather\(", text)}


def test_auto_round_at_n226_has_no_neighbour_table():
    """At the paper's N=226, B=7, ``gossip_repr="auto"`` resolves to the
    dense contraction: the compiled round gathers no (N·(B+1), ·) rows
    and builds no neighbour table (its (N·N,) activity gather).  The
    explicit sparse trainer, compiled the same way, has both."""
    n, b = N226, 7
    cfg = FLConfig(topology="random", num_nodes=n, rounds=1, comm_batch=b,
                   inactive_ratio=0.3)
    auto = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                   gossip_repr="auto")
    sparse = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                     gossip_repr="sparse")
    assert auto.gossip_repr == "dense"
    table_rows = {n * (b + 1), n * n}
    assert not _gather_rows(auto, n) & table_rows
    assert _gather_rows(sparse, n) >= table_rows


def test_bad_gossip_repr_rejected():
    with pytest.raises(ValueError, match="gossip_repr"):
        GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2),
                FLConfig(num_nodes=4, rounds=1), gossip_repr="csr")


# ---------------------------------------------------------------------------
# sharded mixer (multidevice)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_sharded_sparse_matches_dense_and_tree():
    """``sharded_gossip_mix_sparse`` on 8 forced devices == the dense
    sharded mix == the single-device tree reference, with bit-exact
    inactive rows."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import sharded_gossip_mix, sharded_gossip_mix_sparse
        from repro.core.gossip import gossip_mix_tree
        from repro.core.topology import mixing_matrix, neighbor_table, random_adjacency
        N, D = 8, 96
        k = jax.random.split(jax.random.PRNGKey(0), 4)
        w = {"a": jax.random.normal(k[0], (N, D)),
             "b": jax.random.normal(k[1], (N, 3, 7))}
        active = (jax.random.uniform(k[2], (N,)) > 0.4).astype(jnp.float32)
        adj = random_adjacency(jax.random.PRNGKey(7), N, 3)
        mix = mixing_matrix(adj, active, 3)
        idx, wgt = neighbor_table(adj, active, 3)
        sp = jax.jit(lambda ww, ii, gg, aa: sharded_gossip_mix_sparse(ww, ii, gg, aa))(w, idx, wgt, active)
        dn = jax.jit(lambda ww, mm, aa: sharded_gossip_mix(ww, mm, aa))(w, mix, active)
        ref = gossip_mix_tree(w, mix)
        for kk in w:
            np.testing.assert_allclose(np.asarray(sp[kk]), np.asarray(dn[kk]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(sp[kk]), np.asarray(ref[kk]), atol=1e-5)
            bad = np.where(np.asarray(active) == 0)[0]
            np.testing.assert_array_equal(np.asarray(sp[kk])[bad], np.asarray(w[kk])[bad])
        print("SHARDED_SPARSE_OK")
    """))


@pytest.mark.multidevice
def test_sharded_sparse_grid_stacked():
    """Grid-stacked (G, N, B+1) tables on a 2-D ("grid", "node") mesh:
    the sparse shard body batches under the grid axis exactly like the
    dense one (scenario-for-scenario parity)."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import sharded_gossip_mix, sharded_gossip_mix_sparse
        from repro.core.topology import (mixing_matrix_stacked, random_adjacency,
                                         stacked_neighbor_table)
        G, N, D = 4, 8, 64
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("grid", "node"))
        adjs = jnp.stack([random_adjacency(jax.random.PRNGKey(i), N, 3) for i in range(G)])
        acts = (jax.random.uniform(jax.random.PRNGKey(9), (G, N)) > 0.3).astype(jnp.float32)
        si, sw = stacked_neighbor_table(adjs, acts, 3)
        ms = mixing_matrix_stacked(adjs, acts, 3)
        w = {"a": jax.random.normal(jax.random.PRNGKey(1), (G, N, D))}
        sp = jax.jit(lambda ww, ii, gg, aa: sharded_gossip_mix_sparse(ww, ii, gg, aa, mesh=mesh))(w, si, sw, acts)
        dn = jax.jit(lambda ww, mm, aa: sharded_gossip_mix(ww, mm, aa, mesh=mesh))(w, ms, acts)
        np.testing.assert_allclose(np.asarray(sp["a"]), np.asarray(dn["a"]), atol=1e-5)
        print("GRID_SPARSE_OK")
    """))


@pytest.mark.multidevice
def test_trainer_sharded_sparse_trains_like_dense():
    """GluADFL end-to-end with mixer="sharded": gossip_repr="sparse"
    matches the dense sharded run's losses and final params."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import FLConfig
        from repro.core import GluADFL
        from repro.models import LSTMModel
        from repro.optim import sgd
        from repro.utils.pytree import tree_l2_norm, tree_sub
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 20, 12)).astype(np.float32)
        y = (x @ rng.normal(size=(12,)).astype(np.float32)).astype(np.float32)
        counts = np.full((8,), 20, np.int32)
        cfg = FLConfig(topology="random", num_nodes=8, rounds=4,
                       comm_batch=3, inactive_ratio=0.25)
        def train(repr_):
            tr = GluADFL(LSTMModel(hidden=8).as_model(), sgd(1e-2), cfg,
                         mixer="sharded", gossip_repr=repr_, dp_noise_sigma=0.02)
            return tr.train(jax.random.PRNGKey(0), x, y, counts,
                            batch_size=8, chunk=4)
        p_d, h_d, _ = train("dense")
        p_s, h_s, _ = train("sparse")
        assert len(h_d) == len(h_s) == 4
        assert float(tree_l2_norm(tree_sub(p_d, p_s))) < 1e-4
        for a, b in zip(h_d, h_s):
            assert abs(a["loss"] - b["loss"]) < 1e-4, (a, b)
        print("SHARDED_SPARSE_TRAIN_OK")
    """))


@pytest.mark.multidevice
def test_swept_sharded_sparse_matches_dense():
    """The swept-sharded engine (vmap with spmd_axis_name over the 2-D
    sweep mesh) under gossip_repr="sparse": per-scenario losses match
    the dense swept-sharded run."""
    print(_run("""
        import jax, numpy as np
        from repro.config import FLConfig
        from repro.core import GluADFL, SweepGrid
        from repro.launch.mesh import make_sweep_mesh
        from repro.models import LSTMModel
        from repro.optim import sgd
        rng = np.random.default_rng(0)
        N = 8
        x = rng.normal(size=(N, 10, 12)).astype(np.float32)
        y = (x @ rng.normal(size=(12,)).astype(np.float32)).astype(np.float32)
        counts = np.full((N,), 10, np.int32)
        grid = SweepGrid.build(["ring", "random"], [0.0, 0.5], [0], num_nodes=N)
        mesh = make_sweep_mesh(grid.size, N)
        def sweep(repr_):
            cfg = FLConfig(topology="ring", num_nodes=N, rounds=2, comm_batch=3)
            tr = GluADFL(LSTMModel(hidden=8).as_model(), sgd(1e-2), cfg,
                         mixer="sharded", gossip_repr=repr_, mesh=mesh)
            return tr.train_sweep(x, y, counts, grid=grid, batch_size=4, chunk=2)
        _, h_d, _ = sweep("dense")
        _, h_s, _ = sweep("sparse")
        for g in range(grid.size):
            for rd, rs in zip(h_d[g], h_s[g]):
                assert abs(rd["loss"] - rs["loss"]) < 1e-5, (g, rd, rs)
        print("SWEPT_SHARDED_SPARSE_OK")
    """))
