"""Scenario-sweep engine tests: the vmapped (topology x inactive-ratio
x seed) grid must NUMERICALLY MATCH per-config serial train() runs —
params, losses, streaming-eval records — including DP-noise and
inactive-mask cases, plus the batched topology/scheduling builders the
engine is made of.  An 8-forced-device subprocess case pins the same
parity on the multi-device path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig, SweepConfig
from repro.core import GluADFL, SweepGrid, sweep_active_masks
from repro.core.async_sched import bernoulli_active
from repro.core.topology import (
    cluster_adjacency,
    mixing_matrix,
    mixing_matrix_stacked,
    ring_adjacency,
    stacked_adjacency,
)
from repro.models import LSTMModel
from repro.optim import adam, sgd
from repro.utils.pytree import tree_index, tree_l2_norm, tree_sub
from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_fed(n=6, m=40, L=12, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, m, L)).astype(np.float32)
    w_true = rng.normal(size=(L,)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.normal(size=(n, m)).astype(np.float32)
    counts = np.full((n,), m, np.int32)
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(counts)


def _val_set(m=24, L=12, seed=7):
    rng = np.random.default_rng(seed)
    vx = rng.normal(size=(m, L)).astype(np.float32)
    vy = (vx @ rng.normal(size=(L,)).astype(np.float32)).astype(np.float32)
    return jnp.asarray(vx), jnp.asarray(vy)


# ----------------------------------------------------------------------
# batched builders
# ----------------------------------------------------------------------

def test_stacked_adjacency_matches_static_builders():
    n = 8
    adj, resample = stacked_adjacency(["ring", "cluster", "random"], n)
    assert adj.shape == (3, n, n) and resample.shape == (3,)
    np.testing.assert_array_equal(np.asarray(adj[0]), np.asarray(ring_adjacency(n)))
    np.testing.assert_array_equal(
        np.asarray(adj[1]), np.asarray(cluster_adjacency(n, 4))
    )
    # "random" scenarios: zero placeholder + resample flag
    np.testing.assert_array_equal(np.asarray(adj[2]), np.zeros((n, n)))
    np.testing.assert_array_equal(np.asarray(resample), [0.0, 0.0, 1.0])


def test_stacked_adjacency_unknown_topology_raises():
    with pytest.raises(KeyError):
        stacked_adjacency(["ring", "moebius"], 8)


def test_mixing_matrix_stacked_matches_single():
    n = 8
    adj, _ = stacked_adjacency(["ring", "cluster"], n)
    key = jax.random.PRNGKey(0)
    active = (jax.random.uniform(key, (2, n)) > 0.3).astype(jnp.float32)
    stacked = mixing_matrix_stacked(adj, active, 3)
    for g in range(2):
        np.testing.assert_array_equal(
            np.asarray(stacked[g]),
            np.asarray(mixing_matrix(adj[g], active[g], 3)),
        )


def test_sweep_active_masks_per_scenario_keys():
    """(G, N) masks: scenario g bitwise-matches bernoulli_active on the
    g-th split key; ratio 0 activates everyone, high ratio >= 1 active."""
    key = jax.random.PRNGKey(3)
    ratios = jnp.asarray([0.0, 0.4, 0.99])
    masks = sweep_active_masks(key, 16, ratios)
    assert masks.shape == (3, 16)
    keys = jax.random.split(key, 3)
    for g, r in enumerate([0.0, 0.4, 0.99]):
        np.testing.assert_array_equal(
            np.asarray(masks[g]),
            np.asarray(bernoulli_active(keys[g], 16, jnp.float32(r))),
        )
    np.testing.assert_array_equal(np.asarray(masks[0]), np.ones(16))
    assert np.asarray(masks).sum(axis=1).min() >= 1


def test_bernoulli_active_traced_ratio_matches_concrete_shortcut():
    """The sweep engine feeds the ratio as a TRACED scalar; ratio 0 must
    still mean 'everyone active', matching the python-float shortcut."""
    key = jax.random.PRNGKey(11)
    concrete = bernoulli_active(key, 12, 0.0)
    traced = jax.jit(lambda r: bernoulli_active(key, 12, r))(jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(concrete), np.asarray(traced))


def test_sweep_grid_build_layout():
    grid = SweepGrid.build(("ring", "random"), (0.0, 0.5), (0, 1), num_nodes=6)
    assert grid.size == 8
    # topology-major, then ratio, then seed — the documented order
    assert grid.labels[0] == ("ring", 0.0, 0)
    assert grid.labels[1] == ("ring", 0.0, 1)
    assert grid.labels[4] == ("random", 0.0, 0)
    assert grid.adjacency.shape == (8, 6, 6)
    np.testing.assert_array_equal(
        np.asarray(grid.resample), [0, 0, 0, 0, 1, 1, 1, 1]
    )
    np.testing.assert_array_equal(
        np.asarray(grid.init_keys[1]), np.asarray(jax.random.PRNGKey(1))
    )
    cfg = SweepConfig()
    fig5 = SweepGrid.build(cfg.topologies, cfg.inactive_ratios,
                           cfg.seed_list(), num_nodes=6)
    assert fig5.size == 15  # the paper's Fig-5 grid


# ----------------------------------------------------------------------
# engine parity
# ----------------------------------------------------------------------

def _serial_histories(model, grid, x, y, counts, *, rounds, chunk,
                      dp_sigma=0.0, optimizer=None, eval_every=0, val=None):
    """Per-config serial train() runs — the oracle the sweep must match."""
    pops, hists, states = [], [], []
    for topo, ratio, seed in grid.labels:
        cfg = FLConfig(topology=topo, num_nodes=x.shape[0], comm_batch=3,
                       rounds=rounds, inactive_ratio=ratio)
        tr = GluADFL(model, optimizer or sgd(1e-2), cfg, dp_noise_sigma=dp_sigma)
        pop, hist, st = tr.train(
            jax.random.PRNGKey(seed), x, y, counts, batch_size=8, chunk=chunk,
            eval_every=eval_every, val_data=val,
        )
        pops.append(pop)
        hists.append(hist)
        states.append(st)
    return pops, hists, states


@pytest.mark.parametrize("dp_sigma", [0.0, 0.05])
def test_train_sweep_matches_serial_runs(dp_sigma):
    """The whole vmapped grid == per-config serial train(): losses,
    streaming-eval records, population params and final state — incl.
    the DP-noise path and non-zero inactive ratios, across a chunk
    remainder (rounds % chunk != 0)."""
    rounds, chunk, eval_every = 6, 4, 2
    x, y, counts = _toy_fed()
    model = LSTMModel(hidden=8).as_model()
    val = _val_set()
    if dp_sigma:
        grid = SweepGrid.build(("cluster", "random"), (0.3,), (0,),
                               num_nodes=6)
    else:
        grid = SweepGrid.build(("ring", "random"), (0.0, 0.4), (0, 1),
                               num_nodes=6)

    cfg = FLConfig(topology="ring", num_nodes=6, comm_batch=3, rounds=rounds)
    tr = GluADFL(model, sgd(1e-2), cfg, dp_noise_sigma=dp_sigma)
    pops, hists, states = tr.train_sweep(
        x, y, counts, grid=grid, batch_size=8, chunk=chunk,
        eval_every=eval_every, val_data=val,
    )

    s_pops, s_hists, s_states = _serial_histories(
        model, grid, x, y, counts, rounds=rounds, chunk=chunk,
        dp_sigma=dp_sigma, eval_every=eval_every, val=val,
    )
    for g in range(grid.size):
        assert len(hists[g]) == rounds
        assert [h["round"] for h in hists[g]] == list(range(rounds))
        for hs, hl in zip(hists[g], s_hists[g]):
            assert abs(hs["loss"] - hl["loss"]) < 1e-5
            assert ("val_rmse" in hs) == ("val_rmse" in hl)
            if "val_rmse" in hs:
                assert abs(hs["val_rmse"] - hl["val_rmse"]) < 1e-5
        assert float(
            tree_l2_norm(tree_sub(tree_index(pops, g), s_pops[g]))
        ) < 1e-5
        # final state: round counter, staleness, key chain all line up
        assert int(jax.tree.leaves(states.round)[0][g]) == rounds
        np.testing.assert_array_equal(
            np.asarray(states.key[g]), np.asarray(s_states[g].key)
        )
        np.testing.assert_allclose(
            np.asarray(states.staleness[g]),
            np.asarray(s_states[g].staleness),
        )


def test_train_sweep_adam_population_matches_serial():
    """Parity also holds with a stateful optimizer (Adam moments ride
    the vmapped scan carry)."""
    rounds = 5
    x, y, counts = _toy_fed()
    model = LSTMModel(hidden=8).as_model()
    grid = SweepGrid.build(("cluster",), (0.2,), (0, 1), num_nodes=6)
    cfg = FLConfig(topology="cluster", num_nodes=6, comm_batch=3, rounds=rounds)
    tr = GluADFL(model, adam(5e-3), cfg)
    pops, hists, _ = tr.train_sweep(x, y, counts, grid=grid, batch_size=8)
    s_pops, s_hists, _ = _serial_histories(
        model, grid, x, y, counts, rounds=rounds, chunk=None,
        optimizer=adam(5e-3),
    )
    for g in range(grid.size):
        for hs, hl in zip(hists[g], s_hists[g]):
            assert abs(hs["loss"] - hl["loss"]) < 1e-5
        assert float(
            tree_l2_norm(tree_sub(tree_index(pops, g), s_pops[g]))
        ) < 1e-5


def test_train_sweep_compiled_execution_budget():
    """The Fig-5 grid (3 topologies x 5 ratios) must run in <= 3
    compiled sweep executions — one batched program per chunk shape,
    never per scenario."""
    x, y, counts = _toy_fed()
    model = LSTMModel(hidden=8).as_model()
    cfg = SweepConfig()
    grid = SweepGrid.build(cfg.topologies, cfg.inactive_ratios,
                           cfg.seed_list(), num_nodes=6)
    assert grid.size == 15
    tr = GluADFL(model, sgd(1e-2), FLConfig(num_nodes=6, comm_batch=3))
    calls = []
    real = tr._sweep_chunk_jit

    def counting(*a, **kw):
        calls.append(kw.get("chunk"))
        return real(*a, **kw)

    tr._sweep_chunk_jit = counting
    _, hists, _ = tr.train_sweep(x, y, counts, grid=grid, batch_size=8,
                                 rounds=10, chunk=8)
    assert len(calls) <= 3, calls          # 8 + 2 -> two executions
    assert len({c for c in calls}) == len(calls)  # distinct chunk shapes
    assert all(len(h) == 10 for h in hists)


def test_train_sweep_guards():
    """Wrong-N grids, the per-scenario Pallas mixer, and non-sweep
    meshes must refuse loudly."""
    model = LSTMModel(hidden=8).as_model()
    grid = SweepGrid.build(("ring",), (0.0,), (0,), num_nodes=4)
    tr = GluADFL(model, sgd(1e-2), FLConfig(num_nodes=6))
    with pytest.raises(ValueError, match="num_nodes"):
        tr.train_sweep(*_toy_fed(), grid=grid)
    grid6 = SweepGrid.build(("ring",), (0.0,), (0,), num_nodes=6)
    tr_kernel = GluADFL(model, sgd(1e-2), FLConfig(num_nodes=6),
                        mixer="kernel")
    with pytest.raises(NotImplementedError, match="kernel"):
        tr_kernel.train_sweep(*_toy_fed(), grid=grid6)
    # the swept-sharded engine needs the 2-D (grid, node) mesh — a 1-D
    # federation mesh is the serial train() layout, not the sweep's
    tr_1d = GluADFL(model, sgd(1e-2), FLConfig(num_nodes=6),
                    mixer="sharded", mesh=make_mesh((1,), ("node",)))
    with pytest.raises(ValueError, match="2-D"):
        tr_1d.train_sweep(*_toy_fed(), grid=grid6)
    with pytest.raises(ValueError, match="empty"):
        SweepGrid.build((), (0.0,), (0,), num_nodes=6)


def test_train_sweep_sharded_matches_tree_in_process():
    """The swept-sharded engine must match the swept tree mixer exactly
    — same key streams, same losses, same populations — on whatever
    sweep mesh the test process's devices give (a degenerate (1, 1)
    local mesh on one device; a real multi-device mesh when another
    test module forced an XLA device count).  This keeps the 2-D
    dispatch path covered by tier-1; the pinned-layout multi-device
    parity lives in the ``multidevice`` test below."""
    rounds = 4
    x, y, counts = _toy_fed()
    model = LSTMModel(hidden=8).as_model()
    grid = SweepGrid.build(("ring", "random"), (0.0, 0.4), (0,), num_nodes=6)
    cfg = FLConfig(num_nodes=6, comm_batch=3, rounds=rounds)
    pops_t, hists_t, _ = GluADFL(model, sgd(1e-2), cfg).train_sweep(
        x, y, counts, grid=grid, batch_size=8
    )
    for impl in ("allgather", "psum"):
        tr = GluADFL(model, sgd(1e-2), cfg, mixer="sharded", gossip_impl=impl)
        pops_s, hists_s, _ = tr.train_sweep(x, y, counts, grid=grid, batch_size=8)
        for g in range(grid.size):
            for a, b in zip(hists_s[g], hists_t[g]):
                assert abs(a["loss"] - b["loss"]) < 1e-5, (impl, g, a, b)
            assert float(
                tree_l2_norm(tree_sub(tree_index(pops_s, g), tree_index(pops_t, g)))
            ) < 1e-5, (impl, g)


@pytest.mark.multidevice
def test_train_sweep_parity_on_forced_8_devices():
    """The sweep/serial parity must survive a real multi-device topology
    (the vmapped program and the serial scans both run on the forced
    8-device CPU platform CI uses for collective tests)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    src = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import FLConfig
        from repro.core import GluADFL, SweepGrid
        from repro.models import LSTMModel
        from repro.optim import sgd
        from repro.utils.pytree import tree_index, tree_l2_norm, tree_sub

        assert len(jax.devices()) == 8
        rng = np.random.default_rng(0)
        n = 8
        x = jnp.asarray(rng.normal(size=(n, 24, 12)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(n, 24)).astype(np.float32))
        counts = jnp.asarray(np.full((n,), 24, np.int32))
        model = LSTMModel(hidden=8).as_model()
        grid = SweepGrid.build(("ring", "random"), (0.0, 0.5), (0,), num_nodes=n)
        tr = GluADFL(model, sgd(1e-2), FLConfig(num_nodes=n, comm_batch=3, rounds=4))
        pops, hists, _ = tr.train_sweep(x, y, counts, grid=grid, batch_size=8)
        for g, (topo, ratio, seed) in enumerate(grid.labels):
            cfg = FLConfig(topology=topo, num_nodes=n, comm_batch=3,
                           rounds=4, inactive_ratio=ratio)
            s_tr = GluADFL(model, sgd(1e-2), cfg)
            pop, hist, _ = s_tr.train(jax.random.PRNGKey(seed), x, y, counts,
                                      batch_size=8)
            assert all(abs(a["loss"] - b["loss"]) < 1e-5
                       for a, b in zip(hists[g], hist))
            assert float(tree_l2_norm(tree_sub(tree_index(pops, g), pop))) < 1e-5
        print("SWEEP_8DEV_OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SWEEP_8DEV_OK" in out.stdout


@pytest.mark.multidevice
def test_train_sweep_sharded_parity_on_forced_8_devices():
    """The swept-SHARDED engine on a real 2-D (2 grid x 4 node) mesh:
    scenario g of ``train_sweep(mixer="sharded")`` must match a serial
    ``train(mixer="sharded", key=PRNGKey(seed_g))`` run — params,
    losses, AND streaming-eval records — for BOTH collective schedules
    (allgather and psum), plus the final-state key chain/staleness.
    The serial runs use the 1-D federation mesh, so this also pins that
    the (grid, node) lowering changes the schedule, not the numbers."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    src = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import FLConfig
        from repro.core import GluADFL, SweepGrid
        from repro.launch.mesh import make_sweep_mesh
        from repro.models import LSTMModel
        from repro.optim import sgd
        from repro.utils.pytree import tree_index, tree_l2_norm, tree_sub

        assert len(jax.devices()) == 8
        rng = np.random.default_rng(0)
        n, rounds, chunk, eval_every = 8, 5, 4, 2
        x = jnp.asarray(rng.normal(size=(n, 24, 12)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(n, 24)).astype(np.float32))
        counts = jnp.asarray(np.full((n,), 24, np.int32))
        vx = jnp.asarray(rng.normal(size=(16, 12)).astype(np.float32))
        vy = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
        model = LSTMModel(hidden=8).as_model()
        grid = SweepGrid.build(("ring", "random"), (0.0, 0.5), (0,), num_nodes=n)
        mesh = make_sweep_mesh(grid.size, n, grid_width=2, node_width=4)
        assert dict(mesh.shape) == {"grid": 2, "node": 4}
        for impl in ("allgather", "psum"):
            tr = GluADFL(model, sgd(1e-2),
                         FLConfig(num_nodes=n, comm_batch=3, rounds=rounds),
                         mixer="sharded", gossip_impl=impl, mesh=mesh)
            pops, hists, states = tr.train_sweep(
                x, y, counts, grid=grid, batch_size=8, chunk=chunk,
                eval_every=eval_every, val_data=(vx, vy))
            for g, (topo, ratio, seed) in enumerate(grid.labels):
                cfg = FLConfig(topology=topo, num_nodes=n, comm_batch=3,
                               rounds=rounds, inactive_ratio=ratio)
                s_tr = GluADFL(model, sgd(1e-2), cfg,
                               mixer="sharded", gossip_impl=impl)
                pop, hist, st = s_tr.train(
                    jax.random.PRNGKey(seed), x, y, counts, batch_size=8,
                    chunk=chunk, eval_every=eval_every, val_data=(vx, vy))
                assert len(hists[g]) == rounds
                for a, b in zip(hists[g], hist):
                    assert abs(a["loss"] - b["loss"]) < 1e-4, (impl, g, a, b)
                    assert ("val_rmse" in a) == ("val_rmse" in b)
                    if "val_rmse" in a:
                        assert abs(a["val_rmse"] - b["val_rmse"]) < 1e-4
                assert sum("val_rmse" in h for h in hists[g]) == 2
                assert float(tree_l2_norm(tree_sub(
                    tree_index(pops, g), pop))) < 1e-4, (impl, g)
                np.testing.assert_array_equal(
                    np.asarray(states.key[g]), np.asarray(st.key))
                np.testing.assert_allclose(
                    np.asarray(states.staleness[g]),
                    np.asarray(st.staleness), atol=0)
            print(f"SWEEP_SHARDED_{impl.upper()}_OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SWEEP_SHARDED_ALLGATHER_OK" in out.stdout
    assert "SWEEP_SHARDED_PSUM_OK" in out.stdout
