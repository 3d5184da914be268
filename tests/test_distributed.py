"""Sharded-gossip + gossip-DP + small-mesh dry-run integration tests.

These spawn subprocesses with XLA_FLAGS for multi-device CPU (the main
test process must keep the default single device).  The ``multidevice``
marker routes them to CI's forced-8-device job (`pytest -m
multidevice`); a plain local `pytest` run still executes everything."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


@pytest.mark.multidevice
def test_sharded_ring_gossip_matches_reference():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import make_sharded_gossip
        from repro.core.topology import mixing_matrix, ring_adjacency
        from repro.utils.pytree import tree_weighted_mix
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        N, D = 8, 96
        w = {"a": jax.random.normal(jax.random.PRNGKey(0), (N, D)),
             "b": jax.random.normal(jax.random.PRNGKey(1), (N, 3, 5))}
        active = jnp.ones((N,))
        out = jax.jit(make_sharded_gossip(mesh, ("data",), "ring"))(w, active)
        ref = tree_weighted_mix(w, mixing_matrix(ring_adjacency(N), active, 7))
        for k in w:
            np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]), rtol=2e-5, atol=1e-5)
        print("RING_OK")
    """))


@pytest.mark.multidevice
def test_sharded_general_gossip_matches_reference():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import make_sharded_gossip
        from repro.core.topology import mixing_matrix, cluster_adjacency
        from repro.utils.pytree import tree_weighted_mix
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        N, D = 8, 64
        w = {"a": jax.random.normal(jax.random.PRNGKey(0), (N, D))}
        active = (jax.random.uniform(jax.random.PRNGKey(2), (N,)) > 0.4).astype(jnp.float32)
        mix = mixing_matrix(cluster_adjacency(N, 4), active, 3)
        out = jax.jit(make_sharded_gossip(mesh, ("data",), "cluster"))(w, mix)
        ref = tree_weighted_mix(w, mix)
        np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(ref["a"]), rtol=2e-5, atol=1e-5)
        print("GENERAL_OK")
    """))


@pytest.mark.multidevice
def test_ring_gossip_multi_node_shards_match_reference():
    """BUGFIX PIN: with >1 node per shard the ring body must average row
    i with its ACTUAL ring neighbours i±1 — the pre-fix code ppermuted
    whole shard blocks, handing interior rows the params of rows
    i±nodes_per_shard.  2 nodes/shard (N=8 over 4 shards) against the
    dense mixing-matrix ring, all-active and partially-active."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import make_sharded_gossip
        from repro.core.topology import mixing_matrix, ring_adjacency
        from repro.utils.pytree import tree_weighted_mix
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))  # 8 nodes -> 2 per shard
        N, D = 8, 24
        w = {"a": jax.random.normal(jax.random.PRNGKey(0), (N, D)),
             "b": jax.random.normal(jax.random.PRNGKey(1), (N, 3, 5))}
        gossip = jax.jit(make_sharded_gossip(mesh, ("data",), "ring"))
        for label, active in (
            ("all-active", jnp.ones((N,))),
            ("partial", jnp.asarray([1, 0, 1, 1, 0, 1, 1, 0], jnp.float32)),
        ):
            out = gossip(w, active)
            ref = tree_weighted_mix(w, mixing_matrix(ring_adjacency(N), active, 7))
            for k in w:
                np.testing.assert_allclose(
                    np.asarray(out[k]), np.asarray(ref[k]), rtol=2e-5, atol=1e-5,
                    err_msg=f"{label}/{k}")
            # inactive rows bit-exact
            idx = np.where(np.asarray(active) == 0)[0]
            for k in w:
                np.testing.assert_array_equal(
                    np.asarray(out[k])[idx], np.asarray(w[k])[idx])
        print("RING_BLOCK_OK")
    """, devices=4))


@pytest.mark.multidevice
def test_grid_sharded_gossip_mix_matches_dense():
    """The 2-D (grid, node) sweep mesh: ONE shard_map with P("grid", ...)
    in_specs mixes every scenario's federation — each scenario g must
    match the dense per-scenario contraction, for BOTH collective
    schedules, with bit-exact inactive rows; and the explicit grid call
    must agree with vmap(spmd_axis_name="grid") over the per-scenario
    call (the trainer's swept-sharded lowering)."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import sharded_gossip_mix
        from repro.core.topology import mixing_matrix, random_adjacency
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh(4, 8, grid_width=2, node_width=4)
        assert dict(mesh.shape) == {"grid": 2, "node": 4}
        G, N, D = 4, 8, 48
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        w = {"a": jax.random.normal(k[0], (G, N, D)),
             "b": jax.random.normal(k[1], (G, N, 3, 5))}
        active = (jax.random.uniform(k[2], (G, N)) > 0.4).astype(jnp.float32)
        mix = jnp.stack([
            mixing_matrix(random_adjacency(jax.random.PRNGKey(g), N, 3),
                          active[g], 3)
            for g in range(G)
        ])
        for impl in ("allgather", "psum"):
            out = jax.jit(lambda ww, mm, aa: sharded_gossip_mix(
                ww, mm, aa, mesh=mesh, impl=impl))(w, mix, active)
            batched = jax.jit(jax.vmap(
                lambda ww, mm, aa: sharded_gossip_mix(
                    ww, mm, aa, mesh=mesh, impl=impl),
                spmd_axis_name="grid"))(w, mix, active)
            for kk in w:
                flat = w[kk].reshape(G, N, -1)
                ref = jnp.einsum("gnm,gmd->gnd", mix, flat).reshape(w[kk].shape)
                np.testing.assert_allclose(
                    np.asarray(out[kk]), np.asarray(ref), atol=1e-5)
                np.testing.assert_allclose(
                    np.asarray(batched[kk]), np.asarray(ref), atol=1e-5)
                idx = np.where(np.asarray(active) == 0)
                np.testing.assert_array_equal(
                    np.asarray(out[kk])[idx], np.asarray(w[kk])[idx])
        print("GRID_MIX_OK")
    """))


def test_sharded_gossip_mix_shape_mismatch_fails_at_trace():
    """Mismatched scenario grids must fail with readable shapes at trace
    time, not inside the collective (single-device (1, 1) sweep mesh)."""
    import jax.numpy as jnp

    from repro.core.distributed import sharded_gossip_mix
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh(3, 8, grid_width=1, node_width=1)
    w = {"a": jnp.ones((3, 8, 4))}
    with pytest.raises(ValueError, match="leading dim"):
        sharded_gossip_mix(w, jnp.stack([jnp.eye(8)] * 4), mesh=mesh)
    # a 2-D mix on a grid mesh is a mis-shaped call, not a silent demotion
    with pytest.raises(ValueError, match="mixing matrix"):
        sharded_gossip_mix(w, jnp.eye(8), mesh=mesh, grid_axis="grid")


@pytest.mark.multidevice
def test_sharded_ring_gossip_respects_inactive():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import make_sharded_gossip
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        N, D = 8, 16
        w = {"a": jax.random.normal(jax.random.PRNGKey(0), (N, D))}
        active = jnp.zeros((N,)).at[0].set(1.0)
        out = jax.jit(make_sharded_gossip(mesh, ("data",), "ring"))(w, active)
        # inactive nodes keep their rows bit-exact
        np.testing.assert_array_equal(np.asarray(out["a"])[1:], np.asarray(w["a"])[1:])
        print("INACTIVE_OK")
    """))


@pytest.mark.multidevice
def test_mixer_parity_tree_kernel_sharded():
    """The three interchangeable gossip mixers agree on random
    row-stochastic matrices with inactive nodes (the sharded one under a
    real 8-device node-sharded mesh)."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.gossip import gossip_mix_tree, gossip_mix_kernel, sharded_gossip_mix
        from repro.core.topology import mixing_matrix, random_adjacency
        N, D = 8, 96
        k = jax.random.split(jax.random.PRNGKey(0), 4)
        w = {"a": jax.random.normal(k[0], (N, D)),
             "b": jax.random.normal(k[1], (N, 3, 7))}
        active = (jax.random.uniform(k[2], (N,)) > 0.4).astype(jnp.float32)
        mix = mixing_matrix(random_adjacency(jax.random.PRNGKey(7), N, 3), active, 3)
        np.testing.assert_allclose(np.asarray(mix).sum(1), 1.0, atol=1e-5)
        a = gossip_mix_tree(w, mix)
        b = gossip_mix_kernel(w, mix, active)
        c = jax.jit(lambda ww, mm, aa: sharded_gossip_mix(ww, mm, aa))(w, mix, active)
        for kk in w:
            np.testing.assert_allclose(np.asarray(a[kk]), np.asarray(b[kk]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(a[kk]), np.asarray(c[kk]), atol=1e-5)
            # inactive rows: kernel and sharded paths copy bit-exact
            idx = np.where(np.asarray(active) == 0)[0]
            np.testing.assert_array_equal(np.asarray(b[kk])[idx], np.asarray(w[kk])[idx])
            np.testing.assert_array_equal(np.asarray(c[kk])[idx], np.asarray(w[kk])[idx])
        print("MIXER_PARITY_OK")
    """))


@pytest.mark.multidevice
def test_sharded_mixer_trains_like_tree_mixer():
    """GluADFL end-to-end with mixer="sharded" (scan engine, N nodes over
    8 devices) matches the tree mixer's population model."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import FLConfig
        from repro.core import GluADFL
        from repro.models import LSTMModel
        from repro.optim import sgd
        from repro.utils.pytree import tree_l2_norm, tree_sub
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 40, 12)).astype(np.float32)
        y = (x @ rng.normal(size=(12,)).astype(np.float32)).astype(np.float32)
        counts = np.full((8,), 40, np.int32)
        cfg = FLConfig(topology="ring", num_nodes=8, rounds=6, inactive_ratio=0.25)
        def train(mixer, sigma=0.0):
            tr = GluADFL(LSTMModel(hidden=8).as_model(), sgd(1e-2), cfg,
                         mixer=mixer, dp_noise_sigma=sigma)
            return tr.train(jax.random.PRNGKey(0), x, y, counts, batch_size=8)
        p_tree, h_tree, _ = train("tree")
        p_shard, h_shard, _ = train("sharded")
        assert len(h_tree) == len(h_shard) == 6
        assert float(tree_l2_norm(tree_sub(p_tree, p_shard))) < 1e-4
        for a, b in zip(h_tree, h_shard):
            assert abs(a["loss"] - b["loss"]) < 1e-4, (a, b)
        # DP broadcast noise: the composed shard_map restore path matches
        # the tree mixer's composed path (same key stream -> same noise)
        p_tree_dp, _, _ = train("tree", sigma=0.05)
        p_shard_dp, _, _ = train("sharded", sigma=0.05)
        assert float(tree_l2_norm(tree_sub(p_tree_dp, p_shard_dp))) < 1e-4
        assert float(tree_l2_norm(tree_sub(p_tree_dp, p_tree))) > 1e-4  # noise bites
        print("SHARDED_TRAIN_OK")
    """))


@pytest.mark.multidevice
def test_mini_dryrun_dense_and_moe():
    """End-to-end mini dry-run: reduced archs on an 8-device (4,2) mesh,
    lower + compile + cost analysis — the same path as the 512-device
    production dry-run."""
    print(_run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.arch import build_arch
        from repro.arch.common import init_train_state, make_train_step
        from repro.arch.sharding import param_pspecs
        from repro.config import get_arch_config
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        for name in ("yi-6b", "granite-moe-1b-a400m", "mamba2-370m"):
            cfg = get_arch_config(name).reduced()
            arch = build_arch(cfg)
            pspec = jax.eval_shape(arch.init_params, jax.random.PRNGKey(0))
            prules = param_pspecs(pspec, axis_size=2)
            psh = jax.tree.map(lambda s: NamedSharding(mesh, s), prules,
                               is_leaf=lambda x: isinstance(x, P))
            step = make_train_step(arch.loss_fn, num_microbatches=2, lr=1e-3,
                                   data_axes=("data",))
            st_spec = jax.eval_shape(init_train_state, pspec)
            from repro.arch.common import TrainState
            st_sh = TrainState(params=psh, m=psh, v=psh, step=NamedSharding(mesh, P()))
            batch = arch.input_specs("train_4k", override_batch=8, override_seq=32)
            bsh = jax.tree.map(lambda s: NamedSharding(mesh, P("data", *([None]*(s.ndim-1)))) if s.ndim else NamedSharding(mesh, P()), batch)
            with mesh:
                fn = jax.jit(step, in_shardings=(st_sh, bsh))
                compiled = fn.lower(st_spec, batch).compile()
            cost = compiled.cost_analysis()
            assert cost.get("flops", 0) > 0, name
            print("MINI_DRYRUN_OK", name, int(cost["flops"]))
    """))


def test_gossip_dp_schedule():
    from repro.core.gossip_dp import GossipDPSchedule

    sched = GossipDPSchedule("random", 8, comm_batch=3, mix_every=4)
    assert [sched.should_mix(s) for s in range(8)] == [False, False, False, True] * 2
    m1 = sched.next_mix()
    m2 = sched.next_mix()
    import numpy as np

    assert m1.shape == (8, 8)
    np.testing.assert_allclose(np.asarray(m1).sum(1), 1.0, atol=1e-5)
    assert not np.allclose(np.asarray(m1), np.asarray(m2))  # time-varying


@pytest.mark.multidevice
def test_psum_gossip_matches_allgather_and_reference():
    """gossip_impl="psum" (reduce-scatter of local contributions) matches
    the allgather impl AND the single-device reference numerically on 8
    forced CPU devices, with bit-exact inactive rows."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.gossip import gossip_mix_tree, sharded_gossip_mix
        from repro.core.topology import mixing_matrix, random_adjacency
        N, D = 8, 96
        k = jax.random.split(jax.random.PRNGKey(0), 4)
        w = {"a": jax.random.normal(k[0], (N, D)),
             "b": jax.random.normal(k[1], (N, 3, 7))}
        active = (jax.random.uniform(k[2], (N,)) > 0.4).astype(jnp.float32)
        mix = mixing_matrix(random_adjacency(jax.random.PRNGKey(7), N, 3), active, 3)
        ref = gossip_mix_tree(w, mix)
        ag = jax.jit(lambda ww, mm, aa: sharded_gossip_mix(ww, mm, aa, impl="allgather"))(w, mix, active)
        ps = jax.jit(lambda ww, mm, aa: sharded_gossip_mix(ww, mm, aa, impl="psum"))(w, mix, active)
        for kk in w:
            np.testing.assert_allclose(np.asarray(ref[kk]), np.asarray(ag[kk]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(ref[kk]), np.asarray(ps[kk]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(ag[kk]), np.asarray(ps[kk]), atol=1e-5)
            idx = np.where(np.asarray(active) == 0)[0]
            np.testing.assert_array_equal(np.asarray(ps[kk])[idx], np.asarray(w[kk])[idx])
        print("PSUM_PARITY_OK")
    """))


@pytest.mark.multidevice
def test_psum_impl_trains_like_allgather_impl():
    """GluADFL end-to-end: mixer="sharded" with gossip_impl="psum" (scan
    engine + in-scan streaming eval) matches the allgather impl's
    population model, losses, and eval records."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import FLConfig
        from repro.core import GluADFL
        from repro.models import LSTMModel
        from repro.optim import sgd
        from repro.utils.pytree import tree_l2_norm, tree_sub
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 40, 12)).astype(np.float32)
        y = (x @ rng.normal(size=(12,)).astype(np.float32)).astype(np.float32)
        counts = np.full((8,), 40, np.int32)
        vx = rng.normal(size=(16, 12)).astype(np.float32)
        vy = rng.normal(size=(16,)).astype(np.float32)
        cfg = FLConfig(topology="random", num_nodes=8, rounds=6,
                       comm_batch=3, inactive_ratio=0.25)
        def train(impl):
            tr = GluADFL(LSTMModel(hidden=8).as_model(), sgd(1e-2), cfg,
                         mixer="sharded", gossip_impl=impl)
            return tr.train(jax.random.PRNGKey(0), x, y, counts, batch_size=8,
                            eval_every=3, val_data=(vx, vy), chunk=6)
        p_ag, h_ag, _ = train("allgather")
        p_ps, h_ps, _ = train("psum")
        assert len(h_ag) == len(h_ps) == 6
        assert float(tree_l2_norm(tree_sub(p_ag, p_ps))) < 1e-4
        for a, b in zip(h_ag, h_ps):
            assert abs(a["loss"] - b["loss"]) < 1e-4, (a, b)
            assert ("val_rmse" in a) == ("val_rmse" in b)
            if "val_rmse" in a:
                assert abs(a["val_rmse"] - b["val_rmse"]) < 1e-4, (a, b)
        assert sum("val_rmse" in h for h in h_ag) == 2
        print("PSUM_TRAIN_OK")
    """))


@pytest.mark.multidevice
def test_gossip_dp_psum_scatter_matches_full_psum():
    """gossip_mix_params impl="psum" (psum_scatter, memory-scaled) agrees
    with the impl="allgather" baseline (full psum + slice) for
    node-replicated params on a (node, model) mesh."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.gossip_dp import gossip_mix_params
        from repro.core.topology import mixing_matrix, random_adjacency
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("node", "model"))
        k = jax.random.split(jax.random.PRNGKey(0), 2)
        params = {"w": jax.random.normal(k[0], (4, 8, 6)), "b": jnp.zeros((3,))}
        mix = mixing_matrix(random_adjacency(jax.random.PRNGKey(3), 4, 2),
                            jnp.ones((4,)), 2)
        pa = jax.jit(lambda p: gossip_mix_params(p, mix, mesh, ("node",), impl="allgather"))(params)
        pb = jax.jit(lambda p: gossip_mix_params(p, mix, mesh, ("node",), impl="psum"))(params)
        for kk in params:
            np.testing.assert_allclose(np.asarray(pa[kk]), np.asarray(pb[kk]), atol=1e-5)
        print("GOSSIP_DP_PSUM_OK")
    """))


def test_bad_gossip_impl_rejected():
    """Unknown gossip_impl must raise at construction, not at trace."""
    from repro.config import FLConfig
    from repro.core import GluADFL
    from repro.models import LSTMModel
    from repro.optim import sgd

    with pytest.raises(ValueError, match="gossip_impl"):
        GluADFL(LSTMModel(hidden=8).as_model(), sgd(1e-2),
                FLConfig(num_nodes=4, rounds=1), gossip_impl="ringz")


@pytest.mark.multidevice
def test_gossip_dp_ring_mix_on_mesh():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.gossip_dp import ring_mix_params
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("node", "model"))
        params = {"w": jnp.ones((8, 8)), "b": jnp.zeros((3,))}
        out = jax.jit(lambda p: ring_mix_params(p, mesh, ("node",)))(params)
        np.testing.assert_allclose(np.asarray(out["w"]), 1.0, atol=1e-6)
        print("GOSSIP_DP_OK")
    """))


@pytest.mark.multidevice
def test_every_mesh_has_auto_axes():
    """Every mesh ``launch/mesh.py`` builds has Auto axes (the installed
    jax.make_mesh defaults to Explicit, which the shard_map bodies,
    sharding constraints and spmd vmaps are not written for).  4 forced
    devices for the federation meshes; the pod-scale meshes need 512."""
    print(_run("""
        from jax.sharding import AxisType
        from repro.launch import mesh as M
        meshes = [M.make_mesh((2, 2), ("a", "b")), M.make_test_mesh(),
                  M.make_federation_mesh(224), M.make_federation_mesh(226),
                  M.make_sweep_mesh(2, 226)]
        for m in meshes:
            assert all(t == AxisType.Auto for t in m.axis_types), m
        print("AUTO_4_OK")
    """, devices=4))
    print(_run("""
        from jax.sharding import AxisType
        from repro.launch import mesh as M
        for m in (M.make_production_mesh(), M.make_production_mesh(multi_pod=True),
                  M.make_gossip_dp_mesh(), M.make_gossip_dp_mesh(multi_pod=True)):
            assert all(t == AxisType.Auto for t in m.axis_types), m
        print("AUTO_512_OK")
    """, devices=512))


@pytest.mark.multidevice
@pytest.mark.parametrize("nodes,width", [(224, 4), (226, 2)])
def test_federation_mesh_width_on_four_devices(nodes, width):
    """The node axis takes the largest divisor of N that fits the
    devices: 224 = 4·56 spans all four, while the paper's 226 = 2·113
    cohort shards only two-wide on a four-chip host."""
    out = _run(f"""
        from repro.launch.mesh import make_federation_mesh
        m = make_federation_mesh({nodes})
        print("WIDTH", m.shape["node"], len({{d.id for d in m.devices.flat}}))
    """, devices=4)
    assert f"WIDTH {width} {width}" in out, out
