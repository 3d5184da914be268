"""GossipPlan resolution — totality over the full knob product, the
``use_kernel`` deprecation shim, the mesh-aware auto-repr policy, the
gather-table backend's refusals and its parity against the sparse
allgather schedule (single device and forced-8-device ``multidevice``
runs at the paper's N=226 and at N=10,000)."""
import importlib.util
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig
from repro.core import GluADFL, GossipPlanError
from repro.core.distributed import GOSSIP_IMPLS, GOSSIP_REPRS
from repro.core.gossip import gossip_mix_sparse_tree
from repro.core.gossip_plan import (
    MIXERS,
    choose_gossip_impl,
    choose_gossip_repr,
    mix_backends,
    resolve_gossip_plan,
    supported_cells,
)
from repro.core.topology import neighbor_table, random_adjacency
from repro.models import LSTMModel
from repro.optim import sgd
from repro.launch.mesh import make_mesh

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


def _resolve(**kw):
    kw.setdefault("num_nodes", 8)
    kw.setdefault("comm_batch", 2)
    return resolve_gossip_plan(**kw)


# ---------------------------------------------------------------------------
# totality: every cell of the knob product resolves or refuses loudly
# ---------------------------------------------------------------------------


def test_plan_totality_full_knob_product():
    """Every (mixer, gossip_impl, gossip_repr) triple either resolves to
    a registered backend or raises a knob-naming error — no silent
    fallthrough.  The supported set is exactly: every dense-wire impl on
    every mixer, plus gather on (sharded, sparse) only."""
    cells = {
        (c["mixer"], c["gossip_impl"], c["gossip_repr"])
        for c in supported_cells()
    }
    expected = set()
    for mixer in MIXERS:
        for impl in GOSSIP_IMPLS:
            for repr_ in GOSSIP_REPRS:
                if impl == "gather":
                    if mixer == "sharded" and repr_ == "sparse":
                        expected.add((mixer, impl, repr_))
                else:
                    expected.add((mixer, impl, repr_))
    assert cells == expected

    registered = set(mix_backends())
    for mixer in MIXERS:
        for impl in GOSSIP_IMPLS:
            for repr_ in GOSSIP_REPRS:
                if (mixer, impl, repr_) in cells:
                    plan = _resolve(mixer=mixer, gossip_impl=impl,
                                    gossip_repr=repr_)
                    assert plan.backend in registered
                    assert plan.mixer == mixer
                    assert plan.gossip_repr == repr_
                    assert plan.masked == (impl == "masked")
                else:
                    with pytest.raises(ValueError) as e:
                        _resolve(mixer=mixer, gossip_impl=impl,
                                 gossip_repr=repr_)
                    # refusals are GossipPlanError (a ValueError) and
                    # name the offending knob value
                    assert isinstance(e.value, GossipPlanError)
                    assert "gather" in str(e.value)


def test_plan_totality_matches_knob_matrix_generator():
    """The doc generator and the totality test read the same registry:
    every supported cell's backend shows up in the generated matrix and
    the gather row carries its memory class."""
    spec = importlib.util.spec_from_file_location(
        "gen_knob_matrix", os.path.join(ROOT, "tools", "gen_knob_matrix.py")
    )
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    block = gen.generate()
    for mixer in MIXERS:
        assert f'`mixer="{mixer}"`' in block
    gather = mix_backends()["sharded_gather_tables"]
    assert gather.name in block
    assert gather.caps.memory_class in block
    # refused sweep cells document the refusal in the same table
    assert "raises" in block


def test_unknown_knob_values_name_the_registry():
    with pytest.raises(ValueError, match=r"mixer 'fft' not in"):
        _resolve(mixer="fft")
    with pytest.raises(ValueError, match=r"gossip_impl 'rdma' not in"):
        _resolve(gossip_impl="rdma")
    with pytest.raises(ValueError, match=r"gossip_repr 'csr' not in"):
        _resolve(gossip_repr="csr")


def test_bad_gossip_repr_message_lists_reprs_and_auto():
    """The satellite fix: the refusal prints the actual GOSSIP_REPRS
    tuple (not a mangled concatenation) and explains 'auto'."""
    with pytest.raises(ValueError) as e:
        _resolve(gossip_repr="csr")
    msg = str(e.value)
    assert str(GOSSIP_REPRS) in msg
    assert "auto" in msg
    # same message through the trainer constructor
    with pytest.raises(ValueError, match="auto"):
        GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2),
                FLConfig(num_nodes=4, rounds=1), gossip_repr="csr")


# ---------------------------------------------------------------------------
# the use_kernel deprecation shim
# ---------------------------------------------------------------------------


def test_use_kernel_flag_warns_and_maps():
    with pytest.warns(DeprecationWarning, match="use_kernel is deprecated"):
        plan = _resolve(use_kernel=True)
    assert plan.mixer == "kernel"
    assert plan.use_kernel  # the fused-DP capability mirrors the mixer


def test_plain_kernel_mixer_does_not_warn():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        plan = _resolve(mixer="kernel")
    assert plan.mixer == "kernel"
    assert plan.use_kernel


def test_use_kernel_conflicting_mixer_rejected():
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="contradicts"):
            _resolve(use_kernel=True, mixer="tree")


def test_trainer_use_kernel_warns_and_maps():
    cfg = FLConfig(num_nodes=4, rounds=1)
    with pytest.warns(DeprecationWarning, match="use_kernel is deprecated"):
        tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                     use_kernel=True)
    assert tr.mixer == "kernel"
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        tr2 = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                      mixer="kernel")
    assert tr2.mixer == "kernel"


def test_launcher_use_kernel_shim():
    """The --use-kernel launcher path: the flag warns (visible under
    -W error) and a contradicting --mixer exits before any training."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    warn = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning:__main__", "-m",
         "repro.launch.train", "--use-kernel"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert warn.returncode != 0
    assert "--use-kernel is deprecated" in warn.stderr
    conflict = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--use-kernel",
         "--mixer", "tree"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert conflict.returncode != 0
    assert "contradicts --mixer tree" in conflict.stderr


# ---------------------------------------------------------------------------
# plan-resolution policies
# ---------------------------------------------------------------------------


def test_choose_gossip_repr_mesh_aware():
    from jax.sharding import AbstractMesh

    # no mesh: the one-device crossover, FACTOR * (B+1)
    assert choose_gossip_repr(CROSS - 1, 7) == "dense"
    assert choose_gossip_repr(CROSS, 7) == "sparse"
    # an explicit factor is used as given, whatever the device count
    assert choose_gossip_repr(31, 7, factor=4) == "dense"
    assert choose_gossip_repr(32, 7, factor=4) == "sparse"
    # a width-1 node axis is one device too
    mesh = make_mesh((1,), ("node",))
    assert choose_gossip_repr(CROSS - 1, 7, mesh=mesh) == "dense"
    assert choose_gossip_repr(CROSS, 7, mesh=mesh) == "sparse"
    # mesh path: same (N, B) flips to sparse once the per-device
    # (N/shards, N) row block outgrows the budget
    assert choose_gossip_repr(31, 7, mesh=mesh) == "dense"
    assert choose_gossip_repr(31, 7, mesh=mesh, budget_bytes=31 * 31) == "sparse"
    # grid/model axes don't count toward the node width
    gm = make_mesh((1, 1), ("grid", "node"))
    assert choose_gossip_repr(CROSS - 1, 7, mesh=gm) == "dense"
    assert choose_gossip_repr(31, 7, mesh=gm, budget_bytes=31 * 31) == "sparse"
    # a node axis wider than one device keeps the 4 * (B+1) = 32 boundary
    for wide in (AbstractMesh((4,), ("node",)), AbstractMesh((2, 2), ("grid", "node"))):
        assert choose_gossip_repr(31, 7, mesh=wide) == "dense"
        assert choose_gossip_repr(32, 7, mesh=wide) == "sparse"
        assert choose_gossip_repr(226, 7, mesh=wide) == "sparse"
    assert choose_gossip_repr(32, 7, mesh=AbstractMesh((4, 1), ("grid", "node"))) == "dense"


@pytest.mark.multidevice
def test_auto_repr_one_device_rule_is_the_tree_backends():
    """Only the tree mixer takes the one-device crossover: at N=224,
    B=7 on 8 devices it resolves dense, while the kernel mixer and both
    sharded schedules keep the 4 * (B+1) rule and resolve sparse."""
    out = _run(
        """
        from repro.core.gossip_plan import resolve_gossip_plan
        for mixer, impl in [("sharded", "allgather"), ("tree", "allgather"),
                            ("sharded", "gather"), ("kernel", "allgather")]:
            plan = resolve_gossip_plan(mixer=mixer, gossip_impl=impl,
                                       gossip_repr="auto", num_nodes=224,
                                       comm_batch=7)
            print(mixer, impl, plan.gossip_repr)
        """
    )
    assert out.split("\n")[:4] == [
        "sharded allgather sparse", "tree allgather dense", "sharded gather sparse",
        "kernel allgather sparse",
    ]


def test_choose_gossip_impl_secure_past_budget_refuses():
    assert choose_gossip_impl(8, 4, shards=2, secure=True) == "masked"
    with pytest.raises(GossipPlanError, match="masked"):
        choose_gossip_impl(1000, 1 << 20, shards=2, budget_bytes=1 << 10,
                           secure=True)


def test_auto_repr_through_trainer_uses_plan_policy():
    cfg = FLConfig(topology="ring", num_nodes=226, rounds=1, comm_batch=7)
    tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                 gossip_repr="auto")
    assert tr.gossip_repr == "dense"
    assert tr.plan.gossip_repr == "dense"
    # the kernel mixer keeps the 4 * (B+1) rule on one device
    trk = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                  mixer="kernel", gossip_repr="auto")
    assert trk.plan.gossip_repr == "sparse"


# ---------------------------------------------------------------------------
# the gather-tables backend: refusals + single-device parity
# ---------------------------------------------------------------------------


def test_gather_refuses_non_sharded_mixer_and_dense_repr():
    with pytest.raises(GossipPlanError, match="needs mixer"):
        _resolve(mixer="tree", gossip_impl="gather", gossip_repr="sparse")
    with pytest.raises(GossipPlanError, match="needs gossip_repr='sparse'"):
        _resolve(mixer="sharded", gossip_impl="gather", gossip_repr="dense")
    with pytest.raises(ValueError, match="gossip_impl"):
        GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2),
                FLConfig(num_nodes=8, rounds=1), mixer="kernel",
                gossip_impl="gather", gossip_repr="sparse")


def test_gather_plan_refuses_sweep_but_offers_multihost():
    plan = _resolve(mixer="sharded", gossip_impl="gather",
                    gossip_repr="sparse")
    with pytest.raises(NotImplementedError, match="gather"):
        plan.require_sweep()
    plan.require_multihost()  # the scale-out schedule spans processes
    with pytest.raises(ValueError, match="sharded"):
        _resolve(mixer="tree").require_multihost()


def test_trainer_gather_sweep_refused():
    cfg = FLConfig(topology="ring", num_nodes=8, rounds=1, comm_batch=2)
    tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                 mixer="sharded", gossip_impl="gather", gossip_repr="sparse")
    from repro.core import SweepGrid

    grid = SweepGrid.build(["ring"], [0.0], [0], num_nodes=8)
    x = np.zeros((8, 4, 12), np.float32)
    y = np.zeros((8, 4), np.float32)
    counts = np.full((8,), 4, np.int32)
    with pytest.raises(NotImplementedError, match="gather"):
        tr.train_sweep(x, y, counts, grid=grid, batch_size=4, chunk=1)


def test_gather_matches_sparse_tree_single_device():
    """n_shards=1 degenerates to the local contraction: the gather mix
    equals the sparse tree reference, inactive rows bitwise."""
    from repro.core.distributed import sharded_gossip_mix_gather

    n, d = 24, 60
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    adj = random_adjacency(k[0], n, 4)
    active = (jax.random.uniform(k[1], (n,)) > 0.4).astype(jnp.float32)
    idx, wgt = neighbor_table(adj, active, 4)
    w = {"a": jax.random.normal(k[2], (n, d)), "b": jnp.ones((n, 3, 5))}
    got = sharded_gossip_mix_gather(w, idx, wgt, active)
    ref = gossip_mix_sparse_tree(w, idx, wgt, active)
    for kk in w:
        np.testing.assert_allclose(np.asarray(got[kk]), np.asarray(ref[kk]),
                                   atol=1e-5)
        for i in np.where(np.asarray(active) == 0)[0]:
            np.testing.assert_array_equal(np.asarray(got[kk])[i],
                                          np.asarray(w[kk])[i])


def test_trainer_gather_trains_single_device():
    n = 8
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8, 12)).astype(np.float32)
    y = (x @ rng.normal(size=(12,)).astype(np.float32)).astype(np.float32)
    counts = np.full((n,), 8, np.int32)
    cfg = FLConfig(topology="ring", num_nodes=n, rounds=2, comm_batch=3,
                   inactive_ratio=0.25)

    def train(impl):
        tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                     mixer="sharded", gossip_impl=impl, gossip_repr="sparse")
        st = tr.init(jax.random.PRNGKey(0))
        st, losses = tr.train_chunk(st, x, y, counts, batch_size=4, chunk=2)
        return st, np.asarray(losses)

    sg, lg = train("gather")
    sa, la = train("allgather")
    np.testing.assert_allclose(lg, la, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sg.staleness),
                                  np.asarray(sa.staleness))


# ---------------------------------------------------------------------------
# gather vs sparse allgather on 8 forced devices (multidevice)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_gather_matches_sparse_allgather_10k_nodes():
    """Contraction-level parity at N=10,000 over 8 shards: the
    ring-rotating gather-table schedule equals the sparse allgather mix
    to 1e-5 (different summation order), inactive rows bitwise — and no
    gathered (N, D) federation is needed to check it."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import (sharded_gossip_mix_gather,
                                            sharded_gossip_mix_sparse)
        N, B, D = 10_000, 3, 48
        rng = np.random.default_rng(0)
        idx = rng.integers(0, N, size=(N, B + 1)).astype(np.int32)
        idx[:, 0] = np.arange(N)
        wgt = rng.uniform(0.1, 1.0, size=(N, B + 1)).astype(np.float32)
        wgt /= wgt.sum(1, keepdims=True)
        active = (rng.uniform(size=N) > 0.3).astype(np.float32)
        inact = active == 0
        wgt[inact] = 0.0
        wgt[inact, 0] = 1.0
        idx[inact, 1:] = idx[inact, :1]
        w = {"a": rng.normal(size=(N, D)).astype(np.float32),
             "b": rng.normal(size=(N, 3, 5)).astype(np.float32)}
        w = jax.tree.map(jnp.asarray, w)
        ga = jax.jit(lambda ww, ii, gg, aa: sharded_gossip_mix_gather(ww, ii, gg, aa))(
            w, idx, wgt, active)
        sp = jax.jit(lambda ww, ii, gg, aa: sharded_gossip_mix_sparse(ww, ii, gg, aa))(
            w, idx, wgt, active)
        bad = np.where(inact)[0]
        for kk in w:
            np.testing.assert_allclose(np.asarray(ga[kk]), np.asarray(sp[kk]),
                                       atol=1e-5)
            np.testing.assert_array_equal(np.asarray(ga[kk])[bad],
                                          np.asarray(w[kk])[bad])
        print("GATHER_10K_OK")
    """))


@pytest.mark.multidevice
def test_trainer_gather_matches_sparse_allgather_n226():
    """GluADFL end-to-end at the paper's N=226 (2 node shards on the
    8-device box): gossip_impl="gather" matches the sparse allgather
    run's losses to 1e-5 with identical staleness (inactive-row bitwise
    parity is pinned at the contraction level above)."""
    print(_run("""
        import numpy as np, jax
        from repro.config import FLConfig
        from repro.core import GluADFL
        from repro.models import LSTMModel
        from repro.optim import sgd
        N = 226
        rng = np.random.default_rng(0)
        x = rng.normal(size=(N, 8, 12)).astype(np.float32)
        y = (x @ rng.normal(size=(12,)).astype(np.float32)).astype(np.float32)
        counts = np.full((N,), 8, np.int32)
        cfg = FLConfig(topology="random", num_nodes=N, rounds=3,
                       comm_batch=7, inactive_ratio=0.3)
        def train(impl):
            tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg,
                         mixer="sharded", gossip_impl=impl,
                         gossip_repr="sparse")
            st = tr.init(jax.random.PRNGKey(0))
            st, losses = tr.train_chunk(st, x, y, counts, batch_size=4,
                                        chunk=3)
            return st, np.asarray(losses)
        sg, lg = train("gather")
        sa, la = train("allgather")
        np.testing.assert_allclose(lg, la, atol=1e-5)
        st_g = np.asarray(sg.staleness)
        np.testing.assert_array_equal(st_g, np.asarray(sa.staleness))
        assert (st_g > 0).any(), "want inactive nodes in the last round"
        for a, b in zip(jax.tree.leaves(sg.params), jax.tree.leaves(sa.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
        print("GATHER_N226_OK")
    """))
