"""Launcher-layer unit tests: HLO collective parsing, divisibility-aware
sharding helpers, checkpoint round-trip, config overrides."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ExperimentConfig, apply_overrides, get_arch_config
from repro.launch.dryrun import _shape_bytes, collective_schedule
from repro.launch.mesh import make_mesh


SAMPLE_HLO = """
  %all-gather.1 = bf16[16,1024]{1,0} all-gather(%param.1), replica_groups={}
  %all-reduce.2 = f32[8,256]{1,0} all-reduce(%x), to_apply=%add
  %all-reduce-start.3 = f32[128]{0} all-reduce-start(%y), to_apply=%add
  %all-reduce-done.3 = f32[128]{0} all-reduce-done(%all-reduce-start.3)
  %reduce-scatter.4 = bf16[4,64]{1,0} reduce-scatter(%z), dimensions={0}
  %collective-permute.5 = s32[32]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %tuple.9 = (f32[2,2]{1,0}, f32[4]{0}) all-to-all(%a, %b), dimensions={0}
"""


def test_collective_schedule_counts_and_bytes():
    out = collective_schedule(SAMPLE_HLO)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 16 * 1024 * 2
    # -start counted once, -done skipped
    assert out["all-reduce"]["count"] == 2
    assert out["all-reduce"]["bytes"] == 8 * 256 * 4 + 128 * 4
    # wire model: all-reduce moves 2x
    assert out["all-reduce"]["wire_bytes"] == 2 * (8 * 256 * 4 + 128 * 4)
    assert out["reduce-scatter"]["count"] == 1
    assert out["collective-permute"]["bytes"] == 32 * 4
    # tuple-result all-to-all sums both components
    assert out["all-to-all"]["bytes"] == 2 * 2 * 4 + 4 * 4
    assert out["total_wire_bytes"] > 0


def test_shape_bytes_parses_dtypes():
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("f32[10]") == 40
    assert _shape_bytes("pred[7]") == 7
    assert _shape_bytes("(f32[2], bf16[4,4])") == 8 + 32


def test_apply_overrides_nested():
    cfg = ExperimentConfig()
    cfg = apply_overrides(cfg, ["fl.comm_batch=3", "train.lr=0.01", "data.dataset=ctr3"])
    assert cfg.fl.comm_batch == 3
    assert cfg.train.lr == pytest.approx(0.01)
    assert cfg.data.dataset == "ctr3"
    with pytest.raises(KeyError):
        apply_overrides(cfg, ["fl.nonexistent=1"])


def test_checkpoint_roundtrip(tmp_path):
    from repro.launch.train import load_checkpoint, save_checkpoint
    from repro.models import LSTMModel

    m = LSTMModel(hidden=16)
    params = m.init(jax.random.PRNGKey(0))
    path = tmp_path / "ckpt.npz"
    save_checkpoint(path, params)
    back = load_checkpoint(path, params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)


def test_param_pspecs_divisibility_fallback():
    """kv-projection output (8 heads x 128) shards 16 ways via the fused
    dim; a 7-wide dim must fall back to replication."""
    from jax.sharding import PartitionSpec as P

    from repro.arch.sharding import param_pspecs

    params = {
        "wk": jnp.zeros((128, 8 * 128)),     # fused kv dim 1024 % 16 == 0
        "odd": jnp.zeros((7, 13)),            # nothing divisible
        "layers": {"wq": jnp.zeros((4, 128, 256))},  # stacked
    }
    specs = param_pspecs(params, axis_size=16)
    assert specs["wk"] == P(None, "model")
    assert specs["odd"] == P(None, None)
    assert specs["layers"]["wq"] == P(None, None, "model")


def test_reduced_configs_under_cpu_limits():
    for name in ("mistral-large-123b", "mixtral-8x22b", "whisper-medium"):
        r = get_arch_config(name).reduced()
        assert r.num_layers == 2
        assert r.d_model <= 512
        assert (r.num_experts or 0) <= 4


def test_gossip_dp_ring_specs_roundtrip():
    """ring_mix_params with shard-aware specs matches the unsharded
    reference on a single device (specs degenerate to replicated)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.gossip_dp import ring_mix_params

    mesh = make_mesh((1,), ("node",))
    params = {"w": jnp.arange(12.0).reshape(3, 4)}
    specs = {"w": P(None, None)}
    out = ring_mix_params(params, mesh, ("node",), specs=specs)
    # single node: mix = (w + w + w)/3 = w
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(params["w"]), atol=1e-6)


def test_sweep_mesh_width_search():
    """The (grid, node) width search: both widths divide their extents,
    devices used are maximized, ties break toward the node axis (the
    memory-scaled one)."""
    from repro.launch.mesh import _sweep_mesh_widths

    # Fig-5 grid (15 scenarios) x 32 nodes on 8 devices: full node shard
    assert _sweep_mesh_widths(15, 32, 8) == (1, 8)
    # G=4, N=6: (4, 2) uses all 8 devices, beating the (1, 6)/(2, 3) layouts
    assert _sweep_mesh_widths(4, 6, 8) == (4, 2)
    # equal-total candidates (2, 4) vs (4, 2): node axis wins the tie
    assert _sweep_mesh_widths(4, 4, 8) == (2, 4)
    # degenerate: nothing divides -> (1, 1) local fallback
    assert _sweep_mesh_widths(7, 13, 4) == (1, 1)
    # single device
    assert _sweep_mesh_widths(15, 226, 1) == (1, 1)


def test_make_sweep_mesh_contract():
    """The sweep mesh always keeps the 2-D ("grid", "node") contract
    and divides its extents — down to the degenerate one-device (1, 1)
    local fallback (``devices=1`` caps the search regardless of how
    many devices the test process exposes); invalid explicit widths
    refuse."""
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh(15, 32, devices=1)
    assert mesh.axis_names == ("grid", "node")
    assert dict(mesh.shape) == {"grid": 1, "node": 1}
    auto = make_sweep_mesh(15, 32)
    assert auto.axis_names == ("grid", "node")
    assert 15 % auto.shape["grid"] == 0 and 32 % auto.shape["node"] == 0
    with pytest.raises(ValueError, match="divide"):
        make_sweep_mesh(15, 32, grid_width=2, node_width=1)


def test_choose_gossip_impl_memory_heuristic():
    """--gossip-impl auto: allgather while the gathered (N, D) federation
    fits the per-device budget, psum above it; single-shard meshes always
    allgather (the gather is a no-op copy)."""
    from repro.launch.mesh import choose_gossip_impl

    # 32 nodes x 1 KiB fits any sane budget
    assert choose_gossip_impl(32, 1024, shards=8) == "allgather"
    # 256 nodes x 64 MiB = 16 GiB gathered per device -> memory-scaled
    assert choose_gossip_impl(256, 64 << 20, shards=8) == "psum"
    # explicit budget boundary is inclusive
    assert choose_gossip_impl(4, 100, shards=4, budget_bytes=400) == "allgather"
    assert choose_gossip_impl(4, 101, shards=4, budget_bytes=400) == "psum"
    # one shard: nothing to scale
    assert choose_gossip_impl(7, 1 << 40, shards=1) == "allgather"
