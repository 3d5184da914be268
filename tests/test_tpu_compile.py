"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Each test compiles one kernel for a described (not attached) v5e chip —
the TPU compiler runs here, nothing executes — at the paper's largest
cohort, N = 226 nodes padded to 232, and D = the hidden-128 LSTM's
parameter count padded to ``TILE_D``.  Mosaic refuses what the CPU
interpreter accepts (unaligned slices, in-kernel gathers, VMEM over the
limit), so these guard the compiled path at no chip time.  The
topology is described inside a module fixture: only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gossip_mix import (
    TILE_D,
    gossip_mix_dp_pallas,
    gossip_mix_pallas,
    gossip_mix_sparse_dp_pallas,
    gossip_mix_sparse_pallas,
)
from repro.kernels.lstm_cell import lstm_cell_pallas
from repro.models import LSTMModel

N_PAD = 232     # 226 nodes padded to the 8-sublane multiple
B1 = 8          # B = 7 neighbours + self
HIDDEN = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep it off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def d_pad():
    """Flat parameter count of the hidden-128 LSTM, padded to TILE_D."""
    shapes = jax.eval_shape(LSTMModel(hidden=HIDDEN).as_model().init,
                            jax.random.PRNGKey(0))
    d = sum(s.size for s in jax.tree.leaves(shapes))
    return -(-d // TILE_D) * TILE_D


def _compile(fn, shardings, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=shardings) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"


F32, I32 = jnp.float32, jnp.int32


def test_dense_gossip_kernel_compiles(one_chip, d_pad):
    _compile(lambda m, w, a: gossip_mix_pallas(m, w, a, interpret=False), one_chip,
             ((N_PAD, N_PAD), F32), ((N_PAD, d_pad), F32), ((N_PAD,), F32))


def test_dense_dp_gossip_kernel_compiles(one_chip, d_pad):
    _compile(lambda m, w, z, a: gossip_mix_dp_pallas(m, w, z, a, interpret=False),
             one_chip, ((N_PAD, N_PAD), F32), ((N_PAD, d_pad), F32),
             ((N_PAD, d_pad), F32), ((N_PAD,), F32))


def test_sparse_gossip_kernel_compiles(one_chip, d_pad):
    _compile(lambda i, g, w, a: gossip_mix_sparse_pallas(i, g, w, a, interpret=False),
             one_chip, ((N_PAD, B1), I32), ((N_PAD, B1), F32),
             ((N_PAD, d_pad), F32), ((N_PAD,), F32))


def test_sparse_dp_gossip_kernel_compiles(one_chip, d_pad):
    _compile(
        lambda i, g, w, z, a: gossip_mix_sparse_dp_pallas(i, g, w, z, a, interpret=False),
        one_chip, ((N_PAD, B1), I32), ((N_PAD, B1), F32), ((N_PAD, d_pad), F32),
        ((N_PAD, d_pad), F32), ((N_PAD,), F32))


def test_lstm_cell_kernel_compiles(one_chip):
    b, h, i = 128, HIDDEN, 1
    _compile(
        lambda x, hh, c, wx, wh, bb: lstm_cell_pallas(x, hh, c, wx, wh, bb, interpret=False),
        one_chip, ((b, i), F32), ((b, h), F32), ((b, h), F32),
        ((i, 4 * h), F32), ((h, 4 * h), F32), ((4 * h,), F32))
