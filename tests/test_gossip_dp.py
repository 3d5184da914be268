"""Gossip data-parallelism correctness sweep (``core/gossip_dp.py``).

Pins the module against the single-device dense oracle: on a forced
8-device mesh, ``gossip_mix_params`` under both collectives
(allgather / psum-scatter) must equal ``mixing_matrix(...) @ w`` row for
row, and ``ring_mix_params`` must equal
``mixing_matrix(ring_adjacency(N), ones, 2) @ w`` for N ∈ {2, 4, 8} —
the N=2 case is the regression test for the double-peer bug (fwd and
bwd permutes deliver the SAME node, so the three-way average weighted
the single peer 2/3 instead of 1/2).  Node-varying parameters are
manufactured INSIDE one jit via a shard_map scatter (params are
logically replicated over the node axes, so divergence can't be fed in
from the host).  Tier-1 half: ``GossipDPSchedule`` key-stream
determinism and the ``ring_mix_params`` specs-leaf-count guard."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.gossip_dp import GossipDPSchedule, ring_mix_params
from repro.launch.mesh import make_mesh

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


# ---------------------------------------------------------------------------
# tier-1: host-side schedule + input validation
# ---------------------------------------------------------------------------


def test_schedule_key_stream_deterministic():
    """Same seed -> bitwise-identical mixing-matrix sequence; a
    different seed diverges.  The schedule is the only stateful object
    in gossip-DP, so replaying a run hinges on exactly this."""
    def draw(seed, k=4):
        s = GossipDPSchedule("random", 8, comm_batch=3, mix_every=2,
                             inactive_ratio=0.3, seed=seed)
        return [np.asarray(s.next_mix()) for _ in range(k)]

    a, b = draw(0), draw(0)
    for ma, mb in zip(a, b):
        np.testing.assert_array_equal(ma, mb)
    c = draw(1)
    assert any(not np.array_equal(ma, mc) for ma, mc in zip(a, c))
    # each matrix is row-stochastic (a sanity floor under the oracle tests)
    for m in a:
        np.testing.assert_allclose(m.sum(axis=1), np.ones(8), atol=1e-6)


def test_schedule_cadence():
    s = GossipDPSchedule("ring", 4, mix_every=3)
    assert [s.should_mix(t) for t in range(6)] == [
        False, False, True, False, False, True
    ]


def test_ring_mix_specs_leaf_mismatch_raises():
    """A specs tree with the wrong leaf count must refuse loudly — the
    old ``zip`` silently truncated and mixed the tail as replicated."""
    mesh = make_mesh((1, 1), ("node", "model"))
    params = {"a": np.ones((4,)), "b": np.ones((4,))}
    with pytest.raises(ValueError, match="leaves"):
        ring_mix_params(params, mesh, ("node",), specs={"a": P(None)})


# ---------------------------------------------------------------------------
# multidevice: dense-oracle parity on a forced 8-device mesh
# ---------------------------------------------------------------------------

_SCATTER_GATHER = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh

    def node_varying(mesh):
        # params in gossip-DP are replicated over the node axes (P()),
        # so per-node divergence must be built INSIDE the program:
        # scatter hands node i row i of a host (N, D) base, gather
        # reads the per-node values back out as (N, D)
        scatter = jax.shard_map(lambda b: b[jax.lax.axis_index('node')],
                                mesh=mesh, in_specs=(P(),), out_specs=P(),
                                check_vma=False)
        gather = jax.shard_map(lambda w: jax.lax.all_gather(w, 'node'),
                               mesh=mesh, in_specs=(P(),), out_specs=P(),
                               check_vma=False)
        return scatter, gather
"""


@pytest.mark.multidevice
def test_gossip_mix_params_matches_dense_oracle():
    """allgather == psum-scatter == ``mix @ w`` for every node row."""
    print(_run(_SCATTER_GATHER + """
    from repro.core.gossip_dp import gossip_mix_params
    from repro.core.topology import mixing_matrix, random_adjacency

    N, D = 4, 96
    mesh = make_mesh((N, 2), ('node', 'model'))
    base = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    adj = random_adjacency(jax.random.PRNGKey(3), N, 2)
    active = jnp.array([1.0, 0.0, 1.0, 1.0])
    mix = mixing_matrix(adj, active, 2)
    scatter, gather = node_varying(mesh)
    oracle = np.asarray(mix @ base)

    for impl in ("allgather", "psum"):
        @jax.jit
        def run(b):
            out = gossip_mix_params({'w': scatter(b)}, mix, mesh,
                                    ('node',), impl=impl)
            return gather(out['w'])
        got = np.asarray(run(base))
        np.testing.assert_allclose(got, oracle, atol=1e-5, err_msg=impl)
        # inactive node 1 has the identity row: bitwise-unchanged params
        np.testing.assert_array_equal(got[1], np.asarray(base)[1])
    print("GOSSIP_MIX_ORACLE_OK")
    """))


@pytest.mark.multidevice
def test_ring_mix_matches_mixing_matrix_oracle():
    """``ring_mix_params`` == the paper's ring mixing matrix for
    N ∈ {2, 4, 8}.  N=2 is the double-peer regression: pre-fix the
    permute average gave (w0 + 2·w1)/3 instead of (w0 + w1)/2."""
    print(_run(_SCATTER_GATHER + """
    from repro.core.gossip_dp import ring_mix_params
    from repro.core.topology import mixing_matrix, ring_adjacency

    D = 64
    for N in (2, 4, 8):
        mesh = make_mesh((N, 8 // N), ('node', 'model'))
        base = jax.random.normal(jax.random.PRNGKey(N), (N, D))
        scatter, gather = node_varying(mesh)

        @jax.jit
        def run(b):
            out = ring_mix_params({'w': scatter(b)}, mesh, ('node',))
            return gather(out['w'])

        oracle = mixing_matrix(
            ring_adjacency(N), jnp.ones((N,)), 2
        ) @ base
        np.testing.assert_allclose(
            np.asarray(run(base)), np.asarray(oracle), atol=1e-5,
            err_msg=f"N={N}",
        )
    print("RING_MIX_ORACLE_OK")
    """))
