"""Pallas TPU kernel: blocked gossip parameter mixing (the paper's
Step 2+3 — ``W <- M @ W`` with a row-stochastic mixing matrix).

TPU adaptation (DESIGN.md §3): gossip is expressed as a dense mixing
contraction rather than point-to-point sends.  The contraction is
memory-bound (N is the federation size, tiny against D, the flattened
parameter size), so the kernel's job is to stream the (N, D) parameter
matrix through VMEM exactly once in MXU-aligned D-tiles while the (N, N)
mixing matrix stays VMEM-resident, and to fuse the active-mask select so
inactive nodes' rows are copies rather than flops.

Grid: one program per D-tile.  BlockSpecs:
  mix    (N, N)        — replicated to every program (index_map -> (0, 0)),
  w      (N, TILE_D)   — the program's slice of the parameter matrix,
  active (N, 1)        — replicated,
  out    (N, TILE_D).

N is padded to the 8-lane sublane multiple by the wrapper (ops.py).

The local-DP variant (``gossip_mix_dp_pallas``) fuses the whole DP
broadcast — noise-add, mix, clean-self-restore — into the same single
pass: ``out = M @ (W + Z) - diag(M) * Z`` (each node shares a noised
view but re-adds its own clean self-contribution), so the (N, D) matrix
is still streamed through VMEM exactly once instead of the three
tree_map passes the unfused path takes.

Sparse (neighbor-table) twins: ``gossip_mix_sparse_pallas`` /
``gossip_mix_sparse_dp_pallas`` take the (N, B+1) ``(idx, wgt)`` table
from ``core.topology.neighbor_table`` instead of the dense matrix and
compute ``out[n] = Σ_b wgt[n,b] · w[idx[n,b]]`` (DP:
``Σ_b wgt[n,b]·(w+z)[idx[n,b]] − wgt[n,0]·z[n]``) — O(N·B·D) flops on
the same one-pass TILE_D streaming layout.  The table is flattened and
scalar-prefetched into SMEM (``PrefetchScalarGridSpec``); the body loops
over output rows and reads each neighbour row out of the VMEM-resident
``(N, TILE_D)`` block with a dynamic ``pl.ds`` row slice, so the same
body runs compiled on a TPU and in the CPU interpreter.  The table
costs 8·N·(B+1) bytes of SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_D = 512  # lane-dim tile: multiple of 128 (MXU), 4 regs deep


def _kernel(mix_ref, w_ref, act_ref, out_ref):
    mix = mix_ref[...]          # (N, N) f32, VMEM-resident
    w = w_ref[...]              # (N, TILE_D)
    act = act_ref[...]          # (N, 1)
    mixed = jnp.dot(
        mix, w.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    out = act * mixed + (1.0 - act) * w.astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gossip_mix_pallas(
    mix: jnp.ndarray,
    w: jnp.ndarray,
    active: jnp.ndarray,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """mix (N,N) f32, w (N,D), active (N,) -> (N,D).  D % TILE_D == 0
    (ops.py pads)."""
    n, d = w.shape
    assert d % TILE_D == 0, d
    grid = (d // TILE_D,)
    act2 = active.astype(jnp.float32).reshape(n, 1)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda j: (0, 0)),
            pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), w.dtype),
        interpret=interpret,
    )(mix.astype(jnp.float32), w, act2)


def _gather_rows(idx_ref, wgt_ref, read_row, out_ref, row_fn):
    """``out[r] = row_fn(r, Σ_b wgt[r,b] · read_row(idx[r,b]))`` for
    every row of the block.  ``idx_ref``/``wgt_ref`` are the flat
    (N·B1,) SMEM tables; ``read_row(j)`` returns row ``j`` of the VMEM
    block(s) as a dynamic (1, TILE_D) slice."""
    n = out_ref.shape[0]
    b1 = idx_ref.shape[0] // n

    def row(r, carry):
        acc = jnp.zeros((1, out_ref.shape[1]), jnp.float32)
        for b in range(b1):  # B+1 is small and static: unrolled
            k = r * b1 + b
            acc = acc + wgt_ref[k] * read_row(idx_ref[k])
        out_ref[pl.ds(r, 1), :] = row_fn(r, acc).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n, row, 0)


def _sparse_kernel(idx_ref, wgt_ref, w_ref, act_ref, out_ref):
    def w_row(j):
        return w_ref[pl.ds(j, 1), :].astype(jnp.float32)

    _gather_rows(idx_ref, wgt_ref, w_row, out_ref, lambda r, mixed: mixed)
    # bit-exact inactive copies
    out_ref[...] = jnp.where(act_ref[...] > 0, out_ref[...], w_ref[...])


def _sparse_call(kernel, idx, wgt, blocks, active, out_dtype, interpret):
    """Shared ``pallas_call`` of the two sparse kernels: the flat table
    is scalar-prefetched into SMEM, every (N, D) operand in ``blocks``
    streams through VMEM in (N, TILE_D) tiles, ``active`` is replicated."""
    n, d = blocks[0].shape
    b1 = idx.shape[1]
    assert d % TILE_D == 0, d
    assert idx.shape == wgt.shape == (n, b1), (idx.shape, wgt.shape, (n, d))
    tile = pl.BlockSpec((n, TILE_D), lambda j, idx_s, wgt_s: (0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d // TILE_D,),
        in_specs=[tile] * len(blocks)
        + [pl.BlockSpec((n, 1), lambda j, idx_s, wgt_s: (0, 0))],
        out_specs=tile,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        interpret=interpret,
    )(
        idx.astype(jnp.int32).reshape(-1),
        wgt.astype(jnp.float32).reshape(-1),
        *blocks,
        active.astype(jnp.float32).reshape(n, 1),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def gossip_mix_sparse_pallas(
    idx: jnp.ndarray,
    wgt: jnp.ndarray,
    w: jnp.ndarray,
    active: jnp.ndarray,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Sparse gather-mix: idx/wgt (N, B+1) neighbor table, w (N, D),
    active (N,) -> (N, D).  D % TILE_D == 0 (ops.py pads)."""
    return _sparse_call(_sparse_kernel, idx, wgt, (w,), active, w.dtype, interpret)


def _sparse_dp_kernel(idx_ref, wgt_ref, w_ref, noise_ref, act_ref, out_ref):
    b1 = idx_ref.shape[0] // w_ref.shape[0]

    def shared_row(j):  # the noised view a neighbour shares
        return (w_ref[pl.ds(j, 1), :].astype(jnp.float32)
                + noise_ref[pl.ds(j, 1), :].astype(jnp.float32))

    def restore(r, mixed):
        # slot 0 is always self: wgt[r, 0] is the densified diagonal
        z_self = noise_ref[pl.ds(r, 1), :].astype(jnp.float32)
        return mixed - wgt_ref[r * b1] * z_self     # clean-self-restore

    _gather_rows(idx_ref, wgt_ref, shared_row, out_ref, restore)
    out_ref[...] = jnp.where(act_ref[...] > 0, out_ref[...], w_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def gossip_mix_sparse_dp_pallas(
    idx: jnp.ndarray,
    wgt: jnp.ndarray,
    w: jnp.ndarray,
    noise: jnp.ndarray,
    active: jnp.ndarray,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused sparse local-DP gossip:
    ``out[n] = Σ_b wgt[n,b]·(w+z)[idx[n,b]] − wgt[n,0]·z[n]`` with the
    active-mask select, one VMEM pass.  Shapes as
    ``gossip_mix_sparse_pallas`` plus noise (N, D)."""
    assert noise.shape == w.shape, (noise.shape, w.shape)
    return _sparse_call(
        _sparse_dp_kernel, idx, wgt, (w, noise.astype(w.dtype)), active,
        w.dtype, interpret,
    )


def _dp_kernel(mix_ref, w_ref, noise_ref, self_w_ref, act_ref, out_ref):
    mix = mix_ref[...]                              # (N, N) f32, VMEM-resident
    w = w_ref[...].astype(jnp.float32)              # (N, TILE_D)
    noise = noise_ref[...].astype(jnp.float32)      # (N, TILE_D)
    self_w = self_w_ref[...]                        # (N, 1) = diag(mix), grid-
    act = act_ref[...]                              # (N, 1)   invariant, hoisted
    mixed = jnp.dot(mix, w + noise, preferred_element_type=jnp.float32)
    out = mixed - self_w * noise                    # clean-self-restore
    # where-select, not arithmetic blend: inactive rows stay bit-exact
    # copies even when active rows hold NaN/Inf (diverging runs)
    out = jnp.where(act > 0, out, w)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gossip_mix_dp_pallas(
    mix: jnp.ndarray,
    w: jnp.ndarray,
    noise: jnp.ndarray,
    active: jnp.ndarray,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused local-DP gossip: ``M @ (W + Z) - diag(M) * Z`` with the
    active-mask select, one VMEM pass.  Shapes as gossip_mix_pallas plus
    noise (N, D); D % TILE_D == 0 (ops.py pads)."""
    n, d = w.shape
    assert d % TILE_D == 0, d
    assert noise.shape == w.shape, (noise.shape, w.shape)
    grid = (d // TILE_D,)
    act2 = active.astype(jnp.float32).reshape(n, 1)
    mix32 = mix.astype(jnp.float32)
    self_w = jnp.diagonal(mix32).reshape(n, 1)  # grid-invariant: once, not per tile
    return pl.pallas_call(
        _dp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda j: (0, 0)),
            pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n, TILE_D), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), w.dtype),
        interpret=interpret,
    )(mix32, w, noise.astype(w.dtype), self_w, act2)
