#!/usr/bin/env python3
"""Bring-up smoke run of GluADFL on a TPU: train, kernels, serve.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the node-sharded gossip path only

One process, phases in order; each prints one line of what it found and
any failure exits non-zero (no phase is caught and passed over).

  device  jax version, platform, device_kind, count.  No TPU -> exit 2.
  train   the REPLACE-BG twin (226 patients) at the paper's widths: LSTM
          hidden 128, history 12, horizon 6, B=7 random topology, 30%
          inactive, batch 64, Adam.  ``GluADFL.train`` on the scan engine
          with the default plan (tree mixer, ``auto`` representation) in
          two chunks, checked against the ``engine="loop"`` oracle on the
          same key.  ``fast=True`` shortens the CGM series to 6 days; no
          width changes.
  kernel  the same federation through the compiled Pallas gossip kernels
          (dense and sparse tables) against the tree mixer.
  serve   a ``GlucoseServable`` built from the trained population:
          a four-patient cold-start cohort personalized in one call, 16
          requests replayed through ``MicroBatcher``; every forecast must
          be bitwise eager ``model.apply``, jitted ``model.apply`` and the
          same request served alone (the servable's contract).

``--four-chips`` runs only the sharded path: the twin cut to 224
patients (56 per chip), ``mixer="sharded"`` with ``gossip_impl``
allgather, psum and masked on the dense table and gather on the sparse
one, each against the tree mixer on the same key.

TPU float32 matmuls run as bfloat16 passes by default, so training is
checked within the tolerances below, not bitwise; the largest
differences are printed.  Compile seconds and rounds/s are printed as
information only — this is not a benchmark.  The last line of stdout is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

DATASET = "replace-bg"
HIDDEN = 128
HISTORY = 12
HORIZON = 6
COMM_BATCH = 7
INACTIVE = 0.3
BATCH = 64
LR = 1e-3
CHUNK = 3              # rounds per compiled chunk; train runs two
KERNEL_ROUNDS = 3
FOUR_CHIP_PATIENTS = 224
SEED = 0

# stated tolerances (max |a - b| over every element compared)
LOSS_RTOL = 1e-3       # scan engine vs loop oracle: same program numerics
PARAM_ATOL = 1e-3      # population params, scan vs loop
MIX_LOSS_RTOL = 1e-2   # another gossip backend vs the tree mixer: the
MIX_PARAM_ATOL = 1e-2  #   contraction rounds differently (bf16 passes)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def max_diff(a, b) -> float:
    """Largest elementwise |a - b| over two pytrees of equal structure."""
    return max(
        float(np.max(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def finite(tree) -> bool:
    return all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(tree))


def losses_of(hist) -> np.ndarray:
    return np.asarray([h["loss"] for h in hist], np.float64)


def rel_loss_diff(a, b) -> float:
    la, lb = losses_of(a), losses_of(b)
    return float(np.max(np.abs(la - lb) / np.maximum(np.abs(lb), 1.0)))


def device_phase(want: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    found = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(f"[device] jax {jax.__version__} platform={d.platform} "
          f"kind={d.device_kind!r} count={len(devs)}", flush=True)
    if d.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform={d.platform}); "
              f"refusing to run on another backend", file=sys.stderr)
        sys.exit(2)
    check(len(devs) >= want, f"need {want} TPU devices, found {len(devs)}")
    from repro.kernels.ops import _on_tpu

    check(_on_tpu(), "kernels would run in interpret mode on this backend")
    return found


def trainer(cfg, **kw):
    from repro.core import GluADFL
    from repro.models import LSTMModel
    from repro.optim import adam

    model = LSTMModel(history_len=HISTORY, hidden=HIDDEN).as_model()
    return GluADFL(model, adam(LR), cfg, **kw)


def fl_config(n: int, rounds: int):
    from repro.config import FLConfig

    return FLConfig(topology="random", num_nodes=n, rounds=rounds,
                    comm_batch=COMM_BATCH, inactive_ratio=INACTIVE, seed=SEED)


def load(max_patients=None):
    from repro.data import load_federated_dataset

    return load_federated_dataset(DATASET, history_len=HISTORY, horizon=HORIZON,
                                  fast=True, max_patients=max_patients, seed=SEED)


def train_phase(fed):
    n = fed.num_nodes
    rounds = 2 * CHUNK
    tr = trainer(fl_config(n, rounds), gossip_repr="auto")
    key = jax.random.PRNGKey(SEED)
    run = functools.partial(tr.train, key, fed.x, fed.y, fed.counts,
                            batch_size=BATCH, rounds=rounds)
    t0 = time.perf_counter()
    pop, hist, _ = run(chunk=CHUNK)
    jax.block_until_ready(pop)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    pop2, _, _ = run(chunk=CHUNK)
    jax.block_until_ready(pop2)
    warm = time.perf_counter() - t0
    pop_l, hist_l, _ = run(engine="loop")
    check(finite(pop) and np.isfinite(losses_of(hist)).all(), "train: non-finite")
    check(max_diff(pop, pop2) == 0.0, "train: two runs on one key differ")
    d_loss, d_par = rel_loss_diff(hist, hist_l), max_diff(pop, pop_l)
    print(f"[train] {DATASET} twin N={n} (fast=True: 6-day series, widths "
          f"unchanged) hidden={HIDDEN} L={HISTORY} horizon={HORIZON} "
          f"B={COMM_BATCH} inactive={INACTIVE} batch={BATCH} adam "
          f"plan={tr.plan.mixer}/{tr.plan.gossip_repr} rounds={rounds} "
          f"chunk={CHUNK} loss {hist[0]['loss']:.6f}->{hist[-1]['loss']:.6f} "
          f"| vs loop: max rel loss diff {d_loss:.3e} (tol {LOSS_RTOL:g}), "
          f"max pop param diff {d_par:.3e} (tol {PARAM_ATOL:g}) "
          f"| compile+first call {first:.2f}s, warm call {warm:.2f}s, "
          f"compile ~{first - warm:.2f}s, steady {rounds / warm:.2f} rounds/s",
          flush=True)
    check(d_loss <= LOSS_RTOL, f"train: loss diff {d_loss} vs loop oracle")
    check(d_par <= PARAM_ATOL, f"train: param diff {d_par} vs loop oracle")
    return tr.model, pop


def kernel_phase(fed):
    n = fed.num_nodes
    key = jax.random.PRNGKey(SEED + 1)
    cfg = fl_config(n, KERNEL_ROUNDS)
    parts = []
    for repr_ in ("dense", "sparse"):
        kern = trainer(cfg, mixer="kernel", gossip_repr=repr_)
        lowered = jax.jit(functools.partial(
            kern.train_chunk, batch_size=BATCH, chunk=KERNEL_ROUNDS,
        )).lower(kern.init(key), fed.x, fed.y, fed.counts)
        check("tpu_custom_call" in lowered.as_text(),
              f"kernel/{repr_}: no compiled Pallas call in the trainer program")
        out = {}
        for name, t in (("kernel", kern), ("tree", trainer(cfg, gossip_repr=repr_))):
            pop, hist, _ = t.train(key, fed.x, fed.y, fed.counts, batch_size=BATCH,
                                   rounds=KERNEL_ROUNDS, chunk=KERNEL_ROUNDS)
            check(finite(pop), f"kernel/{repr_}/{name}: non-finite")
            out[name] = (pop, hist)
        d_loss = rel_loss_diff(out["kernel"][1], out["tree"][1])
        d_par = max_diff(out["kernel"][0], out["tree"][0])
        parts.append(f"{repr_}: tpu_custom_call yes, max rel loss diff "
                     f"{d_loss:.3e}, max pop param diff {d_par:.3e}")
        check(d_loss <= MIX_LOSS_RTOL and d_par <= MIX_PARAM_ATOL,
              f"kernel/{repr_} vs tree: loss {d_loss}, params {d_par}")
    print(f"[kernel] N={n} rounds={KERNEL_ROUNDS} vs tree mixer (tol "
          f"{MIX_LOSS_RTOL:g} rel loss, {MIX_PARAM_ATOL:g} params) | "
          + " | ".join(parts), flush=True)


def serve_phase(fed, model, pop):
    import jax.numpy as jnp

    from repro.serve import GlucoseServable, MicroBatcher, Request, replay

    servable = GlucoseServable(model, pop, buckets=(1, 4))
    n, k, m = fed.num_nodes, 4, 24
    cohort = list(range(n - k, n))
    x = np.zeros((k, m, HISTORY), np.float32)
    y = np.zeros((k, m), np.float32)
    counts = np.zeros((k,), np.int32)
    for i, pi in enumerate(cohort):
        p = fed.patients[pi]
        c = min(m, len(p.train_x))
        x[i, :c], y[i, :c], counts[i] = p.train_x[:c], p.train_y[:c], c
    rows = servable.personalize(cohort, jax.random.split(jax.random.PRNGKey(SEED), k),
                                x, y, counts)
    check(finite(rows), "serve: personalized rows non-finite")
    check(max_diff(jax.tree.map(lambda l: l[0], rows), pop) > 0.0,
          "serve: personalization left the population model unchanged")
    servable.warmup(history_len=HISTORY)

    rng = np.random.default_rng(SEED)
    patients = cohort + [int(v) for v in rng.integers(0, n - k, size=4)]
    reqs = []
    for rid in range(16):
        p = fed.patients[patients[rid % len(patients)]]
        w = np.asarray(p.test_x[int(rng.integers(0, len(p.test_x)))], np.float32)
        reqs.append(Request(rid=rid, patient=servable.row_of_or_population(
            patients[rid % len(patients)]), window=w))
    preds = replay(servable, MicroBatcher((1, 4), max_live_batches=4), reqs)
    check(len(preds) == len(reqs), "serve: lost requests")
    check(np.isfinite(list(preds.values())).all(), "serve: non-finite forecast")

    apply_jit = jax.jit(model.apply)
    eager, jitted, alone = [], [], []
    for r in reqs:
        row = jax.tree.map(lambda l: l[0], servable.params_rows([r.patient]))
        win = jnp.asarray(r.window)[None, :]
        eager.append(float(model.apply(row, win)[0]))
        jitted.append(float(apply_jit(row, win)[0]))
        alone.append(float(servable.forecast_rows([r.patient], r.window[None])[0]))
    served = np.asarray([preds[r.rid] for r in reqs], np.float64)

    def cmp(ref):
        ref = np.asarray(ref, np.float64)
        return float(np.max(np.abs(served - ref))), int(np.sum(served == ref))

    (d_e, b_e), (d_j, b_j), (d_a, b_a) = cmp(eager), cmp(jitted), cmp(alone)
    print(f"[serve] buckets (1, 4): personalized {k} patients in one call, "
          f"replayed {len(reqs)} requests | served vs eager model.apply: max "
          f"diff {d_e:.3e}, bitwise {b_e}/{len(reqs)} | vs jit(model.apply): "
          f"max diff {d_j:.3e}, bitwise {b_j}/{len(reqs)} | vs served alone "
          f"(bucket 1): max diff {d_a:.3e}, bitwise {b_a}/{len(reqs)}", flush=True)
    check(b_e == b_j == b_a == len(reqs), "serve: a forecast is not bitwise model.apply")


def four_chip_phase():
    from repro.launch.mesh import make_federation_mesh
    from repro.launch.multihost import place_federation

    fed = load(max_patients=FOUR_CHIP_PATIENTS)
    n = fed.num_nodes
    mesh = make_federation_mesh(n)
    mesh_devs = {d.id for d in mesh.devices.flat}
    print(f"[mesh] {DATASET} twin cut to N={n} patients ({n // len(mesh_devs)} per "
          f"chip) hidden={HIDDEN}: node mesh {dict(mesh.shape)} over devices "
          f"{sorted(mesh_devs)}; N=226 would get width "
          f"{make_federation_mesh(226).shape['node']}", flush=True)
    check(len(mesh_devs) == 4, f"mesh spans {len(mesh_devs)} devices, want 4")

    key = jax.random.PRNGKey(SEED + 2)
    cfg = fl_config(n, KERNEL_ROUNDS)
    gx, gy, gc, _ = place_federation(mesh, fed.x, fed.y, fed.counts)

    def run(t, state, x, y, c):
        state, losses = t.train_chunk(state, x, y, c, batch_size=BATCH,
                                      chunk=KERNEL_ROUNDS)
        return state, np.asarray(losses, np.float64)

    refs = {}
    for impl, repr_ in (("allgather", "dense"), ("psum", "dense"),
                        ("masked", "dense"), ("gather", "sparse")):
        sh = trainer(cfg, mixer="sharded", gossip_impl=impl, gossip_repr=repr_,
                     mesh=mesh)
        if sh.plan.gossip_repr not in refs:
            ref = trainer(cfg, gossip_repr=sh.plan.gossip_repr)
            refs[sh.plan.gossip_repr] = run(ref, ref.init(key), fed.x, fed.y,
                                            fed.counts)
        ref_state, ref_losses = refs[sh.plan.gossip_repr]
        state, losses = run(sh, sh.init_sharded(key, mesh), gx, gy, gc)
        spans = {d.id for l in jax.tree.leaves(state.params)
                 for d in l.sharding.device_set}
        check(finite(state.params) and np.isfinite(losses).all(),
              f"sharded/{impl}: non-finite")
        d_loss = float(np.max(np.abs(losses - ref_losses)
                              / np.maximum(np.abs(ref_losses), 1.0)))
        d_par = max_diff(state.params, ref_state.params)
        print(f"[sharded] gossip_impl={impl} repr={sh.plan.gossip_repr} "
              f"rounds={KERNEL_ROUNDS}: state on {len(spans)} devices | vs tree: "
              f"max rel loss diff {d_loss:.3e} (tol {MIX_LOSS_RTOL:g}), max "
              f"param diff {d_par:.3e} (tol {MIX_PARAM_ATOL:g})", flush=True)
        check(spans == mesh_devs, f"sharded/{impl}: state spans {sorted(spans)}")
        check(d_loss <= MIX_LOSS_RTOL and d_par <= MIX_PARAM_ATOL,
              f"sharded/{impl} vs tree: loss {d_loss}, params {d_par}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the node-sharded gossip path on 4 chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    found = device_phase(4 if args.four_chips else 1)
    print(f"[cache] persistent compile cache at {cache}", flush=True)
    if args.four_chips:
        four_chip_phase()
    else:
        fed = load()
        model, pop = train_phase(fed)
        kernel_phase(fed)
        serve_phase(fed, model, pop)
    print(json.dumps({"ok": True, "device": found}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
