"""Benchmark driver — one function per paper table/figure + kernel
micro-benchmarks.  Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--full]

Paper experiments run on synthetic-twin data (DESIGN.md §5) at reduced
scale by default; --full restores paper-scale rounds (hours).
The roofline table (harness §Roofline) is produced by
``python -m benchmarks.roofline`` (512-device dry-run; summarized here
if its JSON output exists).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np


def _bench(fn, *args, reps=3, warmup=1, **kw):
    for _ in range(warmup):
        fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / reps
    return out, dt * 1e6


def bench_kernels() -> list[str]:
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import gossip_mix, lstm_cell, swa_attention
    from repro.kernels.ref import gossip_mix_ref, lstm_cell_ref, swa_attention_ref

    lines = []
    # gossip mix: federation of 226 nodes (REPLACE-BG); 10k-param slab
    # (interpret mode runs one python iteration per D-tile — sized so the
    # CPU bench stays seconds; the TPU path is compiled)
    n, d = 226, 9_984
    mix = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (n, n)), axis=-1)
    w = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    act = jnp.ones((n,))
    f_kernel = jax.jit(gossip_mix)
    f_ref = jax.jit(gossip_mix_ref)
    _, us_k = _bench(lambda: jax.block_until_ready(f_kernel(mix, w, act)))
    _, us_r = _bench(lambda: jax.block_until_ready(f_ref(mix, w, act)))
    gbs = (n * d * 4 * 2) / (us_k / 1e6) / 1e9
    lines.append(f"kernel.gossip_mix.interp,{us_k:.1f},ref_us={us_r:.1f};GBps={gbs:.2f}")

    # lstm cell: B=256 H=128 (paper's model)
    bsz, hsz = 256, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    xx = jax.random.normal(ks[0], (bsz, 1))
    hh = jax.random.normal(ks[1], (bsz, hsz))
    cc = jax.random.normal(ks[2], (bsz, hsz))
    wx = jax.random.normal(ks[3], (1, 4 * hsz))
    wh = jax.random.normal(ks[4], (hsz, 4 * hsz))
    bb = jnp.zeros((4 * hsz,))
    fk = jax.jit(lambda a, b, c2, d2, e, f: lstm_cell(a, b, c2, d2, e, f)[0])
    fr = jax.jit(lambda a, b, c2, d2, e, f: lstm_cell_ref(a, b, c2, d2, e, f)[0])
    _, us_k = _bench(lambda: jax.block_until_ready(fk(xx, hh, cc, wx, wh, bb)))
    _, us_r = _bench(lambda: jax.block_until_ready(fr(xx, hh, cc, wx, wh, bb)))
    lines.append(f"kernel.lstm_cell.interp,{us_k:.1f},ref_us={us_r:.1f}")

    # swa attention: 1x1024x4x64, window 256
    q = jax.random.normal(ks[5], (1, 1024, 4, 64))
    import functools
    fk = jax.jit(functools.partial(swa_attention, window=256))
    fr = jax.jit(functools.partial(swa_attention_ref, window=256))
    _, us_k = _bench(lambda: jax.block_until_ready(fk(q, q, q)))
    _, us_r = _bench(lambda: jax.block_until_ready(fr(q, q, q)))
    lines.append(f"kernel.swa_attention.interp,{us_k:.1f},ref_us={us_r:.1f}")
    return lines


def bench_fl_round() -> list[str]:
    """GluADFL round throughput (the paper's training loop hot path)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import Scale, load
    from repro.config import FLConfig
    from repro.core import GluADFL
    from repro.models import LSTMModel
    from repro.optim import adam

    scale = Scale()
    fed = load("ohiot1dm", scale)
    model = LSTMModel(hidden=scale.hidden).as_model()
    lines = []
    for topo in ("ring", "random"):
        cfg = FLConfig(topology=topo, num_nodes=fed.num_nodes, rounds=5, comm_batch=7)
        tr = GluADFL(model, adam(2e-3), cfg)
        state = tr.init(jax.random.PRNGKey(0), fed.x[0, :1])
        x, y, c = jnp.asarray(fed.x), jnp.asarray(fed.y), jnp.asarray(fed.counts)
        tr._round_jit(state, x, y, c, batch_size=64)  # compile
        t0 = time.perf_counter()
        reps = 10
        for _ in range(reps):
            state, loss = tr._round_jit(state, x, y, c, batch_size=64)
        jax.block_until_ready(state.params)
        us = (time.perf_counter() - t0) / reps * 1e6
        lines.append(f"fl.gluadfl_round.{topo},{us:.0f},nodes={fed.num_nodes}")
    return lines


def summarize_roofline() -> list[str]:
    out_dir = Path(__file__).resolve().parents[1] / "experiments" / "roofline"
    lines = []
    if not out_dir.exists():
        return ["roofline.missing,0,run `python -m benchmarks.roofline`"]
    for f in sorted(out_dir.glob("*.json")):
        r = json.loads(f.read_text())
        if r.get("status") != "ok":
            continue
        t = r["terms_s"]
        bound = max(t.values())
        lines.append(
            f"roofline.{r['arch']}.{r['shape']},{bound*1e6:.0f},"
            f"dominant={r['dominant']};compute_ms={t['compute']*1e3:.2f};"
            f"memory_ms={t['memory']*1e3:.2f};collective_ms={t['collective']*1e3:.2f};"
            f"useful={r['useful_flop_ratio']:.2f}"
        )
    return lines


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="kernels + reduced tables")
    ap.add_argument("--full", action="store_true", help="paper-scale rounds")
    args = ap.parse_args()

    from benchmarks.common import Scale
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    scale = Scale.full() if args.full else Scale()
    if args.quick:
        scale = Scale(rounds=20, sup_steps=150, max_patients=6, hidden=32)

    print("name,us_per_call,derived")
    for line in bench_kernels():
        print(line)
    for line in bench_fl_round():
        print(line)

    from benchmarks import (
        fig3_personalization,
        fig4_topology,
        fig5_async,
        table2_generalization,
        table3_supervised,
        table4_baselines,
    )

    t0 = time.time()
    s2 = table2_generalization.run(scale)
    print(f"table2.generalization,{(time.time()-t0)*1e6:.0f},"
          f"mean_unseen_gap_rmse={s2['mean_unseen_minus_seen_rmse']:.3f}")

    t0 = time.time()
    table3_supervised.run(scale)
    print(f"table3.supervised,{(time.time()-t0)*1e6:.0f},ok")

    t0 = time.time()
    datasets = ["ohiot1dm", "abc4d"] if args.quick else None
    table4_baselines.run(scale, datasets=datasets)
    print(f"table4.baselines,{(time.time()-t0)*1e6:.0f},ok")

    # figures: 2 datasets by default (all 4 with --full; 1 with --quick)
    fig_ds = (["ohiot1dm"] if args.quick
              else None if args.full else ["ohiot1dm", "abc4d"])
    t0 = time.time()
    fig3_personalization.run(scale, datasets=fig_ds)
    print(f"fig3.personalization,{(time.time()-t0)*1e6:.0f},ok")

    t0 = time.time()
    fig4_topology.run(scale, datasets=fig_ds)
    print(f"fig4.topology,{(time.time()-t0)*1e6:.0f},ok")

    t0 = time.time()
    fig5_async.run(scale, datasets=fig_ds,
                   ratios=[0.0, 0.5, 0.9] if args.quick
                   else [0.0, 0.3, 0.7, 0.9] if not args.full else None)
    print(f"fig5.async,{(time.time()-t0)*1e6:.0f},ok")

    for line in summarize_roofline():
        print(line)


if __name__ == "__main__":
    main()
