"""Serving-path latency/throughput benchmark: the BG-forecast service's
two device-side programs, priced per padded batch-size bucket.

  * per-bucket forecast rows — for each configured bucket B, a batch of
    B requests (random param-store rows x random CGM windows) runs
    through the servable's compiled ``forecast`` method back-to-back;
    the committed numbers are p50/p99 per-call latency (ms) and
    forecasts/sec.  Latencies are WALL CLOCK (machine-specific), so the
    regression gate checks bucket-row PRESENCE, not values — a vanished
    bucket row is how a configured batch shape would quietly stop being
    measured;
  * ``bucket_batching_gain`` — forecasts/sec at the largest bucket over
    the smallest: the same-run, machine-portable payoff of batching
    requests at all (dispatch amortization; the default ``batch_mode=
    "map"`` servable runs rows sequentially inside the program, so this
    gain is dispatch, not SIMD).  Acceptance target >= the gate's
    ``--batching-floor``;
  * ``personalize_batch_speedup_vs_serial`` — the tentpole claim: a
    16-patient cold-start cohort fine-tuned as ONE scan+vmap-batched
    program (``core.personalize.personalize_batch``) vs the historical
    per-patient Python loop (``personalize_loop``, one jitted step per
    iteration, re-traced per patient — exactly how personalization ran
    before the batched engine).  END-TO-END wall clock, compiles
    included on both sides (a cold-start cohort arrives once; there is
    no steady state to amortize a compile into), best-of-``--reps``.
    Acceptance target >= 2x (the gate's ``--personalize-floor``);
  * ``stream`` — the full service loop (MicroBatcher admission/timeout
    policy + padded forecasts) replaying a synthetic request stream;
    committed for the runbook's reference numbers, presence-only in the
    gate.

Usage:
    PYTHONPATH=src python benchmarks/serve_latency.py \
        [--checkpoint experiments/checkpoints/gluadfl_ohiot1dm_ring.npz] \
        [--buckets 1,4,16,64] [--cohort 16] [--steps 50] [--reps 3]

Writes experiments/paper/serve_latency.json; the serve CI job gates it
against the committed BENCH_serve.json via
``benchmarks/check_bench_regression.py --serve-only``.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def bench_buckets(servable, buckets, *, history_len: int, calls: int,
                  reps: int, seed: int = 0) -> dict:
    """Per-bucket p50/p99 latency (ms) and forecasts/sec of the compiled
    forecast method, timed call-by-call after warmup (compiles excluded:
    serving pays them once at ``warmup()``, not per request).  Best rep
    by throughput; percentiles come from that rep's per-call samples."""
    import jax

    rng = np.random.default_rng(seed)
    servable.warmup(history_len=history_len)
    out = {}
    for b in buckets:
        rows = rng.integers(0, servable.num_rows, size=b)
        windows = rng.normal(size=(b, history_len)).astype(np.float32)
        params = servable.params_rows(rows)
        best_fps, best_lat = 0.0, None
        for _ in range(reps):
            lat = np.empty(calls)
            for i in range(calls):
                t0 = time.perf_counter()
                jax.block_until_ready(servable.forecast(params, windows))
                lat[i] = time.perf_counter() - t0
            fps = b * calls / lat.sum()
            if fps > best_fps:
                best_fps, best_lat = fps, lat
        out[str(b)] = {
            "p50_latency_ms": float(np.percentile(best_lat, 50) * 1e3),
            "p99_latency_ms": float(np.percentile(best_lat, 99) * 1e3),
            "forecasts_per_sec": best_fps,
        }
    return out


def bench_personalize(model, pop, *, cohort: int, windows: int, steps: int,
                      reps: int, seed: int = 0) -> tuple[float, float]:
    """END-TO-END wall clock of cold-starting a ``cohort`` of patients:
    the historical per-patient loop vs one batched program.  Returns
    ``(serial_s, batched_s)`` (best of ``reps`` each).  Fresh jit caches
    per rep on BOTH sides — the loop re-traces per patient and the
    batched engine re-traces per rep, exactly the costs each pays when a
    cohort arrives at a cold service."""
    import jax
    import jax.numpy as jnp

    from repro.core.personalize import personalize_batch, personalize_loop
    from repro.optim import adam

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(cohort, windows, 12)).astype(np.float32)
    y = rng.normal(size=(cohort, windows)).astype(np.float32)
    counts = rng.integers(4, windows + 1, size=cohort).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(seed), cohort)

    serial_best = batched_best = float("inf")
    for _ in range(reps):
        opt = adam(5e-4)  # fresh optimizer object -> fresh jit caches
        t0 = time.perf_counter()
        for i in range(cohort):
            jax.block_until_ready(jax.tree.leaves(personalize_loop(
                model, opt, pop, keys[i], x[i], y[i],
                steps=steps, count=counts[i],
            ))[0])
        serial_best = min(serial_best, time.perf_counter() - t0)

        opt = adam(5e-4)
        t0 = time.perf_counter()
        jax.block_until_ready(jax.tree.leaves(personalize_batch(
            model, opt, pop, keys, jnp.asarray(x), jnp.asarray(y), counts,
            steps=steps,
        ))[0])
        batched_best = min(batched_best, time.perf_counter() - t0)
    return serial_best, batched_best


def bench_stream(servable, buckets, *, history_len: int, n_requests: int,
                 seed: int = 0) -> dict:
    """The whole service loop: replay a synthetic stream through the
    MicroBatcher (real clock) and return its stats() — reference numbers
    for the runbook, presence-only in the gate."""
    from repro.serve import MicroBatcher, Request, replay

    rng = np.random.default_rng(seed)
    servable.warmup(history_len=history_len)
    batcher = MicroBatcher(buckets)
    reqs = [
        Request(rid=i, patient=int(rng.integers(0, servable.num_rows)),
                window=rng.normal(size=(history_len,)).astype(np.float32))
        for i in range(n_requests)
    ]
    replay(servable, batcher, reqs)
    return batcher.stats()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checkpoint",
                    default=str(ROOT / "experiments/checkpoints/"
                                       "gluadfl_ohiot1dm_ring.npz"))
    ap.add_argument("--buckets", default="1,4,16,64")
    ap.add_argument("--calls", type=int, default=50,
                    help="timed forecast calls per bucket per rep")
    ap.add_argument("--cohort", type=int, default=16,
                    help="cold-start cohort size for the personalization "
                         "speedup row (the paper-claim scale is 16)")
    ap.add_argument("--windows", type=int, default=24,
                    help="padded history windows per cohort patient")
    ap.add_argument("--steps", type=int, default=50,
                    help="fine-tune steps per cohort patient")
    ap.add_argument("--requests", type=int, default=256,
                    help="synthetic stream length for the stream row")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax

    from repro.serve import GlucoseServable, load_population

    buckets = tuple(int(b) for b in args.buckets.split(",") if b)
    model, pop = load_population(args.checkpoint)
    servable = GlucoseServable(model, pop, buckets=buckets)
    L = 12  # the committed checkpoint's history length

    # a few personalized rows so bucket batches mix param rows like the
    # real service (store gathers are part of the priced path)
    rng = np.random.default_rng(args.seed)
    k = min(4, args.cohort)
    servable.personalize(
        [f"bench-{i}" for i in range(k)],
        jax.random.split(jax.random.PRNGKey(args.seed), k),
        rng.normal(size=(k, args.windows, L)).astype(np.float32),
        rng.normal(size=(k, args.windows)).astype(np.float32),
        np.full((k,), args.windows, np.int32),
    )

    bucket_rows = bench_buckets(servable, buckets, history_len=L,
                                calls=args.calls, reps=args.reps,
                                seed=args.seed)
    serial_s, batched_s = bench_personalize(
        model, pop, cohort=args.cohort, windows=args.windows,
        steps=args.steps, reps=args.reps, seed=args.seed,
    )
    stream = bench_stream(servable, buckets, history_len=L,
                          n_requests=args.requests, seed=args.seed)

    out = {
        "config": vars(args),
        "devices": len(jax.devices()),
        "buckets": bucket_rows,
        # same-run dispatch-amortization payoff of batching at all:
        # acceptance target >= the gate's --batching-floor
        "bucket_batching_gain": (
            bucket_rows[str(buckets[-1])]["forecasts_per_sec"]
            / bucket_rows[str(buckets[0])]["forecasts_per_sec"]
        ),
        # the tentpole claim: one batched cold-start program >= 2x the
        # historical per-patient loop at a 16-patient cohort
        "personalize_cohort": args.cohort,
        "personalize_serial_s": serial_s,
        "personalize_batched_s": batched_s,
        "personalize_batch_speedup_vs_serial": serial_s / batched_s,
        "stream": stream,
    }
    out_dir = ROOT / "experiments" / "paper"
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "serve_latency.json").write_text(json.dumps(out, indent=2))

    for b, row in bucket_rows.items():
        print(f"bucket {b:>3s}: p50 {row['p50_latency_ms']:7.2f}ms  "
              f"p99 {row['p99_latency_ms']:7.2f}ms  "
              f"{row['forecasts_per_sec']:8.0f} forecasts/sec")
    print(f"bucket batching gain ({buckets[-1]} vs {buckets[0]}): "
          f"{out['bucket_batching_gain']:.2f}x")
    print(f"personalize {args.cohort}-patient cohort: serial loop "
          f"{serial_s:.2f}s, one batched program {batched_s:.2f}s -> "
          f"{out['personalize_batch_speedup_vs_serial']:.2f}x (target >= 2)")
    print(f"stream: {stream['completed']} served, "
          f"p50 {stream['p50_latency_ms']:.2f}ms  "
          f"p99 {stream['p99_latency_ms']:.2f}ms  "
          f"{stream['forecasts_per_sec']:.0f} forecasts/sec")
    return out


if __name__ == "__main__":
    main()
