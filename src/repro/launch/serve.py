"""BG-forecast serving entrypoint — the deployment half of the paper's
cold-start story, end to end on one box:

    PYTHONPATH=src python -m repro.launch.serve \
        [--checkpoint experiments/checkpoints/gluadfl_ohiot1dm_ring.npz] \
        [--buckets 1,4,16,64] [--personalize 3 --steps 50] \
        [--requests 256] [--selfcheck]

Lifecycle (see ``docs/SERVING.md`` for the operator runbook):

  1. **load** — the federation checkpoint (population params; the LSTM
     width is inferred from the flat parameter count) becomes row 0 of
     the servable's param store;
  2. **personalize** — the LAST ``--personalize`` patients of the
     dataset twin play newly diagnosed arrivals: their short histories
     (first ``--history-windows`` training windows — the cold-start
     case, shorter than a training batch) fine-tune the population
     model as ONE ``lax.scan``-compiled, vmap-batched program
     (``core.personalize.personalize_batch``); the personalized rows
     join the store;
  3. **serve** — a synthetic request stream (random patient, random
     test window) flows through the ``MicroBatcher`` (pad-to-bucket,
     max-live-batches admission, timeout flush) into the per-bucket
     compiled ``forecast`` method; per-request latency stats print at
     the end.

``--selfcheck`` additionally asserts that EVERY served forecast
bitwise-matches a direct ``model.apply(params_row, window)`` call —
padding, bucketing, and batching must be invisible to the numbers —
and exits 1 on the first mismatch (CI runs this in the ``serve`` job).

The LM-architecture decode demo that used to live at this path moved
intact to ``repro.launch.arch_demo``.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import load_federated_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import GlucoseServable, MicroBatcher, Request, load_population

DEFAULT_CKPT = "experiments/checkpoints/gluadfl_ohiot1dm_ring.npz"


def build_request_stream(fed, servable, n_requests: int, seed: int):
    """A deterministic synthetic stream: each request picks a patient
    (personalized patients by name when present, else the population
    row) and one of that patient's test windows."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n_requests):
        pi = int(rng.integers(0, fed.num_nodes))
        p = fed.patients[pi]
        wi = int(rng.integers(0, len(p.test_x)))
        reqs.append(
            Request(
                rid=rid,
                patient=servable.row_of_or_population(pi),
                window=np.asarray(p.test_x[wi], np.float32),
            )
        )
    return reqs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checkpoint", default=DEFAULT_CKPT,
                    help="federation checkpoint (.npz from launch/train.py); "
                         "the LSTM width is inferred from the param count")
    ap.add_argument("--hidden", type=int, default=None,
                    help="override the inferred LSTM width")
    ap.add_argument("--dataset", default="ohiot1dm",
                    choices=["ohiot1dm", "abc4d", "ctr3", "replace-bg"])
    ap.add_argument("--full-data", action="store_true",
                    help="full-length synthetic series (default is the "
                         "6-day fast twin — CI scale)")
    ap.add_argument("--buckets", default="1,4,16,64",
                    help="comma-separated padded batch-size buckets; the "
                         "forecast method compiles once per bucket")
    ap.add_argument("--max-live-batches", type=int, default=4,
                    help="admission cap: formed-but-unfinished batches")
    ap.add_argument("--flush-timeout-ms", type=float, default=5.0,
                    help="oldest-request wait before a partial batch ships")
    ap.add_argument("--personalize", type=int, default=3,
                    help="how many patients play cold-start arrivals "
                         "(personalized as one batched program; 0 = "
                         "population-only serving)")
    ap.add_argument("--history-windows", type=int, default=24,
                    help="windows of own history each cold-start patient "
                         "brings (small on purpose — newly diagnosed)")
    ap.add_argument("--steps", type=int, default=50,
                    help="fine-tune steps per cold-start patient")
    ap.add_argument("--requests", type=int, default=256,
                    help="synthetic request-stream length")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch-mode", default="map", choices=["map", "vmap"],
                    help="'map' lowers each batch row as the exact "
                         "single-request program (bitwise; the selfcheck "
                         "contract); 'vmap' is the row-parallel "
                         "throughput variant (~1e-8 drift)")
    ap.add_argument("--selfcheck", action="store_true",
                    help="assert every served forecast bitwise-matches "
                         "direct model.apply; exit 1 on mismatch")
    args = ap.parse_args(argv)
    enable_compile_cache()

    buckets = tuple(int(b) for b in args.buckets.split(",") if b)
    model, pop = load_population(args.checkpoint, hidden=args.hidden)
    n_params = int(sum(np.prod(l.shape) for l in jax.tree.leaves(pop)))
    print(f"checkpoint {args.checkpoint}: {n_params} params")

    fed = load_federated_dataset(args.dataset, fast=not args.full_data)
    servable = GlucoseServable(
        model, pop, buckets=buckets, personalize_steps=args.steps,
        batch_mode=args.batch_mode,
    )

    # -- cold-start personalization: the LAST K patients arrive new ----
    if args.personalize:
        k = min(args.personalize, fed.num_nodes)
        cohort = list(range(fed.num_nodes - k, fed.num_nodes))
        m = args.history_windows
        x = np.zeros((k, m, fed.x.shape[-1]), np.float32)
        y = np.zeros((k, m), np.float32)
        counts = np.zeros((k,), np.int32)
        for i, pi in enumerate(cohort):
            p = fed.patients[pi]
            c = min(m, len(p.train_x))
            x[i, :c], y[i, :c], counts[i] = p.train_x[:c], p.train_y[:c], c
        keys = jax.random.split(jax.random.PRNGKey(args.seed), k)
        t0 = time.perf_counter()
        servable.personalize(cohort, keys, x, y, counts)
        dt = time.perf_counter() - t0
        print(f"personalized {k} cold-start patients "
              f"({args.steps} steps on <= {m} windows each) as one "
              f"batched program in {dt:.2f}s")

    # -- serve a synthetic stream through the micro-batcher ------------
    servable.warmup(history_len=fed.x.shape[-1])
    print(f"warmed {len(servable.compiled_buckets)} bucket executables: "
          f"{sorted(servable.compiled_buckets)}")
    batcher = MicroBatcher(
        buckets,
        max_live_batches=args.max_live_batches,
        flush_timeout=args.flush_timeout_ms / 1e3,
    )
    reqs = build_request_stream(fed, servable, args.requests, args.seed)
    from repro.serve import replay

    preds = replay(servable, batcher, reqs)
    stats = batcher.stats()
    print(f"served {stats['completed']} forecasts: "
          f"p50 {stats['p50_latency_ms']:.2f}ms  "
          f"p99 {stats['p99_latency_ms']:.2f}ms  "
          f"{stats['forecasts_per_sec']:.0f} forecasts/sec "
          f"(queue wait {stats['mean_queue_wait_ms']:.2f}ms mean)")
    sample = [round(preds[r] * fed.sd + fed.mean, 1) for r in range(min(4, len(preds)))]
    print(f"first forecasts (mg/dL): {sample}")

    if args.selfcheck:
        bad = 0
        for r in reqs:
            params = jax.tree.map(lambda l: l[r.patient], servable._store)
            direct = float(model.apply(params, jnp.asarray(r.window)[None, :])[0])
            if not (direct == preds[r.rid]):
                bad += 1
                print(f"SELFCHECK MISMATCH rid={r.rid} patient-row={r.patient}: "
                      f"served {preds[r.rid]!r} != direct {direct!r}",
                      file=sys.stderr)
        if bad:
            print(f"selfcheck FAILED: {bad}/{len(reqs)} forecasts drifted "
                  f"from direct model.apply", file=sys.stderr)
            return 1
        print(f"selfcheck: {len(reqs)}/{len(reqs)} served forecasts "
              f"bitwise-match direct model.apply")
    return 0


if __name__ == "__main__":
    sys.exit(main())
