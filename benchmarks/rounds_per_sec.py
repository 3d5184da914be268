"""Training-engine throughput benchmark: rounds/sec of the GluADFL hot
path under its execution strategies.

  * loop          — the per-round Python-loop DEBUG fallback: one jit
                    dispatch and one device->host ``float(loss)`` sync
                    per round;
  * scan          — ``train_chunk``: the whole chunk is ONE ``lax.scan``
                    program with donated FLState buffers, host syncs the
                    stacked losses once per chunk;
  * scan-eval     — scan engine with the in-scan streaming-eval branch
                    armed (``--eval-every``): val RMSE of the population
                    model computed under ``lax.cond`` at boundaries.
                    The claim under test: within ~10% of plain scan;
  * sharded-scan  — scan engine with ``mixer="sharded"`` (allgather
                    impl): the federation axis split over devices,
                    gossip as a real collective (needs >1 device; this
                    script forces an 8-device CPU topology when
                    XLA_FLAGS isn't already set);
  * sharded-psum-scan — same, with ``gossip_impl="psum"``: the
                    memory-scaled reduce-scatter schedule;
  * masked-sharded-scan — sharded-scan with ``gossip_impl="masked"``:
                    the pairwise-masked secure-aggregation wrapper
                    (core/secure_agg.py) on top of the allgather
                    schedule — per-round, per-edge antisymmetric masks
                    generated and cancelled (the mixed result is
                    bitwise the allgather row's, pinned by
                    tests/test_secure_agg.py).  The same-run ratio
                    sharded-scan / masked-sharded-scan prices the
                    masking (``masked_gossip_overhead_vs_allgather`` in
                    the JSON; the gate caps it at ``--masked-ceiling``);
  * serial-sweep   — the Fig-4/Fig-5 ablation shape the sweep engine
                    replaces: G (topology x inactive-ratio) scenarios
                    run one-at-a-time, each config tracing + compiling
                    its OWN scan program then executing it once.  This
                    row (and sweep-scan) is END-TO-END wall clock,
                    compiles included — each scenario runs exactly once
                    in the real workload, so there is no steady state to
                    amortize a compile into.  Rounds/sec counts ALL
                    G x rounds scenario-rounds;
  * sweep-scan    — the same G scenarios as ONE vmapped program
                    (``GluADFL.train_sweep``): stacked adjacency +
                    per-scenario ratios batched over the chunked scan —
                    one compile and one per-chunk host sync for the
                    whole grid.  The claim under test: >= 2x the serial
                    sweep's wall clock at bench scale
                    (``sweep_scan_speedup_vs_serial`` in the JSON);
  * sweep-sharded-psum — the same Fig-5 grid as ONE program on the 2-D
                    ("grid", "node") sweep mesh (``launch.mesh.
                    make_sweep_mesh``): scenarios batch over the grid
                    axis while the psum gossip collectives stay scoped
                    to the node axis — the memory-scaled sweep schedule
                    (per-device state O(G/grid · N/node · D)).  Like the
                    other sweep rows this is END-TO-END wall clock,
                    compile included, and on CPU it prices collective
                    overhead rather than a speedup — the row exists so
                    the schedule's cost stays measured and its presence
                    gated;
  * dense-gossip-n226 / sparse-gossip-n226 — the paper-scale federation
                    (N=226, REPLACE-BG) under the dense (N, N)
                    ``mixing_matrix`` representation vs the O(N·B)
                    neighbor-table one (``gossip_repr="sparse"``),
                    steady-state scan engine, everything else identical.
                    The claim under test: the sparse representation is
                    never slower at paper scale
                    (``sparse_gossip_speedup_vs_dense`` in the JSON);
  * sparse-gossip-10k — the row the dense representation CANNOT run: a
                    10 000-node ring federation, where the dense path
                    would materialize a 10k x 10k f32 matrix (400 MB)
                    per round while the neighbor table holds 10k x 3
                    entries.  Sparse-only END-TO-END wall clock (compile
                    included — population scale runs once, like the
                    sweep rows); the gate checks presence, not a ratio;
  * sparse-gossip-100k — the row even the sparse ALLGATHER schedule
                    cannot run forever: a 100 000-node ring federation
                    under ``gossip_impl="gather"`` (backend
                    ``sharded_gather_tables``), where the neighbor
                    tables AND node rows stay sharded over the node
                    mesh axis and the local row block ring-rotates via
                    ``ppermute`` — no device ever materializes the
                    gathered (N, D) federation.  END-TO-END wall clock
                    like the 10k row, presence-gated; the JSON also
                    records the analytic per-device mixing memory of
                    the allgather schedule vs the gather tables
                    (``gather_table_memory_bytes``), which is the
                    number this schedule exists to shrink;
  * table4-serial-loops / table4-batched — the Table-4 trainable-
                    baseline grid (FedAvg, MAML, MetaSGD, supervised
                    LSTM — the same four configs
                    ``benchmarks/table4_baselines.py::run_baseline_grid``
                    trains) with every method's round budget dispatched
                    per-round (``engine="loop"``: one jit dispatch + one
                    ``float(loss)`` sync per round per method) vs as ONE
                    donated chunk per method (``engine="scan"``,
                    ``chunk=rounds`` — <= 4 compiled executions for the
                    whole grid).  Warm steady state (trainers built
                    once, compiles excluded via warmup) so the same-run
                    ratio ``table4_batched_speedup_vs_serial`` isolates
                    the per-round dispatch+sync overhead the batched
                    grid removes; the gate floors it at
                    ``--table4-floor`` (default 1.5);
  * multihost-psum-scan — OPTIONAL (``--processes P``, P >= 2): the same
                    psum schedule but with the node axis spanning P REAL
                    ``jax.distributed`` processes over localhost TCP
                    (each forced to 8/P CPU devices) — this row prices
                    the cross-process hop.  Spawned as subprocesses of
                    this script; absent from the committed baseline, so
                    the regression gate ignores it.

Usage:
    PYTHONPATH=src python benchmarks/rounds_per_sec.py \
        [--nodes 32] [--rounds 64] [--hidden 16] [--batch 16] \
        [--chunk 32] [--eval-every 8] [--processes 2]

Writes experiments/paper/rounds_per_sec.json (the bench-regression gate
compares this against the committed BENCH_rounds_per_sec.json baseline —
see benchmarks/check_bench_regression.py) and prints one CSV line per
engine: ``engine,rounds_per_sec,speedup_vs_loop``.
"""
from __future__ import annotations

import os

# must precede the first jax import; harmless if the caller already set it
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time
from pathlib import Path

import numpy as np


def synth_federation(n: int, m: int, hist_len: int, seed: int = 0):
    """Linear teacher federation — enough signal that losses stay finite,
    small enough that round time is dominated by engine overhead (the
    quantity under test), not model FLOPs."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, m, hist_len)).astype(np.float32)
    w = rng.normal(size=(hist_len,)).astype(np.float32)
    y = (x @ w + 0.01 * rng.normal(size=(n, m))).astype(np.float32)
    return x, y, np.full((n,), m, np.int32)


def bench_engine(trainer, x, y, counts, *, rounds: int, batch_size: int,
                 chunk: int, engine: str, eval_every: int = 0,
                 val_data=None, reps: int = 3) -> float:
    """Returns steady-state rounds/sec: best of ``reps`` timed runs
    (compile excluded via warmup; best-of defends against noisy shared
    CPUs — the engines' ordering, not absolute numbers, is the claim)."""
    import jax
    import jax.numpy as jnp

    x, y = jnp.asarray(x), jnp.asarray(y)
    counts = jnp.asarray(counts)
    eval_kw = {}
    if eval_every and val_data is not None:
        eval_kw = dict(
            val_x=jnp.asarray(val_data[0]), val_y=jnp.asarray(val_data[1]),
            eval_every=eval_every, eval_fn=trainer._resolve_eval_fn(None),
        )

    def run(state):
        if engine == "loop":
            for _ in range(rounds):
                state, loss = trainer._round_jit(
                    state, x, y, counts, batch_size=batch_size
                )
                float(loss)  # the per-round host sync the loop engine pays
        else:
            t = 0
            while t < rounds:
                c = min(chunk, rounds - t)
                state, aux = trainer.train_chunk(
                    state, x, y, counts, batch_size=batch_size, chunk=c,
                    **eval_kw,
                )
                # one sync per chunk (losses, plus eval records if armed)
                jax.tree.map(np.asarray, aux)
                t += c
        jax.block_until_ready(state.params)

    def fresh_state(seed):
        # outside the timed region: init cost is not a property of the
        # engine (a new state per run is still required — train_chunk
        # donates its input buffers)
        state = trainer.init(jax.random.PRNGKey(seed), x[0, :1])
        jax.block_until_ready(state.params)
        return state

    run(fresh_state(0))  # warmup: compile every chunk shape
    best = 0.0
    for rep in range(reps):
        state = fresh_state(1 + rep)
        t0 = time.perf_counter()
        run(state)
        best = max(best, rounds / (time.perf_counter() - t0))
    return best


# the sweep-row scenario grid IS the paper's Fig-5 grid (3 topologies x
# 5 inactive ratios, seed 0 = 15 scenarios — exactly the workload the
# sweep engine was built to batch), sourced from its canonical home in
# config.SweepConfig.  Safe to import here: config pulls no jax, so the
# XLA_FLAGS line above still precedes the first jax import.
from repro.config import SweepConfig

SWEEP_TOPOLOGIES = SweepConfig().topologies
SWEEP_RATIOS = SweepConfig().inactive_ratios


def bench_sweep(make_trainer, x, y, counts, *, nodes: int, rounds: int,
                batch_size: int, chunk: int, reps: int = 3) -> tuple[float, float]:
    """End-to-end wall clock of the ablation grid, both ways; returns
    ``(serial_rps, sweep_rps)`` in scenario-rounds/sec (G x rounds per
    timed run).

    Unlike the steady-state engine rows above, compile time is PART of
    this measurement on purpose: it reproduces how the figure benchmarks
    actually execute the grid — every scenario config runs exactly once,
    so there is no steady state to amortize a compile into.  The serial
    path re-traces per config (the topology string and inactive ratio
    are baked into each trainer's trace), paying G compiles; the batched
    path traces the vmapped program once.  Removing those G-1 compiles
    (plus batching the execution) is precisely what the sweep engine is
    for, so the row prices it."""
    import dataclasses

    import jax

    from repro.core import SweepGrid

    grid = SweepGrid.build(SWEEP_TOPOLOGIES, SWEEP_RATIOS, (0,), num_nodes=nodes)
    g = grid.size

    def run_serial():
        # fresh trainer per scenario, exactly like the pre-sweep
        # fig4/fig5 loops: each config compiles its own chunk program
        for topo, ratio, seed in grid.labels:
            tr = make_trainer("tree")
            tr.cfg = dataclasses.replace(
                tr.cfg, topology=topo, inactive_ratio=ratio
            )
            tr.train(jax.random.PRNGKey(seed), x, y, counts,
                     batch_size=batch_size, rounds=rounds, chunk=chunk)

    def run_sweep():
        tr = make_trainer("tree")
        tr.train_sweep(x, y, counts, grid=grid, batch_size=batch_size,
                       rounds=rounds, chunk=chunk)

    serial_best = sweep_best = 0.0
    for _ in range(reps):  # fresh trainers each rep -> compiles recur
        t0 = time.perf_counter()
        run_serial()
        serial_best = max(serial_best, g * rounds / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        run_sweep()
        sweep_best = max(sweep_best, g * rounds / (time.perf_counter() - t0))
    return serial_best, sweep_best


def bench_sweep_sharded(make_trainer, x, y, counts, *, nodes: int, rounds: int,
                        batch_size: int, chunk: int, reps: int = 3) -> float:
    """End-to-end wall clock of the same Fig-5 grid on the 2-D
    (grid, node) sweep mesh with the memory-scaled psum schedule —
    scenario-rounds/sec, compile included (same measurement contract as
    :func:`bench_sweep`: the grid runs exactly once in the real
    workload).  On CPU the collectives cost more than the batched
    einsum they replace; what this row buys is per-device memory
    O(G/grid · N/node · D) — the committed number prices that trade."""
    import jax

    from repro.core import SweepGrid

    grid = SweepGrid.build(SWEEP_TOPOLOGIES, SWEEP_RATIOS, (0,), num_nodes=nodes)

    def run():
        tr = make_trainer("sharded", "psum")
        tr.train_sweep(x, y, counts, grid=grid, batch_size=batch_size,
                       rounds=rounds, chunk=chunk)

    best = 0.0
    for _ in range(reps):  # fresh trainer each rep -> the compile recurs
        t0 = time.perf_counter()
        run()
        best = max(best, grid.size * rounds / (time.perf_counter() - t0))
    return best


def bench_sparse_gossip(args) -> dict:
    """The sparse-representation family: dense vs sparse at the paper's
    N=226, plus the 10k-node row only the sparse path can run.

    The N=226 pair shares one federation, model, and config — the ONLY
    difference is ``gossip_repr`` — so the ratio isolates the mixing
    representation.  The 10k row is end-to-end (compile included):
    population-scale federations run once, and its point is existence —
    the dense twin would build a 400 MB (10k, 10k) f32 matrix every
    round."""
    import jax

    from repro.config import FLConfig
    from repro.core import GluADFL
    from repro.models import LSTMModel
    from repro.optim import sgd

    n = args.sparse_nodes
    rounds = args.sparse_rounds
    cfg = FLConfig(topology="ring", num_nodes=n, rounds=rounds,
                   comm_batch=7, inactive_ratio=0.3)
    x, y, counts = synth_federation(n, 4, 12, seed=2)

    # hidden=32 (not the engine rows' 16): the parameter dimension must
    # be large enough that the O(N^2 · D) dense contraction is a real
    # share of the round, or the ratio just measures scheduler noise
    out = {}
    for name, repr_ in (("dense-gossip-n226", "dense"),
                        ("sparse-gossip-n226", "sparse")):
        tr = GluADFL(LSTMModel(hidden=args.sparse_hidden).as_model(),
                     sgd(1e-2), cfg, gossip_repr=repr_)
        out[name] = bench_engine(tr, x, y, counts, rounds=rounds,
                                 batch_size=4, chunk=rounds, engine="scan")

    nb = args.sparse_big_nodes
    if nb:
        cfg_big = FLConfig(topology="ring", num_nodes=nb, rounds=2,
                           comm_batch=7, inactive_ratio=0.2)
        xb, yb, cb = synth_federation(nb, 2, 12, seed=3)

        def run_big():
            tr = GluADFL(LSTMModel(hidden=4).as_model(), sgd(1e-2), cfg_big,
                         gossip_repr="sparse")
            tr.train(jax.random.PRNGKey(0), xb, yb, cb, batch_size=2,
                     rounds=2, chunk=2)

        t0 = time.perf_counter()
        run_big()
        out["sparse-gossip-10k"] = 2 / (time.perf_counter() - t0)

    nh = args.sparse_huge_nodes
    if nh:
        from repro.core.distributed import _default_federation_mesh

        cfg_huge = FLConfig(topology="ring", num_nodes=nh, rounds=2,
                            comm_batch=7, inactive_ratio=0.2)
        xh, yh, ch = synth_federation(nh, 2, 12, seed=4)
        model = LSTMModel(hidden=4).as_model()

        def run_huge():
            tr = GluADFL(model, sgd(1e-2), cfg_huge, mixer="sharded",
                         gossip_impl="gather", gossip_repr="sparse")
            tr.train(jax.random.PRNGKey(0), xh, yh, ch, batch_size=2,
                     rounds=2, chunk=2)

        t0 = time.perf_counter()
        run_huge()
        out["sparse-gossip-100k"] = 2 / (time.perf_counter() - t0)

        # the number the gather-table schedule exists to shrink: analytic
        # per-device bytes the MIXING step must hold resident.  allgather
        # materializes the full (N, D) federation on every device; the
        # gather tables keep the local (N/shards, D) block plus one
        # ring-rotating block of the same size in flight
        p0 = model.init(jax.random.PRNGKey(0))
        node_bytes = sum(
            l.size * l.dtype.itemsize for l in jax.tree.leaves(p0)
        )
        shards = _default_federation_mesh(nh).shape["node"]
        out["gather-table-memory"] = {
            "num_nodes": nh,
            "node_shards": shards,
            "param_bytes_per_node": node_bytes,
            "allgather_gathered_bytes_per_device": nh * node_bytes,
            "gather_table_bytes_per_device":
                2 * (nh // shards) * node_bytes,
        }
    return out


def bench_table4(args) -> dict:
    """The Table-4 trainable-baseline grid, serial per-round loops vs the
    chunked scan engines — the same four method configs
    ``run_baseline_grid`` trains, on the same federation.

    The trainers are built ONCE and each engine gets a warmup pass
    before timing (compiles excluded): a fresh-trainer end-to-end
    measurement is compile-dominated at any practical round budget
    (every method re-traces per construction), which would price XLA's
    compiler instead of the engines.  What the batched grid actually
    removes is the per-round dispatch + ``float(loss)`` host sync paid
    ``4 x rounds`` times by the loops — the warm ratio isolates exactly
    that.  Rows are method-rounds/sec (``4 x rounds`` per grid pass,
    best of ``--table4-reps``)."""
    import sys as _sys

    root = str(Path(__file__).resolve().parents[1])
    if root not in _sys.path:
        _sys.path.insert(0, root)
    import jax

    from benchmarks.common import Scale, load
    from repro.config import FLConfig
    from repro.core import FedAvg, MAML, MetaSGD, train_supervised
    from repro.models import LSTMModel
    from repro.optim import adam

    rounds = args.table4_rounds
    scale = Scale(fast=True, rounds=rounds, sup_steps=rounds,
                  max_patients=args.table4_patients,
                  hidden=args.table4_hidden, batch_size=args.table4_batch)
    fed = load(args.table4_dataset, scale)
    pooled_x = np.concatenate([p.train_x for p in fed.patients])
    pooled_y = np.concatenate([p.train_y for p in fed.patients])

    # the same four constructions as run_baseline_grid, built once so
    # both engines hit warm jit caches
    model = LSTMModel(hidden=scale.hidden).as_model()
    fa = FedAvg(model, adam(2e-3),
                FLConfig(num_nodes=fed.num_nodes, rounds=rounds,
                         local_steps=2, seed=0))
    metas = {"maml": MAML(model, adam(1e-3), inner_lr=1e-2, inner_steps=3),
             "metasgd": MetaSGD(model, adam(1e-3), inner_lr=1e-2,
                                inner_steps=3)}
    # one optimizer instance across passes: train_supervised's jit cache
    # is keyed on it, and each adam() call is a distinct (unequal) object
    sup_opt = adam(2e-3)

    def grid_pass(engine):
        fa.train(jax.random.PRNGKey(0), fed.x, fed.y, fed.counts,
                 batch_size=scale.batch_size, engine=engine, chunk=rounds)
        for meta in metas.values():
            meta.train(jax.random.PRNGKey(0), fed.x, fed.y, fed.counts,
                       batch_size=scale.batch_size, steps=rounds,
                       engine=engine, chunk=rounds)
        train_supervised(model, sup_opt, jax.random.PRNGKey(0),
                         pooled_x, pooled_y, steps=rounds,
                         batch_size=scale.batch_size, engine=engine,
                         chunk=rounds)

    out = {}
    for name, engine in (("table4-serial-loops", "loop"),
                         ("table4-batched", "scan")):
        grid_pass(engine)  # warmup: compile every method's program
        best = 0.0
        for _ in range(args.table4_reps):
            t0 = time.perf_counter()
            grid_pass(engine)
            best = max(best, 4 * rounds / (time.perf_counter() - t0))
        out[name] = best
    return out


def _bench_multihost_worker(args) -> None:
    """One process of the multihost row: join the localhost cluster,
    place this host's node rows, and time the psum scan engine.  Only
    process 0 prints the machine-readable MULTIHOST_RPS line."""
    from repro.launch import multihost

    multihost.initialize(
        f"127.0.0.1:{args.port}", args.processes, args.multihost_worker
    )
    import jax

    from repro.config import FLConfig
    from repro.core import GluADFL
    from repro.core.distributed import _default_federation_mesh
    from repro.models import LSTMModel
    from repro.optim import sgd

    cfg = FLConfig(topology=args.topology, num_nodes=args.nodes,
                   rounds=args.rounds, comm_batch=7)
    trainer = GluADFL(LSTMModel(hidden=args.hidden).as_model(), sgd(1e-2),
                      cfg, mixer="sharded", gossip_impl="psum")
    mesh = _default_federation_mesh(args.nodes)
    x, y, counts = synth_federation(args.nodes, args.windows, 12)
    gx, gy, gc, _ = multihost.place_federation(mesh, x, y, counts)

    def fresh_state(seed):
        # outside the timed region, like bench_engine: init cost is not
        # a property of the engine (train_chunk donates its input)
        state = trainer.init_sharded(jax.random.PRNGKey(seed), mesh)
        jax.block_until_ready(state.params)
        return state

    def run(state):
        t = 0
        while t < args.rounds:
            c = min(args.chunk, args.rounds - t)
            state, losses = trainer.train_chunk(
                state, gx, gy, gc, batch_size=args.batch, chunk=c
            )
            multihost.fetch_replicated(losses)  # the per-chunk host sync
            t += c
        jax.block_until_ready(state.params)

    run(fresh_state(0))  # warmup: compile every chunk shape
    best = 0.0
    for rep in range(3):
        state = fresh_state(1 + rep)
        multihost.barrier(f"bench_rep_{rep}")  # start reps in lockstep
        t0 = time.perf_counter()
        run(state)
        best = max(best, args.rounds / (time.perf_counter() - t0))
    if multihost.is_primary():
        print(f"MULTIHOST_RPS {best:.6f}", flush=True)
    multihost.barrier("bench_done")


def _bench_multihost(args) -> float:
    """Spawn the P-process cluster (8/P forced CPU devices each) running
    THIS script in worker mode; return process 0's rounds/sec."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    # split the PARENT's forced CPU device pool (read from XLA_FLAGS, not
    # from jax) across the workers so this row benches the same global
    # device count as the in-process rows it is read against
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "").split()
    forced = [f for f in flags
              if f.startswith("--xla_force_host_platform_device_count=")]
    pool = int(forced[-1].split("=", 1)[1]) if forced else 1
    devices = max(1, pool // args.processes)
    kept = [f for f in flags if f not in forced]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={devices}"]
    )
    procs = [
        subprocess.Popen(
            [sys.executable, __file__,
             "--multihost-worker", str(i), "--processes", str(args.processes),
             "--port", str(port), "--nodes", str(args.nodes),
             "--rounds", str(args.rounds), "--windows", str(args.windows),
             "--hidden", str(args.hidden), "--batch", str(args.batch),
             "--chunk", str(args.chunk), "--topology", args.topology],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for i in range(args.processes)
    ]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=1200))
    finally:
        # a crashed worker leaves its siblings blocked at the
        # distributed barrier — never orphan them
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, (out, err) in zip(procs, outs):
        if p.returncode != 0:
            raise RuntimeError(f"multihost bench worker failed:\n{err[-3000:]}")
    for line in outs[0][0].splitlines():
        if line.startswith("MULTIHOST_RPS "):
            return float(line.split()[1])
    raise RuntimeError(f"no MULTIHOST_RPS line:\n{outs[0][0][-2000:]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=64)
    ap.add_argument("--windows", type=int, default=64, help="samples per node")
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--eval-every", type=int, default=8,
                    help="streaming-eval cadence for the scan-eval row "
                         "(0 disables the row)")
    ap.add_argument("--topology", default="random")
    ap.add_argument("--sparse-nodes", type=int, default=226,
                    help="federation size for the dense-vs-sparse "
                         "gossip-representation pair (paper scale)")
    ap.add_argument("--sparse-rounds", type=int, default=8,
                    help="steady-state rounds for the representation pair")
    ap.add_argument("--sparse-hidden", type=int, default=32,
                    help="model width for the representation pair (large "
                         "enough that mixing is a real share of the round)")
    ap.add_argument("--sparse-big-nodes", type=int, default=10000,
                    help="node count for the sparse-only scaling row "
                         "(0 skips it)")
    ap.add_argument("--sparse-huge-nodes", type=int, default=100000,
                    help="node count for the sharded gather-table row "
                         "(gossip_impl='gather'; 0 skips it)")
    ap.add_argument("--table4-rounds", type=int, default=128,
                    help="rounds/steps per method for the Table-4 "
                         "baseline-grid pair (0 skips both rows)")
    ap.add_argument("--table4-hidden", type=int, default=8,
                    help="model width for the Table-4 grid pair")
    ap.add_argument("--table4-patients", type=int, default=4,
                    help="patients (fast synth cohort) for the grid pair")
    ap.add_argument("--table4-batch", type=int, default=8,
                    help="batch size for the grid pair")
    ap.add_argument("--table4-dataset", default="ohiot1dm",
                    help="dataset for the grid pair (fast synth cohort)")
    ap.add_argument("--table4-reps", type=int, default=3,
                    help="timed grid passes per engine (best-of, filters "
                         "scheduler spikes on busy CI runners)")
    ap.add_argument("--processes", type=int, default=0,
                    help="add the multihost-psum-scan row: split the node "
                         "axis over this many REAL jax.distributed "
                         "processes (0 = skip the row)")
    ap.add_argument("--multihost-worker", type=int, default=None,
                    help=argparse.SUPPRESS)  # internal: worker process id
    ap.add_argument("--port", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.multihost_worker is not None:
        _bench_multihost_worker(args)
        return None

    import jax

    from repro.config import FLConfig
    from repro.core import GluADFL
    from repro.models import LSTMModel
    from repro.optim import sgd

    if args.processes and jax.default_backend() != "cpu":
        # this process already holds the device; a child that needs it
        # would fail or hang
        raise SystemExit("--processes spawns worker processes that each "
                         "need the device: it runs on the CPU backend only")
    print(f"devices={len(jax.devices())} nodes={args.nodes} rounds={args.rounds} "
          f"chunk={args.chunk} hidden={args.hidden} eval_every={args.eval_every}")

    cfg = FLConfig(topology=args.topology, num_nodes=args.nodes,
                   rounds=args.rounds, comm_batch=7)
    x, y, counts = synth_federation(args.nodes, args.windows, 12)
    rng = np.random.default_rng(1)
    val_x = rng.normal(size=(128, 12)).astype(np.float32)
    val_y = rng.normal(size=(128,)).astype(np.float32)

    def make(mixer, gossip_impl="allgather"):
        return GluADFL(LSTMModel(hidden=args.hidden).as_model(), sgd(1e-2),
                       cfg, mixer=mixer, gossip_impl=gossip_impl)

    cases = [
        ("loop", "tree", "allgather", "loop", 0),
        ("scan", "tree", "allgather", "scan", 0),
        ("sharded-scan", "sharded", "allgather", "scan", 0),
        ("sharded-psum-scan", "sharded", "psum", "scan", 0),
        ("masked-sharded-scan", "sharded", "masked", "scan", 0),
    ]
    if args.eval_every:
        cases.insert(2, ("scan-eval", "tree", "allgather", "scan", args.eval_every))

    results = {}
    for name, mixer, impl, engine, eval_every in cases:
        rps = bench_engine(make(mixer, impl), x, y, counts, rounds=args.rounds,
                           batch_size=args.batch, chunk=args.chunk,
                           engine=engine, eval_every=eval_every,
                           val_data=(val_x, val_y))
        results[name] = rps

    # the scenario-sweep rows: G ablation configs serial vs one vmapped
    # program (rounds/sec here counts scenario-rounds, G x rounds per run)
    serial_rps, sweep_rps = bench_sweep(
        make, x, y, counts, nodes=args.nodes, rounds=args.rounds,
        batch_size=args.batch, chunk=args.chunk,
    )
    results["serial-sweep"] = serial_rps
    results["sweep-scan"] = sweep_rps
    results["sweep-sharded-psum"] = bench_sweep_sharded(
        make, x, y, counts, nodes=args.nodes, rounds=args.rounds,
        batch_size=args.batch, chunk=args.chunk,
    )

    sparse_rows = bench_sparse_gossip(args)
    gather_memory = sparse_rows.pop("gather-table-memory", None)
    results.update(sparse_rows)

    if args.table4_rounds:
        results.update(bench_table4(args))

    if args.processes and args.processes >= 2:
        results["multihost-psum-scan"] = _bench_multihost(args)

    out = {"config": vars(args), "devices": len(jax.devices()),
           "rounds_per_sec": results,
           "scan_speedup_vs_loop": results["scan"] / results["loop"],
           # batching the ablation grid must beat running it serially:
           # acceptance target >= 2x at bench scale
           "sweep_scan_speedup_vs_serial": sweep_rps / serial_rps,
           # the O(N·B) representation must never lose to the (N, N)
           # matrix at paper scale: acceptance target >= the gate's
           # --sparse-floor (1.0 nominal, 0.9 gated for CPU noise)
           "sparse_gossip_speedup_vs_dense":
               results["sparse-gossip-n226"] / results["dense-gossip-n226"],
           # what masking costs, measured in the SAME process against the
           # allgather row it is bitwise-equal to: >1 = slower.  The gate
           # caps this at --masked-ceiling so mask generation can never
           # silently blow up the round
           "masked_gossip_overhead_vs_allgather":
               results["sharded-scan"] / results["masked-sharded-scan"]}
    if "scan-eval" in results:
        # streaming-eval overhead: 1.0 = free, acceptance target >= 0.9
        out["scan_eval_relative_throughput"] = results["scan-eval"] / results["scan"]
    if gather_memory is not None:
        # per-device mixing memory, analytic: what the gather-table
        # schedule buys over allgather at the 100k row's scale
        out["gather_table_memory_bytes"] = gather_memory
    if "table4-batched" in results:
        # the compiled baseline grid vs the per-round loops it demoted,
        # warm steady state: acceptance target >= the gate's
        # --table4-floor (1.5)
        out["table4_batched_speedup_vs_serial"] = (
            results["table4-batched"] / results["table4-serial-loops"])
    out_dir = Path(__file__).resolve().parents[1] / "experiments" / "paper"
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "rounds_per_sec.json").write_text(json.dumps(out, indent=2))

    for name, rps in results.items():
        print(f"{name},{rps:.2f},{rps / results['loop']:.2f}x")
    if "scan_eval_relative_throughput" in out:
        print(f"scan-eval relative throughput: "
              f"{out['scan_eval_relative_throughput']:.3f} (target >= 0.9)")
    print(f"sweep-scan speedup vs serial sweep: "
          f"{out['sweep_scan_speedup_vs_serial']:.2f}x (target >= 2)")
    print(f"sparse gossip speedup vs dense @ N={args.sparse_nodes}: "
          f"{out['sparse_gossip_speedup_vs_dense']:.2f}x (target >= 1)")
    print(f"masked gossip overhead vs allgather: "
          f"{out['masked_gossip_overhead_vs_allgather']:.2f}x (ceiling <= 4)")
    if gather_memory is not None:
        m = gather_memory
        print(f"gather-table per-device mixing memory @ N={m['num_nodes']}: "
              f"{m['gather_table_bytes_per_device'] / 2**20:.1f} MiB vs "
              f"allgather {m['allgather_gathered_bytes_per_device'] / 2**20:.1f} "
              f"MiB ({m['node_shards']} shards)")
    if "table4_batched_speedup_vs_serial" in out:
        print(f"table4 batched grid vs serial loops: "
              f"{out['table4_batched_speedup_vs_serial']:.2f}x (floor 1.5)")
    return out


if __name__ == "__main__":
    main()
