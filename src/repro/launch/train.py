"""Federated training launcher — the paper's experiment, end to end.

    PYTHONPATH=src python -m repro.launch.train \
        --dataset ohiot1dm --topology random --rounds 200 \
        [--eval-every 8] [--mixer sharded --gossip-impl psum] \
        [fl.comm_batch=7 train.lr=1e-3 ...]

Loads the synthetic-twin dataset, runs GluADFL, reports clinical metrics
of the population model per patient + aggregate, and writes a checkpoint
(.npz of the population params).

Engine selection
----------------
The compiled ``lax.scan`` chunk engine is the ONE production path — it
runs every configuration, including streaming eval:

  * default              — scan engine, ``--chunk`` rounds per compiled
                           program (one host sync per chunk);
  * ``--eval-every K``   — STAYS on the scan engine: val RMSE of the
                           population model is computed INSIDE the
                           scanned round body (lax.cond on
                           ``round % K``) against a pre-batched
                           validation set, so eval costs no per-round
                           host sync.  Records land in the history at
                           each boundary;
  * ``--engine loop`` or ``--chunk 0``
                         — explicit per-round Python-loop DEBUG
                           fallback (host callback eval, pdb between
                           rounds).  Never selected automatically.

Scenario sweeps (``--sweep-ratios`` / ``--sweep-seeds``)
--------------------------------------------------------
``--sweep-ratios 0,0.3,0.7 --sweep-seeds 3`` trains the whole
(ratio x seed) grid of the chosen topology as ONE batched device
program (``GluADFL.train_sweep``): per-scenario inactive ratios and
seed keys are vmapped over the compiled chunk scan, so the grid costs
one compile per chunk shape instead of G serial runs.  Streaming eval
(``--eval-every``) stays in-scan and returns a (grid, chunk) record
stack.  With ``--mixer sharded`` the grid becomes a real mesh axis: the
stacked (G, N, ...) state is placed on a 2-D ("grid", "node") mesh
(``launch.mesh.make_sweep_mesh``) where scenarios batch over "grid"
and the gossip collectives (``--gossip-impl allgather|psum|auto``)
stay scoped to "node" — the memory-scaled way to sweep paper-scale
federations.  ``--sweep-schedules bernoulli,markov``,
``--sweep-skews 0,0.5,1`` and ``--sweep-dp-sigmas 0,0.01,0.05`` extend
the cross product with the Markov-sticky staleness, non-IID data-skew
and DP-noise-level axes (each a traced ``(G,)`` array; every scenario
keeps exact serial key-stream parity).  Sweeps are single-process and
scan-engine only
(``--mixer kernel``/``--use-kernel``, ``--engine loop`` and multi-host
flags refuse); instead of a checkpoint, the launcher writes a
per-scenario summary JSON to ``--out``.

Gossip impl
-----------
  * ``--gossip-impl allgather`` (default) — gather the federation's node
    axis per device and contract locally: fastest on ICI while the
    gathered (N, D) block fits per-device memory (``--mixer sharded``;
    ignored by tree/kernel);
  * ``--gossip-impl psum``      — psum-of-local-contributions
    (reduce-scatter): per-device memory O(N/shards · D), the multi-host
    / big-model schedule (``--mixer sharded`` only);
  * ``--gossip-impl masked``    — pairwise-masked secure aggregation
    (``core.secure_agg``): per-round per-edge PRNG masks whose weighted
    sum cancels exactly, so neighbors never see raw parameters and the
    trained state is BITWISE the unmasked run's.  Composes with every
    mixer and representation (sharded rides the allgather schedule);
  * ``--gossip-impl auto``      — pick by the per-device memory the
    gathered federation would need (``launch.mesh.choose_gossip_impl``).

Gossip representation (any mixer)
---------------------------------
``--gossip-repr dense|sparse|auto`` picks the mixing operator's storage:
the dense (N, N) ``mixing_matrix`` or the (N, B+1) neighbor table
(O(N·B·D) contraction, no (N, N) array for static topologies — the
population-scale path).  ``auto`` (default) defers to
``launch.mesh.choose_gossip_repr``: the tree mixer with the node axis
on one device stays dense below its dense/sparse crossover (dense at
the paper's N=226, which reads each node's parameters once where the
table copies them B+1 times); across devices, and for the other
mixers, sparse once N >= 4·(B+1).

Multi-host bootstrap (``--num-processes > 1``)
----------------------------------------------
Launch the SAME command on every host, varying only the process id::

    REPRO_COORDINATOR=host0:12345 REPRO_NUM_PROCESSES=4 \
    REPRO_PROCESS_ID=$RANK PYTHONPATH=src python -m repro.launch.train \
        --dataset replace-bg --mixer sharded --gossip-impl psum ...

(or the equivalent ``--coordinator/--num-processes/--process-id`` flags;
flags win over the environment).  Process/data-placement rules:

  * ``launch.multihost.initialize`` joins the ``jax.distributed``
    cluster FIRST — before any device query — and on CPU selects the
    gloo cross-process collectives.  The local device count is whatever
    the backend exposes (force with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` for CPU runs);
  * the node-axis mesh is GLOBAL (``launch.mesh.make_federation_mesh``
    prefers widths giving every process the same whole number of
    shards), so the gossip collective spans hosts;
  * every process loads the same deterministic dataset host-side but
    materializes ON DEVICE only its own node rows
    (``launch.multihost.place_federation``); the validation set is
    replicated;
  * multi-host implies ``--mixer sharded`` (auto-selected with a note if
    the flag disagrees) and the scan engine (``--engine loop`` refuses);
  * per-patient clinical metrics + the checkpoint are gathered to and
    written by PROCESS 0 only; all processes join a final barrier so the
    cluster tears down cleanly.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ExperimentConfig, apply_overrides
from repro.core import GluADFL
from repro.data import load_federated_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.metrics import all_metrics
from repro.models import LSTMModel
from repro.optim import get_optimizer
from repro.utils.pytree import tree_to_vector, vector_to_tree


def _patient_predictions(model, pop, fed):
    """Yield ``(patient, mg/dL predictions)`` of a population model over
    each patient's test split — shared by the single-run and sweep
    summaries."""
    for p in fed.patients:
        pred = np.asarray(
            model.apply(pop, jnp.asarray(p.test_x))
        ) * fed.sd + fed.mean
        yield p, pred


def save_checkpoint(path: Path, params) -> None:
    vec = np.asarray(tree_to_vector(params))
    leaves, treedef = jax.tree.flatten(params)
    meta = [(str(i), list(l.shape), str(l.dtype)) for i, l in enumerate(leaves)]
    np.savez(path, vec=vec, meta=json.dumps(meta))


def load_checkpoint(path: Path, like):
    data = np.load(path, allow_pickle=False)
    return vector_to_tree(jnp.asarray(data["vec"]), like)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="ohiot1dm",
                    choices=["ohiot1dm", "abc4d", "ctr3", "replace-bg"])
    ap.add_argument("--topology", default="random",
                    choices=["ring", "cluster", "random", "star", "full"])
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--inactive-ratio", type=float, default=0.0)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--fast-data", action="store_true",
                    help="6-day synthetic series (CI scale)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="DEPRECATED: pass --mixer kernel instead (this "
                         "flag maps through, with a DeprecationWarning; it "
                         "also still selects the Pallas LSTM-cell kernel)")
    ap.add_argument("--mixer", default=None, choices=["tree", "kernel", "sharded"],
                    help="gossip mixer: tree (einsum), kernel (Pallas), "
                         "sharded (node-sharded mesh collective); default "
                         "tree")
    ap.add_argument("--chunk", type=int, default=None,
                    help="rounds per compiled lax.scan chunk (host syncs "
                         "once per chunk); 0 = per-round python loop; "
                         "default: gluadfl.DEFAULT_CHUNK")
    ap.add_argument("--engine", default="scan", choices=["scan", "loop"],
                    help="scan (default; the production path, incl. "
                         "streaming eval) or loop (per-round debug "
                         "fallback; also selected by --chunk 0)")
    ap.add_argument("--sweep-ratios", default=None,
                    help="comma-separated inactive ratios, e.g. "
                         "'0,0.3,0.7': train the whole (ratio x seed) "
                         "grid of --topology as ONE batched program "
                         "(GluADFL.train_sweep) instead of a single run")
    ap.add_argument("--sweep-seeds", type=int, default=1,
                    help="seeds per sweep scenario (0..K-1); only with "
                         "--sweep-ratios")
    ap.add_argument("--sweep-schedules", default=None,
                    help="comma-separated participation schedules to "
                         "sweep, from {bernoulli, markov}: adds the "
                         "Markov-sticky staleness axis to the grid; only "
                         "with --sweep-ratios")
    ap.add_argument("--sweep-skews", default=None,
                    help="comma-separated non-IID data-skew strengths, "
                         "e.g. '0,0.5,1': node i trains on batches "
                         "shifted by skew*node_skew_offsets(N)[i]; only "
                         "with --sweep-ratios")
    ap.add_argument("--sweep-dp-sigmas", default=None,
                    help="comma-separated local-DP gossip noise sigmas "
                         "swept as a traced axis, e.g. '0,0.01,0.05'; "
                         "only with --sweep-ratios")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="compute population val-RMSE every K rounds "
                         "INSIDE the scanned chunk (0 = off); no "
                         "per-round host sync")
    ap.add_argument("--gossip-impl", default="allgather",
                    choices=["allgather", "psum", "masked", "gather", "auto"],
                    help="gossip schedule: allgather (per-device O(N*D) "
                         "gather), psum (reduce-scatter, per-device "
                         "O(N/shards*D)), masked (pairwise-masked secure "
                         "aggregation — any mixer; bitwise the allgather "
                         "result), gather (sharded gather tables: ppermute "
                         "halo rotation, per-device O(N/shards*D) with NO "
                         "gathered federation — needs --mixer sharded and "
                         "the sparse repr; the 100k-node schedule), or "
                         "auto (memory-based choice)")
    ap.add_argument("--gossip-repr", default="auto",
                    choices=["dense", "sparse", "auto"],
                    help="mixing-operator representation: dense (N, N) "
                         "matrix, sparse (N, B+1) neighbor table "
                         "(O(N*B) mixing — population scale), or auto "
                         "(sparse once B+1 << N)")
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator host:port (or env "
                         "REPRO_COORDINATOR); only with --num-processes > 1")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total processes in the multi-host federation "
                         "(or env REPRO_NUM_PROCESSES); unset/1 = "
                         "single-process")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's id in [0, num-processes) (or env "
                         "REPRO_PROCESS_ID)")
    ap.add_argument("--out", default="experiments/checkpoints")
    ap.add_argument("overrides", nargs="*", help="cfg overrides a.b=c")
    args = ap.parse_args()
    enable_compile_cache()

    if args.use_kernel:
        import warnings

        warnings.warn(
            "--use-kernel is deprecated; pass --mixer kernel instead "
            "(the flag maps through for now and will be removed)",
            DeprecationWarning,
            stacklevel=2,
        )
        if args.mixer is None:
            args.mixer = "kernel"
        elif args.mixer != "kernel":
            raise SystemExit(
                f"--use-kernel contradicts --mixer {args.mixer}; "
                f"pass one or the other"
            )

    from repro.launch import multihost

    # must precede every device query (mesh building, auto gossip-impl)
    distributed = multihost.initialize(
        args.coordinator, args.num_processes, args.process_id
    )
    sweep_ratios = None
    sweep_axes = {}
    if args.sweep_ratios is not None:
        sweep_ratios = [float(r) for r in args.sweep_ratios.split(",") if r]
        if not sweep_ratios:
            raise SystemExit("--sweep-ratios parsed to an empty list")
        if args.sweep_seeds < 1:
            raise SystemExit("--sweep-seeds must be >= 1")
        if args.sweep_schedules:
            sweep_axes["schedules"] = tuple(
                s.strip() for s in args.sweep_schedules.split(",") if s.strip()
            )
        if args.sweep_skews:
            sweep_axes["skews"] = tuple(
                float(v) for v in args.sweep_skews.split(",") if v
            )
        if args.sweep_dp_sigmas:
            sweep_axes["dp_sigmas"] = tuple(
                float(v) for v in args.sweep_dp_sigmas.split(",") if v
            )
        if distributed:
            raise SystemExit("scenario sweeps are single-process "
                             "(drop --num-processes or --sweep-ratios)")
        if args.mixer == "kernel":
            raise SystemExit("scenario sweeps batch the tree or sharded "
                             "mixer; the Pallas kernel is per-scenario "
                             "(drop --mixer kernel/--use-kernel)")
        if args.engine == "loop" or args.chunk == 0:
            raise SystemExit("scenario sweeps need the scan engine "
                             "(drop --engine loop / --chunk 0)")
    elif args.sweep_schedules or args.sweep_skews or args.sweep_dp_sigmas:
        raise SystemExit("--sweep-schedules/--sweep-skews/--sweep-dp-sigmas "
                         "extend the scenario grid and need --sweep-ratios")
    if distributed:
        print(f"multihost: process {jax.process_index()}/{jax.process_count()} "
              f"local_devices={jax.local_device_count()} "
              f"global_devices={jax.device_count()}")
        if args.mixer not in (None, "sharded"):
            print(f"multihost: overriding --mixer {args.mixer} -> sharded "
                  f"(the node axis must span processes)")
        args.mixer = "sharded"
        if args.engine == "loop" or args.chunk == 0:
            raise SystemExit("multihost runs need the scan engine "
                             "(drop --engine loop / --chunk 0)")

    cfg = apply_overrides(ExperimentConfig(), args.overrides)
    fed = load_federated_dataset(args.dataset, fast=args.fast_data,
                                 history_len=cfg.data.history_len,
                                 horizon=cfg.data.horizon)
    print(f"dataset={args.dataset} nodes={fed.num_nodes} "
          f"windows/node~{int(fed.counts.mean())}")

    model = LSTMModel(hidden=args.hidden, use_kernel=args.use_kernel).as_model()
    from dataclasses import replace

    fl_cfg = replace(
        cfg.fl, topology=args.topology, num_nodes=fed.num_nodes,
        rounds=args.rounds, inactive_ratio=args.inactive_ratio,
    )
    # the scenario grid and its mesh come FIRST: the auto gossip-impl
    # choice must budget for the swept working set, and the trainer gets
    # the one mesh train_sweep will actually run on
    sweep_grid = sweep_mesh = None
    if sweep_ratios is not None:
        from repro.core import SweepGrid

        sweep_grid = SweepGrid.build(
            [args.topology], sweep_ratios, range(args.sweep_seeds),
            num_nodes=fed.num_nodes, cluster_size=fl_cfg.cluster_size,
            **sweep_axes,
        )
        if args.mixer == "sharded":
            from repro.launch.mesh import make_sweep_mesh

            sweep_mesh = make_sweep_mesh(sweep_grid.size, fed.num_nodes)

    gossip_impl = args.gossip_impl
    if gossip_impl == "auto":
        from repro.launch.mesh import choose_gossip_impl

        p0 = model.init(jax.random.PRNGKey(0))
        node_bytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(p0))
        if sweep_mesh is not None:
            # swept-sharded: the allgather schedule gathers the node axis
            # of EVERY locally-held scenario block, so the per-device
            # working set is (G/grid_width) x the serial estimate — and
            # psum's shard count is the SWEEP mesh's node width, not the
            # 1-D federation mesh's
            g_local = sweep_grid.size // sweep_mesh.shape["grid"]
            gossip_impl = choose_gossip_impl(
                fed.num_nodes, node_bytes * g_local,
                shards=sweep_mesh.shape["node"],
            )
        else:
            gossip_impl = choose_gossip_impl(fed.num_nodes, node_bytes)
        print(f"gossip-impl auto -> {gossip_impl}")

    # args.mixer is already "kernel" when --use-kernel was passed (the
    # deprecation shim above), so the flag itself stays out of the plan;
    # --gossip-repr auto resolves in the plan, on the mesh the mix runs on
    trainer = GluADFL(model, get_optimizer(cfg.train.optimizer, cfg.train.lr),
                      fl_cfg, mixer=args.mixer,
                      gossip_impl=gossip_impl, gossip_repr=args.gossip_repr,
                      mesh=sweep_mesh)
    if args.gossip_repr == "auto":
        print(f"gossip-repr auto -> {trainer.gossip_repr}")

    # pre-batched validation set for the in-scan streaming eval: a capped
    # slice of every patient's val windows (one fixed array -> scan const)
    val_data = None
    if args.eval_every:
        cap = max(1, 2048 // fed.num_nodes)
        val_x = np.concatenate([p.val_x[:cap] for p in fed.patients])
        val_y = np.concatenate([p.val_y[:cap] for p in fed.patients])
        val_data = (val_x, val_y)
        print(f"streaming eval: every {args.eval_every} rounds on "
              f"{len(val_x)} val windows (in-scan)")

    if sweep_ratios is not None:
        from repro.utils.pytree import tree_index

        grid = sweep_grid
        axes_note = "".join(
            f" x {k} {list(v)}" for k, v in sweep_axes.items()
        )
        print(f"sweep: {grid.size} scenarios "
              f"({args.topology} x {sweep_ratios}{axes_note} x "
              f"{args.sweep_seeds} seeds) as one batched program")
        if sweep_mesh is not None:
            # the trainer holds this exact mesh — train_sweep runs on it
            print(f"sweep mesh: {dict(sweep_mesh.shape)} over "
                  f"{len(jax.devices())} devices "
                  f"(grid batches, node carries the gossip collectives)")
        pops, hists, _ = trainer.train_sweep(
            fed.x, fed.y, fed.counts, grid=grid,
            batch_size=cfg.train.batch_size, chunk=args.chunk or None,
            eval_every=args.eval_every, val_data=val_data,
        )
        summary = []
        for g in range(grid.size):
            lab = grid.label_dict(g)
            hist = hists[g]
            pop_g = tree_index(pops, g)
            preds, ys = [], []
            for p, pred in _patient_predictions(model, pop_g, fed):
                preds.append(pred)
                ys.append(p.test_y_raw)
            agg = all_metrics(np.concatenate(ys), np.concatenate(preds))
            rec = {**lab, "final_loss": hist[-1]["loss"], **agg}
            evals = [h["val_rmse"] for h in hist if "val_rmse" in h]
            if evals:
                rec["final_val_rmse"] = evals[-1]
            summary.append(rec)
            extra = ""
            if sweep_axes:
                extra = (f" sched={lab['schedule']} skew={lab['skew']:g} "
                         f"dp={lab['dp_sigma']:g}")
            print(f"  [{lab['topology']:8s} "
                  f"inactive={lab['inactive_ratio']:.0%} "
                  f"seed={lab['seed']}{extra}] "
                  f"loss {rec['final_loss']:.4f}  test RMSE {agg['rmse']:6.2f}  "
                  f"MARD {agg['mard']:5.2f}%")
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        sweep_path = out / f"sweep_{args.dataset}_{args.topology}.json"
        sweep_path.write_text(json.dumps(summary, indent=2))
        print(f"sweep summary -> {sweep_path}")
        return

    pop, hist, state = trainer.train(
        jax.random.PRNGKey(cfg.fl.seed), fed.x, fed.y, fed.counts,
        batch_size=cfg.train.batch_size,
        engine="loop" if args.chunk == 0 else args.engine,
        chunk=args.chunk or None,
        eval_every=args.eval_every,
        val_data=val_data,
    )
    if multihost.is_primary():  # every process holds the same history
        print(f"round 0 loss {hist[0]['loss']:.4f} -> round {args.rounds-1} "
              f"loss {hist[-1]['loss']:.4f}")
        evals = [h for h in hist if "val_rmse" in h]
        if evals:
            print("val RMSE (normalized): " + "  ".join(
                f"r{h['round']}={h['val_rmse']:.4f}" for h in evals[-5:]))

    # the population model is replicated across every process; the
    # host-side gather makes it plain numpy so clinical metrics and the
    # checkpoint run local-only — then PROCESS 0 is the single writer
    pop = multihost.fetch_replicated(pop)
    if multihost.is_primary():
        # per-patient + aggregate clinical metrics
        preds, ys = [], []
        for i, (p, pred) in enumerate(_patient_predictions(model, pop, fed)):
            m = all_metrics(p.test_y_raw, pred)
            print(f"  patient {i:3d}: RMSE {m['rmse']:6.2f}  MARD {m['mard']:5.2f}%  "
                  f"gRMSE {m['grmse']:6.2f}  lag {m['time_lag']:4.1f}min")
            preds.append(pred)
            ys.append(p.test_y_raw)
        agg = all_metrics(np.concatenate(ys), np.concatenate(preds))
        print("population:", {k: round(v, 2) for k, v in agg.items()})

        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        ckpt = out / f"gluadfl_{args.dataset}_{args.topology}.npz"
        save_checkpoint(ckpt, pop)
        print(f"checkpoint -> {ckpt}")
    multihost.barrier("train_done")


if __name__ == "__main__":
    main()
