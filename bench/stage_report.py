#!/usr/bin/env python3
"""Device time per round stage of a training cell, and what tracing costs.

    python3 bench/stage_report.py --workload <cell> --seed <n> --seconds <s>

On the cell's chips, in one process: set-up as a run of the cell does
(trainer, data and weights from the seed, the first chunk), then a
window of ``--seconds`` with the profiler off and one with it on, each
of whole ``train_chunk`` calls ending in a host sync inside the
harness's spans.  The name stacks of the chunk program's ops are read
from its compiled HLO text after set-up.  The traced window is reduced
twice: by ``bench/trace_reduce.py`` (busy union, idle gaps) and by
``bench/trace_stages.py`` (self time per op, device time per round
stage).  The last stdout line is one JSON object: rounds/s of both
windows, busy and window seconds, each stage's seconds per chip and ms
per round, the unscoped share of busy, and the ops by self time.
"""
import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def window(trainer, state, data, cell, seconds, tracer):
    """Whole chunks until ``seconds`` have passed: the state and the
    rounds per second."""
    import numpy as np

    cfg = cell.config
    rounds = 0
    tracer.start()
    start = time.perf_counter()
    while True:
        with tracer.span("chunk.dispatch"):
            state, losses = trainer.train_chunk(state, *data, batch_size=cfg["batch_size"],
                                                chunk=cfg["chunk"])
        with tracer.span("chunk.sync"):
            np.asarray(losses)
        rounds += cfg["chunk"]
        elapsed = time.perf_counter() - start
        if elapsed >= seconds:
            break
    tracer.stop()
    return state, rounds, rounds / elapsed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, trace_reduce, trace_stages
    from bench.kinds import federation

    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    trainer = federation.make_trainer(cell)
    data, state, keys = federation.make_inputs(cell, trainer, args.seed)
    state, _ = federation.first_chunk(cell, trainer, data, state, keys)
    stacks = trace_stages.chunk_stacks(trainer, state, data, cell.config)

    state, _, plain = window(trainer, state, data, cell, args.seconds, harness.Tracer(False))
    tracer = harness.Tracer(True)
    state, rounds, traced = window(trainer, state, data, cell, args.seconds, tracer)
    path = str(sorted(Path(tracer.dir).rglob("*.xplane.pb"))[-1])
    ops, spans = trace_reduce.read_xplane(path)
    shutil.rmtree(tracer.dir, ignore_errors=True)
    ops = dict(sorted(ops.items())[:cell.chips])
    union = trace_reduce.reduce_events(ops, spans)
    staged = trace_stages.reduce_stages(ops, spans, stacks)

    busy = staged["busy_s"]
    line = {
        "workload": args.workload,
        "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "rounds_per_s": {"untraced": plain, "traced": traced},
        "traced_rounds": rounds,
        "busy_s": {"union": union["busy_s"], "stages": busy},
        "window_s": union["window_s"],
        "stages_s": staged["stages"],
        "stage_ms_per_round": {k: 1e3 * v / rounds for k, v in staged["stages"].items()},
        "unscoped_share": 100.0 * staged["stages"][trace_stages.UNSCOPED] / busy,
        "device_ops_self": staged["device_ops"],
        "idle_gaps": union["breakdown"]["idle_gaps"],
    }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
