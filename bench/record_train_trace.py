#!/usr/bin/env python3
"""Records the small chip trace of the training program that
``bench/tests/test_bench_trace_stages.py`` checks the reductions on.

    python3 bench/record_train_trace.py --out <dir>

On a TPU: the federation trainer of ``train.h128.i30`` at a small size
(``SMALL``), its compiled ``train_chunk`` called ``CALLS`` times inside
the harness's spans, each call synced and followed by a sleep of
``SLEEP_S`` inside ``chunk.sync``, so the trace holds known idle gaps.
Writes ``<dir>/tiny_train.xplane.pb.gz`` (gzip: the TPU runtime puts
the program's HLO proto into the trace, 0.78 of its 1.1 MB, though no
reduction reads it) and ``<dir>/tiny_train.json``: what was run, every
plane and line name the trace holds, and the name stack of each
instruction on the device's ops line, from the compiled program's HLO
text.
"""
import argparse
import gzip
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CELL, SEED, CALLS, SLEEP_S = "train.h128.i30", 7, 3, 0.05
SMALL = {"num_nodes": 16, "num_days": 6, "hidden": 8, "batch_size": 16, "chunk": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from bench import harness, trace_reduce, trace_stages
    from bench.kinds import federation

    cell = harness.load_cell(CELL)
    cell.config.update(SMALL)
    devices = harness.require_chips(1)
    trainer = federation.make_trainer(cell)
    data, state, _ = federation.make_inputs(cell, trainer, SEED)

    def chunk(st):
        return trainer.train_chunk(st, *data, batch_size=SMALL["batch_size"],
                                   chunk=SMALL["chunk"])

    state, losses = chunk(state)
    np.asarray(losses)
    stacks = trace_stages.chunk_stacks(trainer, state, data, cell.config)
    tracer = harness.Tracer(True)
    tracer.start()
    for _ in range(CALLS):
        with tracer.span("chunk.dispatch"):
            state, losses = chunk(state)
        with tracer.span("chunk.sync"):
            np.asarray(losses)
            time.sleep(SLEEP_S)
    tracer.stop()
    src = str(sorted(Path(tracer.dir).rglob("*.xplane.pb"))[-1])
    pd = ProfileData.from_file(src)
    ops, _ = trace_reduce.read_xplane(src)
    seen = {trace_stages.instruction(name) for evs in ops.values() for name, _, _ in evs}
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "tiny_train.xplane.pb.gz").write_bytes(
        gzip.compress(Path(src).read_bytes(), compresslevel=9, mtime=0))
    shutil.rmtree(tracer.dir, ignore_errors=True)
    facts = {
        "device_kind": devices[0].device_kind,
        "jax": jax.__version__,
        "cell": CELL,
        "config": SMALL,
        "seed": SEED,
        "calls": CALLS,
        "sleep_s": SLEEP_S,
        "planes": {p.name: [line.name for line in p.lines] for p in pd.planes},
        "stacks": {k: v for k, v in sorted(stacks.items()) if k in seen},
    }
    (out / "tiny_train.json").write_text(json.dumps(facts, indent=1) + "\n")
    print(json.dumps({k: v for k, v in facts.items() if k != "stacks"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
