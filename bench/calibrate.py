#!/usr/bin/env python3
"""Readings that a training cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--fault-seeds a,b,c] [--out FILE]

In one process on the chip, at the cell's own size: the sound program
on every ``--seeds`` seed, then the bfloat16 control and each planted
fault of ``bench/faults.py`` on the ``--fault-seeds``, each compared with
the plain reference exactly as a run's check does; a training cell's
readings need no measured window.  Prints one JSON line per reading, then a summary: per number the
largest sound reading (the lower one) and the smallest reading of the
control and of each fault.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from bench.faults import FAULTS, Patch

    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    rows = []

    def record(what, seed, vals):
        row = {"what": what, "seed": seed, **vals}
        rows.append(row)
        print(json.dumps(row), flush=True)

    kind = harness.kind_module(cell)
    for s in args.seeds:
        record("program", s, kind.readings(cell, s))
    for name, plant in FAULTS[cell.traffic["kind"]].items():
        for s in args.fault_seeds:
            with Patch() as patch:
                plant(patch)
                record(name, s, harness.kind_module(cell).readings(cell, s))

    numbers = sorted(cell.limits)
    summary = {"workload": cell.name, "limits": cell.limits}
    for what in dict.fromkeys(r["what"] for r in rows):
        vals = [r for r in rows if r["what"] == what]
        agg = max if what == "program" else min
        summary[what] = {n: agg(r[n] for r in vals if n in r) for n in numbers}
    summary["seconds"] = time.perf_counter() - T0
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows, "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
