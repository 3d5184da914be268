#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on: set-up from the seed, a measured window of ``--seconds``,
then the check against the plain reference.  The last line of stdout is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and ``checks`` last);
the last lines of stderr repeat each checked number beside its limit.
With ``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones.  Without a TPU, or with fewer chips
than the cell asks for, it exits 3 and prints no result.
"""
import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    out = harness.kind_module(cell).run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t0=T0, devices=devices,
    )
    harness.emit(harness.result_line(cell, out, bool(args.trace), devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
