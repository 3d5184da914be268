#!/usr/bin/env python3
"""Records the small chip trace that ``bench/tests/test_bench_trace.py``
checks the reduction on.

    python3 bench/record_trace.py --out <dir>

On a TPU: a jitted matmul called ``CALLS`` times inside the harness's
spans, each call synced and followed by a sleep of ``SLEEP_S`` inside
``chunk.sync``, so the trace holds known idle gaps.  Writes
``<dir>/tiny_v5e.xplane.pb`` and ``<dir>/tiny_v5e.json`` (what was run,
and every plane and line name the trace holds).
"""
import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CALLS, SLEEP_S, SIZE = 3, 0.05, 2048


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    import jax
    import jax.numpy as jnp

    from bench import harness

    devices = harness.require_chips(1)
    f = jax.jit(lambda a: jnp.tanh(a @ a))
    x = jax.random.normal(jax.random.key(0), (SIZE, SIZE), jnp.float32)
    f(x).block_until_ready()
    tracer = harness.Tracer(True)
    tracer.start()
    for _ in range(CALLS):
        with tracer.span("chunk.dispatch"):
            y = f(x)
        with tracer.span("chunk.sync"):
            y.block_until_ready()
            time.sleep(SLEEP_S)
    tracer.stop()
    src = sorted(Path(tracer.dir).rglob("*.xplane.pb"))[-1]
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "tiny_v5e.xplane.pb")
    shutil.rmtree(tracer.dir, ignore_errors=True)

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(out / "tiny_v5e.xplane.pb"))
    facts = {
        "device_kind": devices[0].device_kind,
        "jax": jax.__version__,
        "calls": CALLS,
        "sleep_s": SLEEP_S,
        "matmul": [SIZE, SIZE],
        "planes": {p.name: [line.name for line in p.lines] for p in pd.planes},
    }
    (out / "tiny_v5e.json").write_text(json.dumps(facts, indent=1) + "\n")
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.exit(main())
