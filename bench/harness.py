"""The parts every cell shares: finding a cell's files by name, the
device check, seeds, the compile cache, spans and tracing, per-layer
readers and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration file is named there; its traffic mix is
``bench/traffic/<traffic>.json``, whose ``kind`` names the driver
``bench/kinds/<kind>.py``; its correctness limits are
``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new cell, mix or metric is new files
and entries only.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# one fixed directory inside the checkout: the path is part of the
# compile cache's key, so it must never move
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a cell and every file it names; a missing one raises."""
    bm = load_benchmark(root)
    wl = {w["name"]: w for w in bm["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    bench = root / "bench"
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench / "limits" / f"{name}.json").read_text())
    e2e = [m for m in bm["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bm["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    return Cell(name, w["chips"], config, traffic, limits, e2e, per_layer)


def kind_module(cell: Cell):
    return importlib.import_module(f"bench.kinds.{cell.traffic['kind']}")


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def seed_key(seed: int, stream: int):
    """A raw uint32 JAX key from the whole seed (``PRNGKey`` would keep
    only its low 32 bits) and a stream number."""
    import jax.numpy as jnp

    words = np.random.SeedSequence([seed % 2**64, stream]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def require_chips(n: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoDevice(
            f"need {n} TPU chip(s); JAX sees {len(devs)} {devs[0].platform} device(s)"
        )
    return devs[:n]


def enable_compile_cache() -> None:
    """The persistent compile cache in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` already placed it; every program is
    cached, small ones too, so a second run compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts backend compilations while armed (a persistent-cache hit
    is not a compilation)."""

    def __init__(self):
        self.armed = False
        self.count = 0

    def _listen(self, event, duration, **kw):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._listen)


class Tracer:
    """The profiler around the measured window, on only with ``--trace 1``,
    and the harness's own spans (host annotations in the same trace)."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if self.on:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def reduce(self, chips: int) -> dict | None:
        """Busy union, window and breakdown of the recorded trace; the
        directory is removed afterwards."""
        if not self.on:
            return None
        from bench.trace_reduce import reduce_xplane

        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            return reduce_xplane(str(files[-1]), chips) if files else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip (read before the reference
    runs: a process's peak never falls again)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def device_info(devices, peak: int, trace: dict | None) -> dict:
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": int(peak)}
    if trace is not None:
        info["busy_s"] = trace["busy_s"]
        info["window_s"] = trace["window_s"]
    return info


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def check(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit)}


def passed(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())


def result_line(cell: Cell, out: dict, trace_on: bool, devices) -> dict:
    """The last stdout line: end-to-end metrics, or with ``--trace 1``
    the per-layer ones each reader finds; the checks come last."""
    if trace_on:
        ctx = dict(out["ctx"], chips=cell.chips, config=cell.config,
                   traffic=cell.traffic, peaks=peaks(devices[0].device_kind),
                   trace=out.get("trace"))
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {
            m["name"]: {"value": float(out["end_to_end"][m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end
        }
    line = {
        "correct": passed(out["checks"]),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": metrics,
        "device": device_info(devices, out["memory_peak_bytes"], out.get("trace")),
    }
    if trace_on and out.get("trace"):
        line["breakdown"] = out["trace"]["breakdown"]
    line["checks"] = out["checks"]
    return line


def emit(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
