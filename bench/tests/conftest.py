"""Shared by the whole-run tests: a cell shrunk to a size the CPU runs in
seconds, past the harness's look for a chip."""
import json

import pytest

from bench import harness

SMALL = {
    "train.h128.i30": ({"num_nodes": 16, "num_days": 6, "hidden": 8, "batch_size": 16,
                        "chunk": 4}, {}),
}


@pytest.fixture
def small(monkeypatch, bench_root):
    import jax

    real = harness.load_cell

    def load_small(name, root=None):
        cell = real(name, root or bench_root)
        cell.config.update(SMALL[name][0])
        cell.traffic.update(SMALL[name][1])
        return cell

    monkeypatch.setattr(harness, "load_cell", load_small)
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


@pytest.fixture
def bench_root():
    """The checkout whose ``BENCHMARK.json`` the run reads."""
    return harness.ROOT


def run_line(capsys, cell: str, trace: int = 0) -> dict:
    from bench import run

    args = ["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "0.6",
            "--trace", str(trace)]
    assert run.main(args) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
