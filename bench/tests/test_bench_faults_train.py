"""A whole train.h128.i30 run at a small size on the CPU: the sound program is
correct, and each fault its kind can have, and the lower-precision
control, make ``correct`` false."""
import pytest

from bench import harness
from bench.faults import FAULTS
from bench.tests.conftest import run_line

CELL = "train.h128.i30"


def test_sound_run_is_correct(small, capsys):
    line = run_line(capsys, CELL)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in harness.load_cell(CELL).end_to_end}


@pytest.mark.parametrize("fault", sorted(FAULTS["federation"]))
def test_fault_is_caught(small, capsys, monkeypatch, fault):
    FAULTS["federation"][fault](monkeypatch.setattr)
    line = run_line(capsys, CELL)
    assert line["correct"] is False, line["checks"]


def test_traced_run_reports_per_layer_metrics(small, capsys, monkeypatch):
    """On the CPU the trace has no TPU plane: the device numbers stay out,
    the host-clock ones are read."""
    peaks = harness.peaks
    monkeypatch.setattr(harness, "peaks", lambda kind: peaks("TPU v5 lite"))
    line = run_line(capsys, CELL, trace=1)
    assert line["correct"] is True
    assert "mfu.train" in line["metrics"] and "idle_share.train" not in line["metrics"]
    assert 0 < line["metrics"]["mfu.train"]["value"] < 100
