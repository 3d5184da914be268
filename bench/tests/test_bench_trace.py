"""The trace reduction: busy union, idle share and labelled gaps, on
hand-made events and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import trace_reduce

FIXTURE = Path(__file__).resolve().parents[1] / "data" / "tiny_train.xplane.pb"


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_events_by_hand():
    ops = {"/device:TPU:0": [("fusion.1", 10, 30), ("fusion.2", 20, 40), ("copy", 60, 70),
                             ("fusion.1", 95, 120)]}
    spans = [("chunk.dispatch", 0, 50), ("chunk.sync", 50, 100)]
    r = trace_reduce.reduce_events(ops, spans)
    # window 0..100 ns; busy [10,40] + [60,70] + [95,100] = 45 ns
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(45e-9)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["chunk.sync", pytest.approx(25e-9)]  # 70..95
    # gaps 0..10, 40..60 (middle 50: the sync has opened) and 70..95
    assert sorted(g[0] for g in gaps) == ["chunk.dispatch", "chunk.sync", "chunk.sync"]
    names = dict(r["breakdown"]["device_ops"])
    assert names["fusion.1"] == pytest.approx(25e-9)  # 20 inside + 5 clipped


def test_two_chips_average():
    ops = {"/device:TPU:0": [("a", 0, 50)], "/device:TPU:1": [("a", 0, 100)]}
    r = trace_reduce.reduce_events(ops, [("chunk.dispatch", 0, 100)])
    assert r["busy_s"] == pytest.approx(75e-9)


def test_no_device_op_inside_the_window_raises():
    """Host spans and device events on different clocks show as a window
    with no device operation in it: an error, not an idle chip."""
    ops = {"/device:TPU:0": [("fusion.1", 500, 600)]}
    with pytest.raises(ValueError, match="inside the host spans"):
        trace_reduce.reduce_events(ops, [("chunk.dispatch", 0, 100)])


def test_device_plane_without_op_lines_raises(monkeypatch):
    """A device plane with neither "XLA Ops" nor "XLA Modules" is an error:
    its other lines (steps, markers) are no measure of busy time."""
    from types import SimpleNamespace as NS

    import jax.profiler

    ev = NS(name="step 1", start_ns=0, duration_ns=100)
    plane = NS(name="/device:TPU:0", lines=[NS(name="Steps", events=[ev])])
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        NS(from_file=lambda path: NS(planes=[plane])))
    with pytest.raises(ValueError, match="XLA Ops"):
        trace_reduce.read_xplane("unused.xplane.pb")
