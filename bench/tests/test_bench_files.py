"""Every cell resolves to its files, and a cell added as new files alone
is found; the FLOP counter gives the stated check values."""
import json
import shutil

import pytest

from bench import harness
from bench.flops import federation_round_flops, lstm_train_flops_per_node_step

BM = harness.load_benchmark()


@pytest.mark.parametrize("name", [w["name"] for w in BM["workloads"]])
def test_cell_resolves(name):
    cell = harness.load_cell(name)
    assert (harness.BENCH / "kinds" / f"{cell.traffic['kind']}.py").is_file()
    assert harness.kind_module(cell).run
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_config_files_state_their_cut():
    for c in BM["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert (harness.ROOT / cfg["reference"]).is_file()


def test_new_cell_from_files_alone(tmp_path):
    """A mix, a limit file, a metric reader and entries: no code edited."""
    shutil.copytree(harness.BENCH, tmp_path / "bench")
    bm = json.loads(json.dumps(BM))
    bm["workloads"].append({"name": "train.h128.i50", "config": "lstm-h128-replacebg226",
                            "traffic": "fed.random.i50", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "rounds_seen", "unit": "count", "better": "higher",
                            "source": "host_clock", "layer": "entry", "moves": "rounds_per_s",
                            "workloads": ["train.h128.i50"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    traffic = json.loads((harness.BENCH / "traffic" / "fed.random.i30.json").read_text())
    (tmp_path / "bench/traffic/fed.random.i50.json").write_text(
        json.dumps(dict(traffic, inactive_ratio=0.5)))
    (tmp_path / "bench/limits/train.h128.i50.json").write_text(
        (harness.BENCH / "limits" / "train.h128.i30.json").read_text())
    (tmp_path / "bench/metrics/rounds_seen.py").write_text(
        "def read(ctx):\n    return ctx['train_window']['rounds']\n")
    cell = harness.load_cell("train.h128.i50", root=tmp_path)
    assert cell.traffic["inactive_ratio"] == 0.5
    assert [m["name"] for m in cell.per_layer][-1] == "rounds_seen"
    read = harness.metric_reader("rounds_seen", root=tmp_path)
    assert read({"train_window": {"rounds": 7}}) == 7


@pytest.mark.parametrize("hidden,per_step,per_round", [
    (128, 304_398_336, 48.16e9),
    (512, 4_841_472_000, 766.0e9),
])
def test_flops_check_values(hidden, per_step, per_round):
    assert lstm_train_flops_per_node_step(hidden, 12, 64) == per_step
    cfg = {"hidden": hidden, "history_len": 12, "batch_size": 64, "input_size": 1,
           "local_steps": 1, "num_nodes": 226}
    assert federation_round_flops(cfg, 0.3) == pytest.approx(per_round, rel=1e-3)


def test_unknown_device_has_no_peaks():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
