"""Self time per op and device time per round stage: on hand-made events,
and on a small trace of the training program recorded on a TPU v5e
(``bench/record_train_trace.py``)."""
import gzip
import json
from pathlib import Path

import pytest
from repro.core.gluadfl import ROUND_STAGES

from bench import trace_reduce, trace_stages

DATA = Path(__file__).resolve().parents[1] / "data"
FIXTURE = DATA / "tiny_train.xplane.pb.gz"
LS = "jit(_train_chunk)/while/body/closed_call/vmap()/round.local_step"
CHIP = "/device:TPU:0"


def spans(lo, hi):
    return [("chunk.dispatch", lo, hi)]


def reduce(ops, lo, hi, stacks=None, **kw):
    return trace_stages.reduce_stages({CHIP: ops}, spans(lo, hi), stacks or {}, **kw)


def busy_union(ops, lo, hi):
    return trace_reduce.reduce_events({CHIP: ops}, spans(lo, hi))["busy_s"]


def test_stage_names_are_the_programs():
    assert ROUND_STAGES == ("neighbours", "gossip", "local_step", "adam", "select")


def test_while_keeps_only_its_uncovered_time():
    """A ``while`` that encloses two fusions: its self time is what they
    leave uncovered; busy is the union, as the busy reduction has it."""
    ops = [("while.1", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 30, 60)]
    r = reduce(ops, 0, 100)
    own = dict(r["device_ops"])
    assert own["while.1"] == pytest.approx(50e-9)  # 100 - union [10, 60]
    assert own["fusion.1"] == pytest.approx(20e-9)  # 10..30; fusion.2 started later
    assert own["fusion.2"] == pytest.approx(30e-9)
    assert r["busy_s"] == pytest.approx(busy_union(ops, 0, 100)) == pytest.approx(100e-9)
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(r["busy_s"])


def test_nested_whiles_and_window_clipping():
    """Nesting two deep, ops cut by the window's edges, and a gap."""
    ops = [("while.0", -50, 150), ("while.1", 0, 80), ("fusion.1", 5, 15),
           ("fusion.2", 70, 130), ("copy", 160, 190)]
    r = reduce(ops, 10, 180)
    own = dict(r["device_ops"])
    assert own["fusion.1"] == pytest.approx(5e-9)     # 10..15
    assert own["while.1"] == pytest.approx(55e-9)     # 15..70
    assert own["fusion.2"] == pytest.approx(60e-9)    # 70..130
    assert own["while.0"] == pytest.approx(20e-9)     # 130..150
    assert own["copy"] == pytest.approx(20e-9)        # 160..180
    assert r["busy_s"] == pytest.approx(busy_union(ops, 10, 180)) == pytest.approx(160e-9)


def test_top_entries_never_exceed_busy():
    """The repair: an enclosing op no longer counts its body's time, so
    the ranked entries sum to at most busy."""
    ops = [("while.238", 0, 1000)] + [(f"fusion.{i}", 10 * i, 10 * i + 8)
                                     for i in range(1, 90)]
    r = reduce(ops, 0, 1000, top=10)
    assert all(t <= 1000e-9 for _, t in r["device_ops"])
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] + 1e-18


def test_equal_intervals_count_once():
    r = reduce([("a", 0, 10), ("b", 0, 10)], 0, 10)
    assert r["busy_s"] == pytest.approx(10e-9)
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(10e-9)


def test_partial_overlap_goes_to_the_later_op():
    r = reduce([("a", 0, 10), ("b", 5, 15)], 0, 20)
    assert dict(r["device_ops"]) == {"a": pytest.approx(5e-9), "b": pytest.approx(10e-9)}
    assert r["busy_s"] == pytest.approx(15e-9)


@pytest.mark.parametrize("stack,stage", [
    ("transpose(jvp(round.local_step))/dot_general", "local_step"),
    (f"{LS}/transpose(jvp())/while/body/closed_call/dot_general", "local_step"),
    (f"{LS}/jvp()/dot_general", "local_step"),
    ("jit(_train_chunk)/while/body/closed_call/round.gossip/nm,md->nd/dot_general", "gossip"),
    ("jit(_train_chunk)/while/body/round.neighbours/jit(_uniform)/round.adam/sqrt", "adam"),
    ("jit(_train_chunk)/while/body/closed_call/round.select/select_n;"
     "jit(_train_chunk)/while/body/closed_call/round.adam/sqrt", "select"),
    ("jit(_train_chunk)/while", "unscoped"),
    ("", "unscoped"),
    ("jit(f)/round.unknown/add", "unscoped"),
    ("jit(f)/round.gossip/round.local_stepper/add", "gossip"),
])
def test_stage_of(stack, stage):
    """Transforms are seen through, the innermost stage wins, a fusion
    takes its first name stack, and no stage is ``unscoped``."""
    assert trace_stages.stage_of(stack) == stage


def test_stages_sum_to_busy():
    ops = [("%while.1 = (s32[]) while(%t)", 0, 100), ("%fusion.1 = f32[8] fusion()", 10, 40),
           ("fusion.2", 40, 50), ("fusion.3", 50, 60), ("copy.1", 55, 58),
           ("fusion.4", 70, 90)]
    stacks = {"while.1": "jit(_train_chunk)/while",
              "fusion.1": f"{LS}/jvp()/dot_general",
              "fusion.2": f"{LS}/transpose(jvp())/dot_general",
              "fusion.3": "jit(_train_chunk)/while/body/round.adam/sqrt",
              "copy.1": "jit(_train_chunk)/while/body/round.select/select_n",
              "fusion.4": "jit(_train_chunk)/while/body/round.gossip/dot_general"}
    st = reduce(ops, 0, 100, stacks)["stages"]
    assert set(st) == set(ROUND_STAGES) | {"unscoped"}
    assert st["local_step"] == pytest.approx(40e-9)
    assert st["adam"] == pytest.approx(7e-9)
    assert st["select"] == pytest.approx(3e-9)
    assert st["gossip"] == pytest.approx(20e-9)
    assert st["neighbours"] == 0.0
    assert st["unscoped"] == pytest.approx(30e-9)  # 0..10, 60..70, 90..100
    assert sum(st.values()) == pytest.approx(busy_union(ops, 0, 100)) == pytest.approx(100e-9)


def test_two_chips_average():
    stacks = {"f": "x/round.gossip/y"}
    r = trace_stages.reduce_stages({CHIP: [("f", 0, 50)], "/device:TPU:1": [("f", 0, 100)]},
                                   spans(0, 100), stacks)
    assert r["stages"]["gossip"] == pytest.approx(75e-9) == pytest.approx(r["busy_s"])


HLO = """\
%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.1 = f32[8]{0} fusion(%p), calls=%f, metadata={op_name="LS/jvp()/dot" stack_frame_id=7}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%c, %fusion.1)
}
ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %copy.188 = f32[8]{0:T(256)} copy(f32[8]{0} %Arg_0.1)
  %while.2 = (s32[], f32[8]) while(%t), body=%body, metadata={op_name="jit(f)/while"}
  ROOT %select-and-scatter.4 = f32[8]{0} select-and-scatter(%a), metadata={op_name="round.select"}
}
"""


def test_stacks_from_the_compiled_text():
    """Every instruction with metadata maps to its ``op_name``; one
    without (a copy the compiler put in) has none, and is unscoped."""
    stacks = trace_stages.hlo_stacks(HLO.replace("LS", LS))
    assert stacks == {"fusion.1": f"{LS}/jvp()/dot", "while.2": "jit(f)/while",
                      "select-and-scatter.4": "round.select"}
    assert trace_stages.instruction("%fusion.1 = f32[8]{0:T(256)} fusion(...)") == "fusion.1"
    assert trace_stages.instruction("copy-start.49") == "copy-start.49"
    ops = [("%fusion.1 = f32[8]{0:T(256)} fusion(f32[8]{0} %p)", 10, 15),
           ("%copy.188 = f32[8]{0:T(256)} copy(f32[8]{0} %Arg_0.1)", 20, 25)]
    st = reduce(ops, 0, 30, stacks)["stages"]
    assert st["local_step"] == pytest.approx(5e-9) == pytest.approx(st["unscoped"])


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The facts, the trace unpacked, and its ops and spans."""
    facts = json.loads((DATA / "tiny_train.json").read_text())
    path = tmp_path_factory.mktemp("trace") / "tiny_train.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    ops, spans_ = trace_reduce.read_xplane(str(path))
    return facts, str(path), ops, spans_


def test_fixture_is_small():
    assert FIXTURE.stat().st_size + (DATA / "tiny_train.json").stat().st_size < 1_000_000


def test_fixture_planes_are_the_reducers(recorded):
    """The device plane and its ops line have the names the reducers
    look for, and the ops' instructions have name stacks."""
    facts, _, ops, _ = recorded
    device = [p for p in facts["planes"] if p.startswith(trace_reduce.DEVICE_PREFIX)]
    assert device and all("XLA Ops" in facts["planes"][p] for p in device)
    assert sorted(ops) == sorted(device)
    named = [n for n, _, _ in ops[device[0]] if trace_stages.instruction(n) in facts["stacks"]]
    assert len(named) > len(ops[device[0]]) / 2


def test_fixture_stages(recorded):
    """Every stage ran and the stages sum to the busy union."""
    facts, path, ops, spans_ = recorded
    r = trace_stages.reduce_stages(dict(sorted(ops.items())[:1]), spans_, facts["stacks"])
    assert all(r["stages"][s] > 0 for s in ROUND_STAGES)
    busy = trace_reduce.reduce_xplane(path, 1)["busy_s"]
    assert sum(r["stages"].values()) == pytest.approx(busy, rel=1e-9)
    assert r["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert r["stages"]["unscoped"] < 0.5 * busy


def test_fixture_sleeps_are_sync_gaps(recorded):
    """The recorder's sleeps show as the longest idle gaps, each inside
    ``chunk.sync``."""
    facts, path = recorded[:2]
    r = trace_reduce.reduce_xplane(path, 1)
    gaps = r["breakdown"]["idle_gaps"][:facts["calls"]]
    assert [g[0] for g in gaps] == ["chunk.sync"] * facts["calls"]
    assert all(g[1] >= facts["sleep_s"] for g in gaps)


def test_chunk_stacks_name_every_stage(small):
    """The name stacks of the chunk program a cell runs (CPU, small)."""
    from bench import harness
    from bench.kinds import federation

    cell = harness.load_cell("train.h128.i30")
    trainer = federation.make_trainer(cell)
    data, state, _ = federation.make_inputs(cell, trainer, 2**31 + 5)
    stacks = trace_stages.chunk_stacks(trainer, state, data, cell.config)
    assert {trace_stages.stage_of(s) for s in stacks.values()} == set(ROUND_STAGES) | {"unscoped"}
