"""Self time per device operation and device time per round stage, from
a profiler trace (``.xplane.pb``).

The ops line of a TPU's plane holds ``while`` and ``conditional`` ops
whose intervals enclose the ops of their bodies, so summing durations
counts nested time twice.  Here every instant of the window on which
some op ran is given to exactly one op: the innermost one open then (the
one that started last; of two that start together, the shorter).  For
ops that nest, an op's share is its clipped duration minus the union of
the ops it encloses, its self time; the shares of one chip sum to its
busy union.

Each op's stage is the innermost ``round.<stage>`` component of its
name stack (the ``op_name`` the compiled op carries from the program's
``jax.named_scope``, ``repro.core.gluadfl.ROUND_STAGES``), whatever
transform wraps it (``round.local_step/transpose(jvp())/...``); an op
with no stage component, or none known, is ``unscoped``.  A fusion
whose name stack lists several (``a;b``) takes the first.

The TPU's trace gives an op's event no name stack: its name is the
instruction's HLO text without metadata (``%fusion.288 = f32[...]
fusion(...)``) and its stats are times.  So the name stacks come from
the compiled program's HLO text (``compiled.as_text()``), read once by
the caller, by instruction name.  The events and spans are the ones
``bench/trace_reduce.read_xplane`` reads.
"""
from __future__ import annotations

import heapq
import re
from collections import defaultdict

from repro.core.gluadfl import ROUND_STAGES

from bench.trace_reduce import clip

UNSCOPED = "unscoped"
_STAGE = re.compile(r"\bround\.([A-Za-z0-9_]+)")
_INSTR = re.compile(r"%?([\w.-]+)")
_OP_NAME = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = .*\bmetadata=\{op_name=\"([^\"]*)\"")


def instruction(event_name: str) -> str:
    """The instruction an ops-line event ran (``%fusion.288 = ...``)."""
    return _INSTR.match(event_name).group(1)


def hlo_stacks(text: str) -> dict:
    """Instruction name -> ``op_name``, from a compiled program's HLO text."""
    return dict(m.groups() for m in map(_OP_NAME.match, text.splitlines()) if m)


def chunk_stacks(trainer, state, data, config) -> dict:
    """``hlo_stacks`` of the chunk program that ``trainer.train_chunk``
    runs on these arguments (compiled once more, or found in a cache)."""
    lowered = trainer._chunk_jit.lower(
        state, *data, None, None, batch_size=config["batch_size"], chunk=config["chunk"],
        eval_every=0, eval_fn=None)
    return hlo_stacks(lowered.compile().as_text())


def stage_of(stack: str) -> str:
    """The innermost ``round.<stage>`` of a name stack."""
    found = [s for s in _STAGE.findall(stack.split(";")[0]) if s in ROUND_STAGES]
    return found[-1] if found else UNSCOPED


def self_times(ops: list, lo: int, hi: int) -> list:
    """``ops``: one chip's ``(start_ns, end_ns)`` pairs.  Each op's share
    of ``[lo, hi]`` in ns, in the order given."""
    spans = clip(ops, lo, hi)
    keep = [i for i, (s, e) in enumerate(ops) if e > lo and s < hi]
    # rank: later start is further in; of equal starts the shorter is
    rank = sorted(range(len(spans)), key=lambda j: (spans[j][0], -spans[j][1]))
    points = sorted({x for iv in spans for x in iv})
    out = [0] * len(ops)
    heap, nxt = [], 0
    for a, b in zip(points, points[1:]):
        while nxt < len(rank) and spans[rank[nxt]][0] <= a:
            heapq.heappush(heap, (-nxt, rank[nxt]))
            nxt += 1
        while heap and spans[heap[0][1]][1] <= a:
            heapq.heappop(heap)
        if heap:
            out[keep[heap[0][1]]] += b - a
    return out


def reduce_stages(device_ops: dict, spans: list, stacks: dict, top: int = 10) -> dict:
    """``device_ops``: chip -> list of ``(name, start_ns, end_ns)``;
    ``spans``: the harness's host spans, whose extent is the window;
    ``stacks``: instruction name -> name stack (``hlo_stacks``).
    Seconds per chip: ``busy_s``, ``stages`` (every stage, ``unscoped``
    too, summing to ``busy_s``) and ``device_ops`` by self time."""
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    per_stage = dict.fromkeys(ROUND_STAGES + (UNSCOPED,), 0.0)
    per_op = defaultdict(float)
    chips = sorted(device_ops)
    for chip in chips:
        ops = device_ops[chip]
        own = self_times([(s, e) for _, s, e in ops], lo, hi)
        for (name, _, _), t in zip(ops, own):
            per_stage[stage_of(stacks.get(instruction(name), ""))] += t * 1e-9 / len(chips)
            per_op[name] += t * 1e-9 / len(chips)
    return {
        "busy_s": sum(per_stage.values()),
        "stages": per_stage,
        "device_ops": sorted(([n, t] for n, t in per_op.items() if t > 0),
                             key=lambda x: -x[1])[:top],
    }
