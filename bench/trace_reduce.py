"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers.

* busy: the union of the intervals in which an XLA operation ran on a
  chip, inside the traced window, averaged over the chips;
* window: from the first to the last of the harness's own spans (host
  annotations around each call into the program);
* device_ops: the operations that took the most device time, by the
  names the trace gives them, in seconds per chip;
* idle_gaps: the longest gaps between device operations on the first
  chip, each labelled with the harness span open on the host at its
  middle.
"""
from __future__ import annotations

from collections import defaultdict

# the harness's spans, by which idle gaps are labelled
SPANS = ("chunk.dispatch", "chunk.sync")
DEVICE_PREFIX = "/device:TPU:"
# the line whose events are single device operations; where a trace has
# none, the whole programs' line; a device plane with neither is an error
OPS_LINES = ("XLA Ops", "XLA Modules")


def union(intervals):
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce_events(device_ops: dict, spans: list, top: int = 10) -> dict:
    """``device_ops``: chip -> list of ``(name, start_ns, end_ns)``;
    ``spans``: list of ``(name, start_ns, end_ns)`` host spans."""
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window = (hi - lo) * 1e-9
    chips = sorted(device_ops)
    busy, per_op = [], defaultdict(float)
    for chip in chips:
        ops = [(n, s, e) for n, s, e in device_ops[chip] if e > lo and s < hi]
        if not ops:
            # the host spans and the device events do not share a clock,
            # or nothing ran on this chip in the window
            raise ValueError(f"no operation of {chip} inside the host spans' window")
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for n, s, e in ops:
            per_op[n] += (min(e, hi) - max(s, lo)) * 1e-9 / len(chips)
    first = union(clip([(s, e) for _, s, e in device_ops[chips[0]]], lo, hi))
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    def label(mid):
        open_ = [(s, n) for n, s, e in spans if s <= mid < e]
        return max(open_)[1] if open_ else "host.other"

    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:top]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window,
        "breakdown": {
            "device_ops": sorted(([n, t] for n, t in per_op.items()),
                                 key=lambda x: -x[1])[:top],
            "idle_gaps": [[label((s + e) / 2), (e - s) * 1e-9] for s, e in longest],
        },
    }


def read_xplane(path: str):
    """Device op events per chip and the harness's spans from a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            pick = next((lines[n] for n in OPS_LINES if n in lines), None)
            if pick is None:
                raise ValueError(f"{plane.name} has none of the lines {OPS_LINES}: "
                                 f"{sorted(lines)}")
            device_ops[plane.name] = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in pick.events
            ]
        else:
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events if ev.name in SPANS]
    return device_ops, spans


def reduce_xplane(path: str, chips: int) -> dict | None:
    device_ops, spans = read_xplane(path)
    if not spans or not device_ops:
        return None
    return reduce_events(dict(sorted(device_ops.items())[:chips]), spans)
