"""Reduce a profiler trace to the numbers the per-layer metrics read.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes. Device planes are
named ``/device:<KIND>:<i>``; their ``XLA Ops`` line holds one event per
operation run on the device. Host planes (``/host:...``) hold the host's
spans, among them the benchmark's own annotation around each timed call.

Output (:class:`Reduced`), over the window from the start of the first
annotated call to the end of the last:

- ``busy_s``: per device, the union of its operations' intervals;
- ``op_s``: per operation (its HLO instruction name, which for a Pallas
  kernel starts with the jitted function around the ``pallas_call``), its
  self time on the device, averaged over devices;
- ``op_text``: per operation, its HLO text without layouts, to read;
- ``gap_s``: idle device time, averaged over devices, by the innermost host
  span under each gap's midpoint (``"no host span"`` where there is none).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
NO_HOST_SPAN = "no host span"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: List[float]
    op_s: Dict[str, float]
    op_text: Dict[str, str]
    gap_s: Dict[str, float]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")


def _short(hlo_text: str, width: int = 160) -> str:
    """The HLO text without layouts, cut to ``width`` characters."""
    return _LAYOUT.sub("", hlo_text)[:width]


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` -> ``fusion.3``:
    the device trace names each operation by its HLO instruction text."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(events):
    """Per event, its duration less the time of the events nested in it (a
    ``while`` op encloses the ops of its body on the same line)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [e - s for _, s, e, _ in events]
    stack = []
    for i in order:
        _, s, e, _ = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return self_ns


def reduce_events(device_ops, host_spans, annotation: str) -> Reduced:
    """Core of :func:`reduce_trace` over plain tuples, so tests can build a
    trace by hand.

    ``device_ops``: ``{device: [(name, start_ns, end_ns, text)]}``;
    ``host_spans``: ``[(name, start_ns, end_ns)]`` from every host thread.
    An operation's time is its self time: what ops nested in it took is
    theirs.
    """
    marks = [(s, e) for n, s, e in host_spans if n == annotation]
    if not marks:
        raise ValueError(f"no {annotation!r} host span in the trace")
    w0 = min(s for s, _ in marks)
    w1 = max(e for _, e in marks)
    spans = sorted(((s, e, n) for n, s, e in host_spans
                    if n != annotation and e > w0 and s < w1))
    busy, op_ns, op_text = [], defaultdict(float), {}
    gap_ns = defaultdict(float)
    n_dev = max(len(device_ops), 1)
    for events in device_ops.values():
        inside = [(n, max(s, w0), min(e, w1), t) for n, s, e, t in events
                  if min(e, w1) > max(s, w0)]
        for (name, _, _, text), dt in zip(inside, _self_times(inside)):
            op_ns[name] += dt / n_dev
            op_text.setdefault(name, text)
        merged = _merge([(s, e) for _, s, e, _ in inside])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        # Sweep the gaps in time order. ``open_`` holds spans in start
        # order; one that has ended before a gap's midpoint never covers a
        # later gap, so it is dropped from the end as the sweep passes it.
        # The last span left open is the innermost under the midpoint.
        open_, nxt = [], 0
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2
            while nxt < len(spans) and spans[nxt][0] <= mid:
                open_.append(spans[nxt])
                nxt += 1
            while open_ and open_[-1][1] < mid:
                open_.pop()
            name = open_[-1][2] if open_ else NO_HOST_SPAN
            gap_ns[name] += (ge - gs) / n_dev
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy,
        op_s={k: v * 1e-9 for k, v in op_ns.items()},
        op_text=op_text,
        gap_s={k: v * 1e-9 for k, v in gap_ns.items()},
    )


def reduce_trace(path, annotation: str) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops, host_spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (op_name(ev.name), int(ev.start_ns), int(ev.end_ns),
                         _short(ev.name))
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (ev.name, int(ev.start_ns), int(ev.end_ns))
                    for ev in line.events
                )
    if not device_ops:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in {path}")
    return reduce_events(device_ops, host_spans, annotation)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took the
    most (self) time, named by their HLO text without layouts, and the
    longest idle stretches by host span, in seconds."""
    def biggest(d):
        return sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[red.op_text.get(k) or k, v] for k, v in biggest(red.op_s)],
        "idle_gaps": [[k, v] for k, v in biggest(red.gap_s)],
    }
