"""Reduce a profiler trace by the program's own spans.

The program marks the layers of a FedAvg round in the profiler's trace
(``docs/engine.md`` "Tracing a run"): each device op of a round carries a
``fedavg.*`` ``jax.named_scope`` segment in its ``op_name``, and the host
loop wraps each round's steps in ``fedavg.prepare`` / ``fedavg.dispatch`` /
``fedavg.sync`` spans. Over the window from the start of the first
``window`` host span to the end of the last (the harness's ``bench.call``
annotation, or the program's own ``fedavg.round`` / ``fedavg.superstep``
steps), this gives (:class:`Spans`):

- ``scope_s``: device self time, averaged over devices, by the innermost
  ``fedavg.*`` segment of each op's ``op_name``, ``"unscoped"`` for ops
  under none;
- ``segment_s``: device self time, averaged over devices, under each
  segment of the ops' ``op_name`` (:func:`segments_of`), whichever layer
  opened it: a reader of a model's own scope, such as ``model.moe``, reads
  its key here;
- ``span_idle_s``: idle device time, averaged over devices, inside each of
  the loop spans: the exact overlap of the idle intervals with the spans,
  once each device's intervals are moved onto the host's clock
  (:func:`clock_shift_ns`). A span name absent from the window is absent
  here;
- ``idle_s``: the window less the device's busy time, averaged over
  devices, on the device's own clock (``device_idle_ms_per_round``'s
  reading).

The profiler aligns a device's clock with the host's only to within a
millisecond or two, and not the same in every run: on a TPU v5e it has put
every program's start on the device 0.9-1.6 ms before the host began to
enqueue it. Device self times do not depend on that; idle time set against
host spans of about that length does.

Run on a kept trace (prints one JSON object, with the six per-round
readings of :func:`per_round_ms`)::

    python3 benchmarks/chip/span_reduce.py <trace dir or .xplane.pb> \\
        --rounds <rounds in the window> [--window bench.call]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, FrozenSet, Optional, Sequence

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, OPS_LINE, _merge, _self_times, find_xplane,
)

MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
RUN_ID = "run_id"
OP_NAME_STAT = "tf_op"
SCOPE = re.compile(r"fedavg\.[A-Za-z_]+")
TRANSFORM = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\((.*)\)")
UNSCOPED = "unscoped"
LOOP_SPANS = ("fedavg.prepare", "fedavg.dispatch", "fedavg.sync")
PROGRAM_STEPS = ("fedavg.round", "fedavg.superstep")

# Per-round readings: name -> (field, key). Each is the field's seconds
# under that key, in milliseconds a round.
PER_ROUND = {
    "assemble_ms_per_round": ("scope_s", "fedavg.assemble"),
    "local_update_ms_per_round": ("scope_s", "fedavg.client_update"),
    "encode_ms_per_round": ("scope_s", "fedavg.encode"),
    "prepare_idle_ms_per_round": ("span_idle_s", "fedavg.prepare"),
    "dispatch_idle_ms_per_round": ("span_idle_s", "fedavg.dispatch"),
    "sync_idle_ms_per_round": ("span_idle_s", "fedavg.sync"),
}


@dataclasses.dataclass
class Spans:
    window_s: float
    idle_s: float
    scope_s: Dict[str, float]
    span_idle_s: Dict[str, float]
    segment_s: Dict[str, float]


def scope_of(op_path: str) -> str:
    """``jit(f)/while/body/fedavg.assemble/jit(g)/gather`` ->
    ``fedavg.assemble``: the innermost ``fedavg.*`` segment of an
    operation's ``op_name``, or ``"unscoped"``."""
    found = SCOPE.findall(op_path)
    return found[-1] if found else UNSCOPED


def segments_of(op_path: str) -> FrozenSet[str]:
    """``jit(f)/while/body/transpose(jvp(model.moe))/dot_general`` ->
    ``{f, while, body, model.moe}``: every segment of an operation's
    ``op_name`` but the last (the operation itself), without the transforms
    JAX wraps round a scope, so that a scope's forward and backward ops
    count under one name."""
    out = set()
    for part in op_path.split("/")[:-1]:
        while (m := TRANSFORM.fullmatch(part)):
            part = m.group(1)
        if part:
            out.add(part)
    return frozenset(out)


def per_round_ms(spans: Spans, rounds: int) -> Dict[str, Optional[float]]:
    """The six per-round readings, ``None`` where the trace holds no op
    under the scope or no such span (a program without spans), and the
    idle left outside the loop spans (``idle_outside_ms_per_round``: the
    device's idle a round less the three span readings)."""
    if rounds <= 0:
        return {name: None for name in [*PER_ROUND, "idle_outside_ms_per_round"]}
    out = {}
    for name, (field, key) in PER_ROUND.items():
        s = getattr(spans, field).get(key)
        out[name] = None if s is None else 1e3 * s / rounds
    out["idle_outside_ms_per_round"] = (
        1e3 * (spans.idle_s - sum(spans.span_idle_s.values())) / rounds
        if spans.span_idle_s else None)
    return out


# -- op_name from the raw trace -------------------------------------------
#
# The profiler keeps each device op's ``op_name`` as the ``tf_op`` stat of
# the op's event *metadata*, which ``ProfileData`` does not expose (an XLA
# op event's own stats hold only its offsets, and its name, the HLO text,
# no metadata). The few XSpace fields needed are read straight from the
# protobuf wire format (tsl/profiler/protobuf/xplane.proto):
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
# .str_value = 5.

def _varint(buf, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for varints, a memoryview for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_values(entry):
    return [v for f, v in _fields(entry) if f == 2]


def read_op_names(raw: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, each op's event name (its HLO text) -> its
    ``op_name``, from a serialized XSpace. Ops without one are left out, and
    so is a text that two entries give different ``op_name``s (the same
    instruction printed alike in two programs): an op read by its text
    alone could be either, so it counts as unscoped."""
    out = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        name, event_meta, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                event_meta.extend(_map_values(v))
            elif f == 5:
                for meta in _map_values(v):
                    d = dict(_fields(meta))
                    stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        if not DEVICE_PLANE.match(name):
            continue
        table, clash = {}, set()
        for meta in event_meta:
            ev_name, value = "", None
            for f, v in _fields(meta):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT and 5 in stat:
                        value = bytes(stat[5]).decode()
            if value and table.setdefault(ev_name, value) != value:
                clash.add(ev_name)
        out[name] = {k: v for k, v in table.items() if k not in clash}
    return out


def clock_shift_ns(module_starts, enqueue_starts) -> int:
    """How far to move a device's times onto the host's clock: the least
    shift that puts every program's start on the device at or after the
    host's ``DoEnqueueProgram`` for it, 0 where none starts before.

    ``module_starts``: ``[(run_id, start_ns)]`` from the device's
    ``XLA Modules`` line; ``enqueue_starts``: ``{run_id: start_ns}``, the
    earliest enqueue of each run on the host. Runs found on one side only
    are skipped.
    """
    early = [enqueue_starts[r] - s for r, s in module_starts
             if r in enqueue_starts]
    return max(0, max(early)) if early else 0


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_events(device_ops, host_spans,
                  window: Sequence[str] = PROGRAM_STEPS,
                  clock_shift=None) -> Spans:
    """Core of :func:`reduce_trace` over plain tuples, so tests can build a
    trace by hand.

    ``device_ops``: ``{device: [(name, start_ns, end_ns, op_name)]}``, with
    ``""`` for an op without one; ``host_spans``: ``[(name, start_ns,
    end_ns)]`` from every host thread; ``window``: the names of the host
    spans whose extent is the window. An operation's time is its self time:
    what ops nested in it took is theirs. ``clock_shift``: ``{device: ns}``
    added to that device's times before they meet the loop spans
    (``span_idle_s`` only).
    """
    if isinstance(window, str):
        window = (window,)
    marks = [(s, e) for n, s, e in host_spans if n in window]
    if not marks:
        raise ValueError(f"no {' or '.join(window)} host span in the trace")
    w0 = min(s for s, _ in marks)
    w1 = max(e for _, e in marks)
    loop_spans = {
        name: _merge([(max(s, w0), min(e, w1)) for n, s, e in host_spans
                      if n == name and min(e, w1) > max(s, w0)])
        for name in LOOP_SPANS
    }
    loop_spans = {k: v for k, v in loop_spans.items() if v}
    scope_ns, span_idle_ns = defaultdict(float), defaultdict(float)
    segment_ns = defaultdict(float)
    idle_ns = 0.0
    n_dev = max(len(device_ops), 1)
    for device, events in device_ops.items():
        inside = [(n, max(s, w0), min(e, w1), p) for n, s, e, p in events
                  if min(e, w1) > max(s, w0)]
        for (_, _, _, path), dt in zip(inside, _self_times(inside)):
            scope_ns[scope_of(path)] += dt / n_dev
            for seg in segments_of(path):
                segment_ns[seg] += dt / n_dev
        merged = _merge([(s, e) for _, s, e, _ in inside])
        idle_ns += ((w1 - w0) - sum(e - s for s, e in merged)) / n_dev
        shift = (clock_shift or {}).get(device, 0)
        if shift:
            merged = [(max(s, w0), min(e, w1)) for s, e in _merge(
                [(s + shift, e + shift) for _, s, e, _ in events])
                if min(e, w1) > max(s, w0)]
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        idle = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        for name, ivs in loop_spans.items():
            span_idle_ns[name] += _overlap_ns(idle, ivs) / n_dev
    return Spans(
        window_s=(w1 - w0) * 1e-9,
        idle_s=idle_ns * 1e-9,
        scope_s={k: v * 1e-9 for k, v in scope_ns.items()},
        span_idle_s={k: v * 1e-9 for k, v in span_idle_ns.items()},
        segment_s={k: v * 1e-9 for k, v in segment_ns.items()},
    )


def reduce_trace(path, window: Sequence[str] = PROGRAM_STEPS) -> Spans:
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    op_names = read_op_names(path.read_bytes())
    device_ops, host_spans, modules, enqueued = {}, [], {}, {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            names = op_names.get(plane.name, {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.end_ns),
                         names.get(ev.name, ""))
                        for ev in line.events
                    ]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (dict(ev.stats).get(RUN_ID), int(ev.start_ns))
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    t = int(ev.start_ns)
                    host_spans.append((ev.name, t, int(ev.end_ns)))
                    if ev.name == ENQUEUE:
                        run = dict(ev.stats).get(RUN_ID)
                        enqueued[run] = min(t, enqueued.get(run, t))
    if not device_ops:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in {path}")
    enqueued.pop(None, None)
    shift = {dev: clock_shift_ns(starts, enqueued)
             for dev, starts in modules.items()}
    return reduce_events(device_ops, host_spans, window, shift)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--rounds", type=int, required=True,
                    help="FedAvg rounds run inside the window")
    ap.add_argument("--window", action="append",
                    help="host span(s) that bound the window (default: "
                         + ", ".join(PROGRAM_STEPS) + ")")
    args = ap.parse_args(argv)
    spans = reduce_trace(args.trace, tuple(args.window or PROGRAM_STEPS))
    print(json.dumps({**dataclasses.asdict(spans),
                      **per_round_ms(spans, args.rounds)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
