"""The program's spans: ``jax.named_scope`` layers inside the round
executables, and the round loop's host spans in the profiler's trace.

Device side: every op of a round sits under one ``fedavg.*`` scope
(sample, assemble, client_update, encode, aggregate, apply), carried in the
op's ``op_name`` metadata, which the profiler writes beside each op on the
device. Host side: each round is a ``fedavg.round`` step (``fedavg.superstep``
for a chunk) holding ``fedavg.prepare``, ``fedavg.dispatch`` and
``fedavg.sync`` spans. None of it changes a result, profiler on or off.
"""
import re

import jax
import numpy as np
import pytest

from repro.core import FedAvgConfig, RoundEngine, quantize_codec
from repro.core.latency import LatencyModel
from repro.models import mnist_2nn

SIZES = (9, 24, 17, 40, 8, 33)
SCOPE = re.compile(r"fedavg\.[a-z_]+")
LOOP_SPANS = ("fedavg.prepare", "fedavg.dispatch", "fedavg.sync")


def _clients(sizes=SIZES, d=12, classes=5):
    rng = np.random.default_rng(0)
    return [
        (rng.normal(size=(n, d)).astype(np.float32),
         rng.integers(0, classes, n).astype(np.int32))
        for n in sizes
    ]


def _engine(**kw):
    model = mnist_2nn(n_classes=5, d_in=12)
    cfg = FedAvgConfig(C=0.5, E=2, B=8, lr=0.1, seed=3)
    return RoundEngine(model.loss, model.init(jax.random.PRNGKey(0)),
                       _clients(), cfg, **kw)


# The superstep's scan machinery, which belongs to no round's layer: the
# loop's result tuple, its counter and bound, the slice of each round's
# staged inputs, the write of each round's loss, and the constants, copies
# and tuple reads of the carry in the call around one round.
LOOP_OPS = re.compile(r"jit\([^)]*\)/while(/cond/lt|/body/(closed_call|"
                      r"dynamic_slice|dynamic_update_slice|add))?")


def _op_names(compiled_text):
    """The op_name of every instruction of the round's own program. Those of
    a reduce's, sort's or scatter's sub-computation and the entry's
    parameters have names relative to no ``jit(...)`` and are left out: they
    are no op of their own."""
    return [m for m in re.findall(r'op_name="([^"]*)"', compiled_text)
            if m.startswith("jit(")]


ROUND = {"fedavg.assemble", "fedavg.client_update", "fedavg.aggregate",
         "fedavg.apply"}

LOWERED = {
    "dense-R1": (dict(), 1, ROUND),
    "dense-R10": (dict(device_sampling=True), 10, ROUND | {"fedavg.sample"}),
    "q4-R1": (dict(codec=quantize_codec(4)), 1, ROUND | {"fedavg.encode"}),
    "q4-R10": (dict(device_sampling=True, codec=quantize_codec(4)), 10,
               ROUND | {"fedavg.sample", "fedavg.encode"}),
    "streamed-R1": (dict(pool="streamed"), 1, ROUND),
    "streamed-R10": (dict(pool="streamed", device_sampling=True), 10, ROUND),
}


@pytest.mark.parametrize("lane", sorted(LOWERED))
def test_round_executables_carry_the_layer_scopes(lane):
    kw, R, expected = LOWERED[lane]
    eng = _engine(**kw)
    names = _op_names(eng.lower_round(R).compile().as_text())
    found = set().union(*(SCOPE.findall(n) for n in names))
    assert found == expected
    # Placed once, at the call site: each op of a round sits under exactly
    # one scope; only the superstep's loop, around the rounds, has none.
    loop = [n for n in names if LOOP_OPS.fullmatch(n)]
    assert bool(loop) == (R > 1)
    assert all(len(set(SCOPE.findall(n))) == 1
               for n in names if not LOOP_OPS.fullmatch(n))
    # Inspection only, the streamed lane's staging included: the sampling
    # stream is where it was, so the run matches a twin that never lowered.
    twin = _engine(**kw)
    assert eng.round_idx == 0
    run_kw = dict(rounds_per_step=R) if R > 1 else {}
    ha, hb = eng.run(R, **run_kw), twin.run(R, **run_kw)
    assert [r.train_loss for r in ha.records] == [
        r.train_loss for r in hb.records]


def _host_spans(log_dir):
    """Every host event of the trace as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    from pathlib import Path

    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.name, ev.start_ns, ev.end_ns)
                           for ev in line.events)
    return out


def _children(spans, step):
    """The loop spans inside each ``step`` span, in time order."""
    steps = sorted((s, e) for n, s, e in spans if n == step)
    loop = sorted((s, e, n) for n, s, e in spans if n in LOOP_SPANS)
    return [[n for s, e, n in loop if s0 <= s and e <= e0]
            for s0, e0 in steps]


PLAIN = ["fedavg.prepare", "fedavg.dispatch", "fedavg.sync"]
# The streamed lane stages the next cohort while this one computes.
STREAMED = ["fedavg.prepare", "fedavg.dispatch", "fedavg.prepare",
            "fedavg.sync"]

TRACED = {
    "sync": (dict(), dict(), "fedavg.round", 3, PLAIN),
    "latency": (dict(latency=LatencyModel(mean_s=1.0)), dict(),
                "fedavg.round", 3, PLAIN),
    # 3 = 2 + 1: one span per chunk, the ragged one included.
    "superstep": (dict(device_sampling=True), dict(rounds_per_step=2),
                  "fedavg.superstep", 2, PLAIN),
    "streamed": (dict(pool="streamed"), dict(), "fedavg.round", 3, STREAMED),
    "streamed-superstep": (dict(pool="streamed", device_sampling=True),
                           dict(rounds_per_step=2), "fedavg.superstep", 2,
                           STREAMED),
}


@pytest.mark.parametrize("lane", sorted(TRACED))
def test_run_writes_round_steps_with_loop_spans(lane, tmp_path):
    eng_kw, run_kw, step, n_steps, children = TRACED[lane]
    eng = _engine(**eng_kw)
    eng.run(3, **run_kw)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.run(3, **run_kw)
    spans = _host_spans(tmp_path)
    assert _children(spans, step) == [children] * n_steps
    # The loop spans never overlap one another.
    loop = sorted((s, e) for n, s, e in spans if n in LOOP_SPANS)
    assert all(a[1] <= b[0] for a, b in zip(loop, loop[1:]))


@pytest.mark.parametrize("lane", ["sync", "superstep", "streamed"])
def test_results_are_bitwise_the_same_with_the_profiler_on(lane, tmp_path):
    eng_kw, run_kw, *_ = TRACED[lane]
    off, on = _engine(**eng_kw), _engine(**eng_kw)
    off.run(3, **run_kw)
    with jax.profiler.trace(str(tmp_path)):
        on.run(3, **run_kw)
    for a, b in zip(jax.tree.leaves(off.params), jax.tree.leaves(on.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [r.train_loss for r in off.history.records] == [
        r.train_loss for r in on.history.records]
