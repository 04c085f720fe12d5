"""The span reduction (``span_reduce.py``) on traces built by hand: device
self time by ``fedavg.*`` scope and by every named-scope segment, device
idle time inside the round loop's host spans (with each device moved onto
the host's clock), the per-round readings, and the per-layer readers that
take them from ``ctx["spans"]``."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import harness, span_reduce, trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def _trace(with_paths=True):
    # Window 0..100 ms. Device 0: an assemble gather 10-30, a while loop
    # 30-60 around a client_update conv 35-55 (the loop's own 10 ms carry
    # no scope), an op under client_update whose innermost scope is encode
    # 60-65, an op with no op_name 70-75. Device 1: an assemble gather 0-40.
    def op(name, s, e, path):
        return (name, s * MS, e * MS, path if with_paths and path else "")

    dev0 = [
        op("fusion.1", 10, 30, "jit(f)/fedavg.assemble/gather"),
        op("while.2", 30, 60, "jit(f)/while"),
        op("conv.3", 35, 55,
           "jit(f)/while/body/fedavg.client_update/transpose(jvp(conv))"),
        op("fusion.4", 60, 65, "jit(f)/fedavg.client_update/fedavg.encode/x"),
        op("copy.5", 70, 75, None),
    ]
    dev1 = [op("fusion.1", 0, 40, "jit(f)/fedavg.assemble/gather")]
    host = [
        ("bench.call", 0, 50 * MS),
        ("bench.call", 50 * MS, 100 * MS),
        ("fedavg.round", 0, 80 * MS),
        ("fedavg.prepare", -5 * MS, 12 * MS),   # starts before the window
        ("fedavg.dispatch", 12 * MS, 20 * MS),
        ("fedavg.sync", 55 * MS, 80 * MS),
        ("fedavg.prepare", 80 * MS, 90 * MS),
        ("device_get", 56 * MS, 79 * MS),
    ]
    return {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host


def _reduced(**kw):
    return span_reduce.reduce_events(*_trace(**kw), "bench.call")


def _busy_s(spans):
    return spans.window_s - spans.idle_s


def test_scope_time_goes_to_the_innermost_scope_averaged_over_devices():
    red = _reduced()
    assert red.scope_s["fedavg.assemble"] == pytest.approx((0.020 + 0.040) / 2)
    assert red.scope_s["fedavg.client_update"] == pytest.approx(0.020 / 2)
    assert red.scope_s["fedavg.encode"] == pytest.approx(0.005 / 2)
    # the while loop's own 10 ms and the op with no op_name
    assert red.scope_s[span_reduce.UNSCOPED] == pytest.approx(0.015 / 2)
    # self times: the scopes partition the busy time
    assert sum(red.scope_s.values()) == pytest.approx(_busy_s(red))


def test_segment_time_agrees_with_the_scope_time():
    red = _reduced()
    seg, scope = red.segment_s, red.scope_s
    # A fedavg scope with none nested in it holds what scope_s gives it;
    # client_update holds the encode op nested in it as well.
    for name in ("fedavg.assemble", "fedavg.encode"):
        assert seg[name] == pytest.approx(scope[name])
    assert seg["fedavg.client_update"] == pytest.approx(
        scope["fedavg.client_update"] + scope["fedavg.encode"])
    # Every op with an op_name is under jit(f): the busy time less the op
    # without one.
    assert seg["f"] == pytest.approx(_busy_s(red) - 0.005 / 2)
    # The loop's body op, not the loop's own time (the while op itself).
    assert seg["while"] == seg["body"] == pytest.approx(0.020 / 2)
    assert span_reduce.UNSCOPED not in seg
    assert _reduced(with_paths=False).segment_s == {}


@pytest.mark.parametrize("path, segments", [
    ("jit(f)/while/body/transpose(jvp(model.moe))/dot_general",
     {"f", "while", "body", "model.moe"}),
    ("jit(step)/fedavg.client_update/vmap()/while/body/jvp(model.mla)/tanh",
     {"step", "fedavg.client_update", "while", "body", "model.mla"}),
    ("jit(f)/fedavg.assemble/gather:", {"f", "fedavg.assemble"}),
    ("jit(f)/model.moe/model.moe/add", {"f", "model.moe"}),
    ("fusion", set()),
    ("", set()),
])
def test_segments_of(path, segments):
    assert span_reduce.segments_of(path) == segments


def test_span_idle_is_the_exact_overlap_of_idle_and_spans():
    red = _reduced()
    # dev0 idle 0-10, 65-70, 75-100; dev1 idle 40-100.
    # prepare (clipped to 0-12, and 80-90): dev0 10 + 10, dev1 10.
    # sync 55-80: dev0 5 + 5 (the gap 75-100 straddles sync and prepare),
    # dev1 25. dispatch 12-20: both devices busy.
    assert red.span_idle_s["fedavg.prepare"] == pytest.approx((0.020 + 0.010) / 2)
    assert red.span_idle_s["fedavg.sync"] == pytest.approx((0.010 + 0.025) / 2)
    assert red.span_idle_s["fedavg.dispatch"] == 0.0
    assert set(red.span_idle_s) == set(span_reduce.LOOP_SPANS)
    outside = red.idle_s - sum(red.span_idle_s.values())
    assert outside == pytest.approx(0.050 - 0.0325)


def test_span_idle_moves_each_device_onto_the_host_clock():
    dev, host = _trace()
    shifted = span_reduce.reduce_events(
        dev, host, "bench.call", {"/device:TPU:0": 5 * MS})
    # dev0 busy 15-70, 75-80 on the host's clock: idle 0-15, 70-75, 80-100.
    # prepare 0-12 and 80-90: 12 + 10; dispatch 12-20: 3; sync 55-80: 5.
    # dev1 is not moved: prepare 10, dispatch 0, sync 25.
    assert shifted.span_idle_s == pytest.approx({
        "fedavg.prepare": (0.022 + 0.010) / 2,
        "fedavg.dispatch": 0.003 / 2,
        "fedavg.sync": (0.005 + 0.025) / 2})
    plain = _reduced()
    for field in ("window_s", "idle_s", "scope_s"):
        assert getattr(shifted, field) == getattr(plain, field)


@pytest.mark.parametrize("modules, enqueued, shift", [
    # the device put run 2 four units before the host enqueued it
    ([(1, 100), (2, 196), (3, 300)], {1: 90, 2: 200, 3: 299}, 4),
    # every program starts after its enqueue: no evidence of skew
    ([(1, 100), (2, 200)], {1: 90, 2: 150}, 0),
    # runs seen on one side only are skipped
    ([(1, 100), (5, 10)], {1: 103, 6: 900}, 3),
    ([(1, 100)], {}, 0),
])
def test_clock_shift_ns(modules, enqueued, shift):
    assert span_reduce.clock_shift_ns(modules, enqueued) == shift


def test_window_and_idle_agree_with_the_trace_reduction():
    # The same events through the benchmark's own reduction: the window and
    # the idle time (``device_idle_ms_per_round``'s reading) are its.
    red = _reduced()
    dev, host = _trace()
    base = trace_reduce.reduce_events(dev, host, "bench.call")
    assert red.window_s == base.window_s
    assert red.idle_s == pytest.approx(base.window_s - base.mean_busy_s)
    without = _reduced(with_paths=False)
    assert without.scope_s == {span_reduce.UNSCOPED: pytest.approx(_busy_s(red))}
    assert without.span_idle_s == red.span_idle_s


def test_a_trace_without_program_spans_has_no_span_idle():
    dev, host = _trace()
    host = [h for h in host if not h[0].startswith("fedavg.")]
    red = span_reduce.reduce_events(dev, host, "bench.call")
    assert red.span_idle_s == {}
    assert span_reduce.per_round_ms(red, 2)["idle_outside_ms_per_round"] is None


def test_the_window_defaults_to_the_program_steps():
    # Without the harness's annotation the program's own round steps bound
    # the window: 0-80 ms here, so only the first prepare counts, and of it
    # only dev0's idle 0-10 (dev1 is busy 0-40).
    dev, host = _trace()
    red = span_reduce.reduce_events(dev, host)
    assert red.window_s == pytest.approx(0.080)
    assert red.span_idle_s["fedavg.prepare"] == pytest.approx(0.010 / 2)
    with pytest.raises(ValueError, match="no missing host span"):
        span_reduce.reduce_events(dev, host, "missing")


@pytest.mark.parametrize("path, scope", [
    ("jit(_engine_round)/fedavg.aggregate/jit(_aggregate_impl)/pallas_call",
     "fedavg.aggregate"),
    ("jit(f)/while/body/fedavg.sample/jit(_uniform)/threefry2x32",
     "fedavg.sample"),
    ("jit(f)/fedavg.client_update/fedavg.encode/x", "fedavg.encode"),
    ("jit(fedavg_aggregate)/mul", span_reduce.UNSCOPED),
    ("", span_reduce.UNSCOPED),
])
def test_scope_of(path, scope):
    assert span_reduce.scope_of(path) == scope


# Two device ops whose op_name is the tf_op stat of their event metadata
# (beside other stats), one with none, as the profiler writes them on a
# TPU; 10 ms ticks from 0.
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000000 duration_ps: 20000000000 }
    events { metadata_id: 2 offset_ps: 40000000000 duration_ps: 10000000000 }
    events { metadata_id: 3 offset_ps: 60000000000 duration_ps: 5000000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
    stats { metadata_id: 7 str_value: "jit(f)/fedavg.assemble/gather:" } } }
  event_metadata { key: 2 value { id: 2
    name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %q)"
    stats { metadata_id: 9 uint64_value: 8 }
    stats { metadata_id: 10 double_value: 1.5 }
    stats { metadata_id: 7
            str_value: "jit(f)/while/body/fedavg.client_update/copy:" } } }
  event_metadata { key: 3 value { id: 3
    name: "%copy-start.3 = f32[8]{0} copy-start(f32[8]{0} %r)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 9 value { id: 9 name: "flops" } }
  stat_metadata { key: 10 value { id: 10 name: "model_flops" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 12000000000 }
    events { metadata_id: 3 offset_ps: 70000000000 duration_ps: 20000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.call" } }
  event_metadata { key: 2 value { id: 2 name: "fedavg.prepare" } }
  event_metadata { key: 3 value { id: 3 name: "fedavg.sync" } }
}
"""


def test_reduce_trace_reads_op_names_from_the_event_metadata(tmp_path):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    assert span_reduce.read_op_names(raw) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)":
            "jit(f)/fedavg.assemble/gather:",
        "%copy.2 = f32[8]{0} copy(f32[8]{0} %q)":
            "jit(f)/while/body/fedavg.client_update/copy:",
    }}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    red = span_reduce.reduce_trace(path, "bench.call")
    assert red.scope_s == pytest.approx({
        "fedavg.assemble": 0.020, "fedavg.client_update": 0.010,
        span_reduce.UNSCOPED: 0.005})
    # idle 0-10, 30-40, 50-60, 65-100: prepare 0-12 holds 10 ms of it,
    # sync 70-90 holds 20 ms.
    assert red.span_idle_s == pytest.approx(
        {"fedavg.prepare": 0.010, "fedavg.sync": 0.020})
    assert red.idle_s == pytest.approx(0.065)


def test_main_prints_the_per_round_readings(tmp_path, capsys):
    from jax.profiler import ProfileData

    (tmp_path / "run").mkdir()
    (tmp_path / "run" / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert span_reduce.main([str(tmp_path), "--rounds", "2",
                             "--window", "bench.call"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["assemble_ms_per_round"] == pytest.approx(10.0)
    assert out["local_update_ms_per_round"] == pytest.approx(5.0)
    assert out["encode_ms_per_round"] is None
    assert out["prepare_idle_ms_per_round"] == pytest.approx(5.0)
    assert out["dispatch_idle_ms_per_round"] is None
    assert out["sync_idle_ms_per_round"] == pytest.approx(10.0)
    # 65 ms idle, 30 of it inside the spans
    assert out["idle_outside_ms_per_round"] == pytest.approx(17.5)
    assert out["window_s"] == pytest.approx(0.100)


# Two programs print an instruction alike (the profiler keys an op's
# metadata by that text) under different op_names; a third text comes
# twice with one op_name.
CLASH = """
planes {
  id: 1
  name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1
    name: "%copy.1 = s32[10]{0} copy(s32[10]{0} %a)"
    stats { metadata_id: 7 str_value: "jit(f)/fedavg.sample/copy" } } }
  event_metadata { key: 2 value { id: 2
    name: "%copy.1 = s32[10]{0} copy(s32[10]{0} %a)"
    stats { metadata_id: 7 str_value: "jit(g)/fedavg.assemble/copy" } } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)"
    stats { metadata_id: 7 str_value: "jit(f)/fedavg.apply/add" } } }
  event_metadata { key: 4 value { id: 4
    name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)"
    stats { metadata_id: 7 str_value: "jit(f)/fedavg.apply/add" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
"""


def test_a_text_with_two_op_names_is_left_unscoped():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(CLASH)
    assert span_reduce.read_op_names(raw) == {"/device:TPU:0": {
        "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)": "jit(f)/fedavg.apply/add",
    }}


# The device's clock runs 4 ms early: run 7 starts on the device at 10 ms,
# 4 ms before the host enqueues it; run 8 starts 2 ms after its enqueue.
SKEWED = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000000 duration_ps: 20000000000 }
    events { metadata_id: 1 offset_ps: 40000000000 duration_ps: 10000000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 10000000000 duration_ps: 20000000000
             stats { metadata_id: 20 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 40000000000 duration_ps: 10000000000
             stats { metadata_id: 20 int64_value: 8 } }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "jit_round(1)" } }
  stat_metadata { key: 20 value { id: 20 name: "run_id" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 14000000000 }
    events { metadata_id: 3 offset_ps: 14000000000 duration_ps: 2000000000 }
    events { metadata_id: 4 offset_ps: 16000000000 duration_ps: 24000000000 }
  }
  lines {
    id: 2 name: "pjrt-tpu-tasks" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 14000000000 duration_ps: 100000000
             stats { metadata_id: 20 int64_value: 7 } }
    events { metadata_id: 5 offset_ps: 38000000000 duration_ps: 100000000
             stats { metadata_id: 20 int64_value: 8 } }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.call" } }
  event_metadata { key: 2 value { id: 2 name: "fedavg.prepare" } }
  event_metadata { key: 3 value { id: 3 name: "fedavg.dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "fedavg.sync" } }
  event_metadata { key: 5 value { id: 5 name: "DoEnqueueProgram" } }
  stat_metadata { key: 20 value { id: 20 name: "run_id" } }
}
"""


def test_reduce_trace_moves_the_device_onto_the_host_clock(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SKEWED))
    red = span_reduce.reduce_trace(path, "bench.call")
    # On the host's clock the ops run 14-34 and 44-54: idle 0-14, 34-44,
    # 54-100. prepare 0-14 holds 14 ms, dispatch 14-16 none, sync 16-40 6.
    # On the device's own clock it would be 10, 0 and 10.
    assert red.span_idle_s == pytest.approx(
        {"fedavg.prepare": 0.014, "fedavg.dispatch": 0.0,
         "fedavg.sync": 0.006})
    # The idle time itself keeps the device's own clock.
    assert red.idle_s == pytest.approx(0.070)


READINGS = {
    "assemble_ms_per_round": 30.0 / 2,
    "local_update_ms_per_round": 10.0 / 2,
    "encode_ms_per_round": 2.5 / 2,
    "prepare_idle_ms_per_round": 15.0 / 2,
    "dispatch_idle_ms_per_round": 0.0,
    "sync_idle_ms_per_round": 17.5 / 2,
    "idle_outside_ms_per_round": 17.5 / 2,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_per_round_ms(name):
    assert span_reduce.per_round_ms(_reduced(), 2)[name] == pytest.approx(
        READINGS[name])
    assert span_reduce.per_round_ms(_reduced(), 0)[name] is None


@pytest.mark.parametrize("name", sorted(READINGS))
def test_per_round_ms_is_silent_without_its_span(name):
    # A program with no spans: every op unscoped, no loop spans.
    dev, host = _trace(with_paths=False)
    host = [h for h in host if not h[0].startswith("fedavg.")]
    red = span_reduce.reduce_events(dev, host, "bench.call")
    assert span_reduce.per_round_ms(red, 2)[name] is None


def test_readers_take_the_span_readings_from_ctx(tmp_path):
    """``harness.per_layer_metrics`` on the hand-built trace: each cell's
    readers find ``ctx["spans"]``; a reading whose scope or span the trace
    lacks is left out."""
    from jax.profiler import ProfileData

    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    chip = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    bench = harness.load_benchmark()
    got = {}
    for name in ("2nn_fedsgd_rounds", "2nn_fedsgd_q4_m100"):
        cell = harness.load_cell(name, bench)
        out, _ = harness.per_layer_metrics(cell, tmp_path, 0.1, 2, chip)
        got[name] = {k: v["value"] for k, v in out.items()}
        assert {v["unit"] for v in out.values()} <= {"ms", "%"}
    # 35 ms busy of 100 (assemble 20, client_update 10, unscoped 5); idle
    # 10 ms inside prepare and 20 inside sync; two rounds.
    assert got["2nn_fedsgd_rounds"] == pytest.approx({
        "device_idle_ms_per_round": 32.5, "assemble_ms_per_round": 10.0,
        "local_update_ms_per_round": 5.0, "prepare_idle_ms_per_round": 5.0,
        "sync_idle_ms_per_round": 10.0})
    q4 = got["2nn_fedsgd_q4_m100"]
    assert "encode_ms_per_round" not in q4 and "aggregate_share" not in q4
    assert q4["assemble_ms_per_round"] == pytest.approx(10.0)
    assert q4["device_idle_share"] == pytest.approx(65.0)
