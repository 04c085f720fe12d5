"""The trace reduction on a trace built by hand: busy union, idle gaps and
their host spans, per-operation totals, the per-layer readers."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import harness, trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def _trace():
    # Window 0..100 ms (two annotated calls). Device 0 runs overlapping
    # ops 10-30 and 20-40 (busy 10-40), then 60-70; one op starts before
    # the window and one ends after it. Device 1 runs 0-50.
    dev0 = [
        ("early", -5 * MS, 5 * MS, ""),
        ("fusion.1", 10 * MS, 30 * MS, "jit(f)/mul"),
        ("fusion.1", 20 * MS, 40 * MS, "jit(f)/mul"),
        ("_aggregate_impl.3", 60 * MS, 70 * MS,
         "jit(f)/jit(_aggregate_impl)/pallas_call"),
        ("late", 95 * MS, 110 * MS, ""),
    ]
    dev1 = [("while.2", 0, 50 * MS, "while"),
            ("fusion.1", 0, 20 * MS, "jit(f)/mul"),
            ("fusion.1", 30 * MS, 50 * MS, "jit(f)/mul")]
    host = [
        ("bench.call", 0, 50 * MS),
        ("bench.call", 50 * MS, 100 * MS),
        ("dispatch", 40 * MS, 58 * MS),
        ("device_get", 70 * MS, 96 * MS),
    ]
    return {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host


def test_busy_union_clips_to_window_and_merges_overlaps():
    red = trace_reduce.reduce_events(*_trace(), "bench.call")
    assert red.window_s == pytest.approx(0.1)
    # dev0: 0-5 + 10-40 + 60-70 + 95-100 = 50 ms; dev1: 50 ms
    assert red.busy_s == pytest.approx([0.050, 0.050])
    assert red.mean_busy_s == pytest.approx(0.050)


def test_op_totals_are_averaged_over_devices():
    red = trace_reduce.reduce_events(*_trace(), "bench.call")
    # fusion.1: dev0 20 + 20 ms (overlapping ops count each), dev1 40 ms
    # nested in while.2, whose self time is what is left of its 50 ms.
    assert red.op_s["fusion.1"] == pytest.approx((0.040 + 0.040) / 2)
    assert red.op_s["while.2"] == pytest.approx(0.010 / 2)
    assert red.op_s["_aggregate_impl.3"] == pytest.approx(0.010 / 2)
    assert red.op_s["early"] == pytest.approx(0.005 / 2)
    assert "pallas_call" in red.op_text["_aggregate_impl.3"]


def test_idle_gaps_go_to_the_innermost_host_span():
    red = trace_reduce.reduce_events(*_trace(), "bench.call")
    # dev0 gaps: 5-10 (no span but the annotation), 40-60 (mid 50:
    # dispatch), 70-95 (mid 82.5: device_get); dev1 gap 50-100 (mid 75:
    # device_get). Averaged over the two devices.
    assert red.gap_s[trace_reduce.NO_HOST_SPAN] == pytest.approx(0.005 / 2)
    assert red.gap_s["dispatch"] == pytest.approx(0.020 / 2)
    assert red.gap_s["device_get"] == pytest.approx((0.025 + 0.050) / 2)
    total_gap = sum(red.gap_s.values())
    assert total_gap == pytest.approx(red.window_s - red.mean_busy_s)


def test_breakdown_lists_the_largest_first():
    red = trace_reduce.reduce_events(*_trace(), "bench.call")
    bd = trace_reduce.breakdown(red, top=2)
    # Named by their HLO text, largest self time first.
    assert [n for n, _ in bd["device_ops"]] == [
        "jit(f)/mul", "jit(f)/jit(_aggregate_impl)/pallas_call"]
    assert bd["idle_gaps"][0][0] == "device_get"
    assert len(bd["idle_gaps"]) == 2


def test_a_trace_without_the_annotation_is_refused():
    dev, host = _trace()
    with pytest.raises(ValueError):
        trace_reduce.reduce_events(dev, host, "missing")


def _ctx(red, **kw):
    ctx = {
        "trace": red, "window_s": red.window_s, "rounds": 2, "chips": 2,
        "peak": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
        "train_flops_per_example": 1e6, "examples_per_round": 1000,
    }
    ctx.update(kw)
    return ctx


def _reader(name):
    return harness.load_module(harness.BENCH_DIR / "layer_metrics" / f"{name}.py")


def test_device_idle_share_reader():
    red = trace_reduce.reduce_events(*_trace(), "bench.call")
    assert _reader("device_idle_share").compute(_ctx(red)) == pytest.approx(50.0)


def test_device_idle_ms_per_round_reader():
    red = trace_reduce.reduce_events(*_trace(), "bench.call")
    # 50 ms of the 100 ms window idle, over 2 rounds
    reader = harness.load_reader(harness.BENCH_DIR, "device_idle_ms_per_round")
    assert reader.compute(_ctx(red)) == pytest.approx(25.0)
    assert reader.compute(_ctx(red, rounds=0)) is None


def test_round_mfu_reader():
    red = trace_reduce.reduce_events(*_trace(), "bench.call")
    # 1e6 FLOP x 1000 examples x 2 rounds / 0.1 s / (2 x 1e12) = 1%
    assert _reader("round_mfu").compute(_ctx(red)) == pytest.approx(1.0)
    assert _reader("round_mfu").compute(
        _ctx(red, train_flops_per_example=None)) is None


def test_aggregate_share_reader_finds_the_kernel_by_name():
    red = trace_reduce.reduce_events(*_trace(), "bench.call")
    # 5 ms of kernel time (averaged over the two devices) in 50 ms busy
    assert _reader("aggregate_share").compute(_ctx(red)) == pytest.approx(10.0)


def test_aggregate_share_is_silent_without_its_kernel():
    dev, host = _trace()
    dev = {k: [e for e in v if "aggregate" not in e[0]] for k, v in dev.items()}
    red = trace_reduce.reduce_events(dev, host, "bench.call")
    assert _reader("aggregate_share").compute(_ctx(red)) is None


def test_op_names_come_from_the_hlo_instruction():
    text = ("%_aggregate_impl.1 = f32[1,212992]{1,0:T(1,128)S(1)} custom-call("
            "f32[10,1]{1,0:T(8,128)S(1)} %copy.53)")
    assert trace_reduce.op_name(text) == "_aggregate_impl.1"
    assert trace_reduce._short(text) == (
        "%_aggregate_impl.1 = f32[1,212992] custom-call(f32[10,1] %copy.53)")
