"""Whole runs of the harness at a tiny size on the CPU, past its look for a
chip: the reference agrees with the program; the bfloat16 control and
faults planted in the program come out not correct.

The tiny cells are added by files alone (configurations with their
references, traffic mixes and limits in a copy of the benchmark's
directory): the 2NN at its published widths over 8 clients of 20
examples, and the program's character LSTM over 4 silos of token
sequences, one topic a silo, with its reference (written here) run two
clients at a time. Their limits are this size's own, set between what the
program reads on the CPU (under 3e-6) and what the control reads (above
1e-3).
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import compare, harness  # noqa: E402

TINY_LIMIT = 1e-4
TRAFFIC = {
    "tiny_dense": {
        "spec": {"partition": {"kind": "pathological_noniid",
                               "shards_per_client": 2},
                 "fedavg": {"C": 0.5, "E": 2, "B": 10, "lr": 0.1},
                 "strategy": {"kind": "fedavg"}, "codec": None,
                 "execution": {}},
        "rounds_per_call": 1,
    },
    "tiny_q4": {
        "spec": {"partition": {"kind": "iid"},
                 "fedavg": {"C": 1.0, "E": 1, "B": None, "lr": 0.5},
                 "strategy": {"kind": "fedsgd"},
                 "codec": {"kind": "quantize", "bits": 4, "chunk": 512},
                 "execution": {"device_sampling": True,
                               "rounds_per_step": 2}},
        "rounds_per_call": 2,
    },
    "tiny_tokens": {
        "spec": {"partition": {"kind": "pathological_noniid",
                               "shards_per_client": 1},
                 "fedavg": {"C": 1.0, "E": 1, "B": 4, "lr": 0.5},
                 "strategy": {"kind": "fedavg"}, "codec": None,
                 "execution": {}},
        "rounds_per_call": 1,
    },
}
CONFIG_OF = {"tiny_dense": "tiny_2nn", "tiny_q4": "tiny_2nn",
             "tiny_tokens": "tiny_char"}
TINY_CHAR = {
    "name": "tiny_char",
    "model": {"kind": "char_lstm",
              "kwargs": {"vocab_size": 24, "embed_dim": 8, "hidden": 32}},
    "population": "tokens", "vocab_size": 24, "seq_len": 12, "clients": 4,
    "examples_per_client": 8, "n_topics": 4,
    "reference_clients_per_block": 2,
}
# The plain reference of the program's two-layer character LSTM
# (``repro.models.char_lstm``), in the weight layout the program takes.
CHAR_LSTM_REFERENCE = '''
import math

import jax
import jax.numpy as jnp


def _glorot(key, shape, dtype):
    lim = math.sqrt(6.0 / (shape[0] + shape[1]))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def _lstm_init(key, d_in, d_hidden, dtype):
    k1, k2 = jax.random.split(key)
    return {"wx": _glorot(k1, (d_in, 4 * d_hidden), dtype),
            "wh": _glorot(k2, (d_hidden, 4 * d_hidden), dtype),
            "b": jnp.zeros((4 * d_hidden,), dtype)}


def init(key, config, dtype=jnp.float32):
    kw = config["model"]["kwargs"]
    v, e, h = config["vocab_size"], kw["embed_dim"], kw["hidden"]
    k = jax.random.split(key, 4)
    return {"embed": 0.1 * jax.random.normal(k[0], (v, e), dtype),
            "lstm1": _lstm_init(k[1], e, h, dtype),
            "lstm2": _lstm_init(k[2], h, h, dtype),
            "out": {"w": _glorot(k[3], (h, v), dtype),
                    "b": jnp.zeros((v,), dtype)}}


def _lstm(p, x, precision):
    def cell(carry, x_t):
        h, c = carry
        gates = (jnp.dot(x_t, p["wx"], precision=precision)
                 + jnp.dot(h, p["wh"], precision=precision) + p["b"])
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zeros = jnp.zeros((x.shape[0], p["wh"].shape[0]), x.dtype)
    _, hs = jax.lax.scan(cell, (zeros, zeros), jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def apply(params, x, precision):
    """Next-token logits (B, S, V) of token ids x (B, S)."""
    h = params["embed"][x]
    h = _lstm(params["lstm2"], _lstm(params["lstm1"], h, precision),
              precision)
    return jnp.dot(h, params["out"]["w"], precision=precision) + params["out"]["b"]
'''


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    bench_dir = tmp_path_factory.mktemp("bench") / "chip"
    shutil.copytree(harness.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((bench_dir / "configs" / "mnist_2nn.json").read_text())
    cfg.update(name="tiny_2nn", clients=8, examples_per_client=20)
    (bench_dir / "configs" / "tiny_2nn.json").write_text(json.dumps(cfg))
    shutil.copy(bench_dir / "configs" / "mnist_2nn.py",
                bench_dir / "configs" / "tiny_2nn.py")
    (bench_dir / "configs" / "tiny_char.json").write_text(json.dumps(TINY_CHAR))
    (bench_dir / "configs" / "tiny_char.py").write_text(CHAR_LSTM_REFERENCE)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    limits = {k: {"limit": TINY_LIMIT} for k in compare.NUMBERS}
    for name, traffic in TRAFFIC.items():
        (bench_dir / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
        (bench_dir / "limits" / f"{name}.json").write_text(json.dumps(limits))
        bench["workloads"].append({"name": name, "config": CONFIG_OF[name],
                                   "traffic": name, "chips": 1, "why": "test"})
        for metric in bench["end_to_end"]:
            if metric["name"] == "round_s":
                metric["workloads"].append(name)
    harness.validate(bench, bench_dir)
    return {name: harness.load_cell(name, bench, bench_dir) for name in TRAFFIC}


def _run(cell):
    import jax

    return harness.run_cell(cell, 2**31 + 11, 0.3, False, jax.devices()[:1],
                            time.perf_counter())


@pytest.mark.parametrize("name", list(TRAFFIC))
def test_reference_agrees_with_the_program(tiny, name):
    out = _run(tiny[name])
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"round_s", "setup_s"}
    for key in compare.NUMBERS:
        assert out["checks"][key]["value"] < 3e-6


@pytest.mark.parametrize("name", list(TRAFFIC))
def test_control_in_bfloat16_is_not_correct(tiny, name):
    import jax
    import jax.numpy as jnp

    cell = tiny[name]
    su = harness.set_up(cell, 2**31 + 12)
    su.engine = su.call = None
    ref = harness.reference_of(cell, su)
    control = harness.reference_of(cell, su, dtype=jnp.bfloat16,
                                   precision=jax.lax.Precision.DEFAULT)
    limits = {k: TINY_LIMIT for k in compare.NUMBERS}
    values = compare.readings(control, ref, su.p0, su.snapshot_rounds,
                              cell.limits)
    ok, checks = compare.judge(values, limits)
    assert not ok, checks
    program = compare.readings(su.prog, ref, su.p0, su.snapshot_rounds,
                               cell.limits)
    assert compare.judge(program, limits)[0]


def _unchanged_state(monkeypatch):
    from repro.core import engine

    orig = engine._apply_round_step

    def step(loss_fn, params, outer, *a, **kw):
        _, new_outer, loss = orig(loss_fn, params, outer, *a, **kw)
        return params, new_outer, loss

    monkeypatch.setattr(engine, "_apply_round_step", step)


def _half_batch(monkeypatch):
    import jax.numpy as jnp

    from repro.core import engine

    orig = engine._assemble_cohort_batches

    def assemble(*a, **kw):
        batch, mask, w = orig(*a, **kw)
        b = batch[0].shape[2]

        def halve(x):  # (m, steps, B, ...): every minibatch its first half
            return jnp.concatenate([x[:, :, : b // 2]] * 2, axis=2)[:, :, :b]

        return tuple(halve(x) for x in batch), mask, w

    monkeypatch.setattr(engine, "_assemble_cohort_batches", assemble)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch],
                         ids=["unchanged_state", "half_batch"])
@pytest.mark.parametrize("name", list(TRAFFIC))
def test_fault_in_the_timed_path_is_not_correct(tiny, name, fault,
                                                monkeypatch):
    fault(monkeypatch)
    out = _run(tiny[name])
    assert not out["correct"], out["checks"]


def test_sharded_cell_fails_without_the_cross_chip_sum():
    """The cohort-sharded lane on four virtual CPU devices: sound as the
    program is, not correct with the psum of its aggregation left out."""
    import os
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, str(Path(__file__).with_name("sharded_cell_run.py"))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {
        "sound": True, "no_exchange": False}
