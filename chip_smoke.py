"""Smoke run of the FedAvg round engine on a TPU.

    python3 chip_smoke.py              # one chip: every aggregation lane
    python3 chip_smoke.py --chips 4    # four chips: the cohort-sharded lane

One process, no subprocesses. Each phase builds an engine through the
user's entry points (``get_spec`` -> ``RoundEngine.from_spec`` -> ``run``)
at the paper's MNIST population: 100 clients of 600 synthetic 28x28
examples each (``make_image_classification(60000, 10000, seed=0)``),
pathologically non-IID, C=0.1 (m=10), E=5, B=10, on the paper's CNN
(1,663,370 parameters) with weights initialized from the spec's seed.
Every phase asserts that its Pallas kernels run compiled (``interpret`` is
off and the lowered round holds a ``tpu_custom_call``), that the training
loss is finite, and that its aggregation kernel agrees with the plain
jnp oracle of ``repro.kernels.ref`` / the kernel module on this chip, at
the phase's real shapes.

Phases on one chip: ``cnn`` (3 plain rounds), ``q8`` and ``q4`` (quantized
uploads, the fused dequantize kernel), ``topk`` (top-k uploads, XLA
scatter-add: this lane has no Pallas kernel), ``gossip`` (ring of 100 nodes
on the 2NN, the neighbor-mixing kernel) and ``superstep`` (device sampling,
5 rounds per dispatch). ``--chips 4`` runs only the cohort-sharded lane on
a 4-device client mesh with m=12 next to the same run on one device, and
asserts they agree round for round (FedSGD setting, see ``four_chips``).

One line per phase reports the device, the first dispatch's compile
overhead and the steady seconds per round; these timings are informational,
not a benchmark. The last line is the JSON verdict. The script exits
non-zero, without a verdict, when JAX finds no TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Tolerances: fp32 agreement of a kernel with its oracle on unit-scale
# data, and the sharded-vs-unsharded bounds of tests/test_engine_sharded.py
# (plain lane).
ORACLE_ATOL = 1e-5
SHARDED_PARAM_ATOL = 1e-5
SHARDED_LOSS_ATOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def timed_run(engine, rounds, rounds_per_step=1):
    """Wall seconds of ``engine.run`` until its params are on the device."""
    import jax

    t0 = time.perf_counter()
    engine.run(rounds, rounds_per_step=rounds_per_step)
    jax.block_until_ready(engine.params)
    return time.perf_counter() - t0


def make_clients(spec, train):
    fed = spec.build_partition(labels=train.y)
    return [(train.x[ix], train.y[ix]) for ix in fed.client_indices]


def check_oracle(name, got, want):
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    err = float(np.max(np.abs(got - want)))
    assert np.isfinite(err) and err <= ORACLE_ATOL, (
        f"{name}: kernel vs oracle max |err| {err:.3g} > {ORACLE_ATOL}"
    )
    return err


def run_phase(name, spec, clients, oracle, *, rounds, rounds_per_step=1):
    """Build the engine, check the lowered executable, run ``rounds`` rounds
    in dispatches of ``rounds_per_step``, check the loss and, through
    ``oracle(engine)``, the lane's aggregation against its reference.
    Returns the engine."""
    import jax
    import numpy as np

    from repro.core import RoundEngine

    engine = RoundEngine.from_spec(spec, clients)
    assert engine.interpret is False, f"{name}: Pallas kernels interpreted"
    text = engine.lower_round(rounds_per_step).as_text()
    pallas = "tpu_custom_call" in text
    # The top-k lane aggregates with an XLA scatter-add; every other lane
    # must show its Pallas kernel lowered for the chip.
    assert pallas or spec.codec is not None and spec.codec.kind == "topk", (
        f"{name}: no tpu_custom_call in the lowered round"
    )
    R = rounds_per_step
    first_s = timed_run(engine, R, R)
    round_s = timed_run(engine, rounds - R, R) / (rounds - R)
    losses = [r.train_loss for r in engine.history.records]
    assert len(losses) == rounds and np.all(np.isfinite(losses)), (
        f"{name}: train loss {losses}"
    )
    err = oracle(engine)
    dev = jax.devices()[0]
    log(
        f"phase={name} device={dev.device_kind} interpret={engine.interpret} "
        f"tpu_custom_call={pallas} compile_s={first_s - R * round_s:.2f} "
        f"round_s={round_s:.4f} loss={losses[-1]:.4f} "
        f"oracle_max_err={err:.3g} compilations={engine.num_compilations} "
        "(timings informational)"
    )
    return engine


# -- kernel-vs-oracle checks at the phase's real shapes ----------------------

def fedavg_oracle(k, n):
    import jax
    import jax.numpy as jnp

    from repro.kernels.fedavg_agg import fedavg_aggregate
    from repro.kernels.ref import fedavg_aggregate_ref

    ks, kw = jax.random.split(jax.random.PRNGKey(1))
    stacked = jax.random.normal(ks, (k, n), jnp.float32)
    w = jax.random.uniform(kw, (k,), jnp.float32, 0.5, 2.0)
    w = w / jnp.sum(w)
    return check_oracle(
        "fedavg_aggregate", fedavg_aggregate(stacked, w),
        fedavg_aggregate_ref(stacked, w),
    )


def quantized_oracle(k, n, bits, chunk=512):
    import jax
    import jax.numpy as jnp

    from repro.kernels.quantized_agg import (
        dequantize_ref,
        packed_quantized_aggregate,
        quantized_aggregate,
        unpack_ref,
    )
    from repro.kernels.ref import fedavg_aggregate_ref
    from repro.utils.bitpack import words_per_chunk

    c = -(-n // chunk)
    levels = 2**bits - 1
    kq, kl, ks, kw = jax.random.split(jax.random.PRNGKey(2), 4)
    lo = jax.random.normal(kl, (k, c), jnp.float32)
    scale = jax.random.uniform(ks, (k, c), jnp.float32, 0.0, 2.0)
    w = jax.random.uniform(kw, (k,), jnp.float32, 0.5, 2.0)
    w = w / jnp.sum(w)
    if bits == 8:
        codes = jax.random.randint(kq, (k, c * chunk), 0, 256).astype(
            jnp.uint8
        )
        got = quantized_aggregate(codes, lo, scale, w, chunk=chunk,
                                  levels=levels)
    else:
        words = jax.random.bits(kq, (k, c * words_per_chunk(chunk, bits)),
                                jnp.uint32)
        got = packed_quantized_aggregate(words, lo, scale, w, bits=bits,
                                         chunk=chunk, levels=levels)
        codes = unpack_ref(words, bits=bits, chunk=chunk)
    dense = dequantize_ref(codes, lo, scale, chunk=chunk, levels=levels)
    return check_oracle(f"quantized_aggregate q{bits}", got,
                        fedavg_aggregate_ref(dense, w))


def topk_oracle(k, n, keep_frac=0.05):
    import jax
    import jax.numpy as jnp

    from repro.core.compression import topk_codec
    from repro.kernels.ops import sparse_fedavg_aggregate
    from repro.kernels.ref import densify_ref, fedavg_aggregate_ref

    kd, ke, kw = jax.random.split(jax.random.PRNGKey(3), 3)
    flats = jax.random.normal(kd, (k, n), jnp.float32)
    p = jax.vmap(topk_codec(keep_frac).encode)(jax.random.split(ke, k), flats)
    w = jax.random.uniform(kw, (k,), jnp.float32, 0.5, 2.0)
    got = sparse_fedavg_aggregate(p["idx"], p["values"], w, n)
    want = fedavg_aggregate_ref(densify_ref(p["idx"], p["values"], n),
                                w / jnp.sum(w))
    return check_oracle("sparse_fedavg_aggregate", got, want)


def gossip_oracle(plan, n):
    import jax
    import jax.numpy as jnp

    from repro.kernels.gossip_mix import gossip_mix, gossip_mix_ref

    x = jax.random.normal(jax.random.PRNGKey(4), (plan.idx.shape[0], n),
                          jnp.float32)
    idx, w = jnp.asarray(plan.idx), jnp.asarray(plan.weight)
    got = gossip_mix(x, idx, w)
    # The oracle's W @ X must not run at the MXU's default bf16 precision.
    with jax.default_matmul_precision("highest"):
        want = gossip_mix_ref(x, idx, w)
    return check_oracle("gossip_mix", got, want)


# -- the two modes ------------------------------------------------------------

def param_count(spec):
    import jax

    from repro.utils.tree import tree_size

    return tree_size(spec.build_model().init(jax.random.PRNGKey(0)))


def one_chip(train):
    from repro.specs import CodecSpec, ExecutionSpec, get_spec

    spec = get_spec("mnist_cnn_noniid")
    clients = make_clients(spec, train)
    n = param_count(spec)
    m = max(int(round(spec.fedavg.C * len(clients))), 1)
    engine = run_phase("cnn", spec, clients, lambda e: fedavg_oracle(m, n),
                       rounds=3)
    assert engine.num_compilations <= 2, engine.num_compilations
    del engine

    for name, codec, oracle in (
        ("q8", CodecSpec("quantize", bits=8),
         lambda e: quantized_oracle(m, n, 8)),
        ("q4", CodecSpec("quantize", bits=4),
         lambda e: quantized_oracle(m, n, 4)),
        ("topk", CodecSpec("topk", keep_frac=0.05),
         lambda e: topk_oracle(m, n)),
    ):
        run_phase(name, dataclasses.replace(spec, codec=codec), clients,
                  oracle, rounds=2)

    ring = get_spec("mnist_2nn_noniid_ring")
    n_2nn = param_count(ring)
    run_phase("gossip", ring, make_clients(ring, train),
              lambda e: gossip_oracle(e.plan, n_2nn), rounds=2)

    superstep = dataclasses.replace(
        spec, execution=ExecutionSpec(device_sampling=True,
                                      rounds_per_step=5),
    )
    run_phase("superstep", superstep, clients,
              lambda e: fedavg_oracle(m, n), rounds=10, rounds_per_step=5)


def four_chips(train):
    """The cohort-sharded lane on a 4-device client mesh against the same
    run on one device, round for round, at fp32 bounds.

    The two programs (a vmap over 12 clients on one chip, over 3 on each of
    four) sum in different orders. The paper's FedAvg setting (E=5, B=10:
    300 local steps per round) carries such rounding apart chaotically, so
    this comparison runs the paper's FedSGD setting on the same CNN,
    population and learning rate (E=1, full-batch B: one local step per
    round), with fp32 matmuls and convolutions (at the TPU's default
    precision they take bf16 inputs)."""
    import jax
    import numpy as np

    from repro.core import RoundEngine
    from repro.launch.mesh import make_client_mesh
    from repro.specs import get_spec

    base = get_spec("mnist_cnn_noniid")
    spec = dataclasses.replace(
        base, fedavg=dataclasses.replace(base.fedavg, C=0.12, E=1, B=None),
    )  # m = 12
    clients = make_clients(spec, train)
    with jax.default_matmul_precision("highest"):
        one = RoundEngine.from_spec(spec, clients)
        four = RoundEngine.from_spec(spec, clients, mesh=make_client_mesh(4))
        assert one.interpret is False and four.interpret is False
        text = four.lower_round().as_text()
        assert "tpu_custom_call" in text, "sharded round has no Pallas kernel"
        for engine, label, n_dev in ((one, "unsharded", 1),
                                     (four, "sharded", 4)):
            times = [timed_run(engine, 1) for _ in range(3)]
            round_s = float(np.mean(times[1:]))
            log(
                f"phase={label} device={jax.devices()[0].device_kind} "
                f"devices={n_dev} interpret={engine.interpret} "
                f"compile_s={times[0] - round_s:.2f} round_s={round_s:.4f} "
                f"compilations={engine.num_compilations} "
                "(timings informational)"
            )
    loss_diffs = [
        abs(r1.train_loss - r4.train_loss)
        for r1, r4 in zip(one.history.records, four.history.records)
    ]
    param_diff = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree.leaves(one.params),
                        jax.tree.leaves(four.params))
    )
    log(
        f"phase=sharded_vs_unsharded rounds={len(loss_diffs)} m=12 "
        f"loss_diff_per_round={[float(f'{d:.3g}') for d in loss_diffs]} "
        f"max_param_diff={param_diff:.3g} "
        f"(bounds {SHARDED_LOSS_ATOL:g} / {SHARDED_PARAM_ATOL:g})"
    )
    assert max(loss_diffs) <= SHARDED_LOSS_ATOL, loss_diffs
    assert param_diff <= SHARDED_PARAM_ATOL, param_diff


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every lane on one chip; 4: the sharded lane")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this script never falls back to the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.data import make_image_classification
    from repro.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    t0 = time.perf_counter()
    train, _, _ = make_image_classification(60000, 10000, seed=0)
    log(f"setup: {len(train.y)} examples in {time.perf_counter() - t0:.1f}s, "
        f"compile cache {cache_dir}")
    if args.chips == 4:
        four_chips(train)
    else:
        one_chip(train)
    log(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
