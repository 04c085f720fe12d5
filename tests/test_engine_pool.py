"""Streamed (out-of-core) client pool == device-resident pool, bit for bit.

The contract (docs/engine.md "Population store & staging pipeline"): for
the same seed, a ``RoundEngine(pool="streamed")`` run produces BITWISE the
same params, strategy state, and history as ``pool="device"`` on every
supported lane — plain host-sampling, plain device-sampling, codec, and
superstep — because the staged cohort bytes equal the device gather's and
everything downstream is the same executable body. Checkpoints are
backend-portable in both directions (a pending double-buffered prefetch
must NOT leak consumed randomness into a checkpoint), and the budget guard
fails loudly with the streamed pool named as the fix.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import FedAvgConfig, RoundEngine, quantize_codec
from repro.core.strategies import FedAvgM
from repro.data.batching import pack_clients
from repro.data.pool import DeviceClientPool, StreamedClientPool

SIZES = [9, 24, 17, 8, 14]


def _clients(sizes=SIZES, d=12, classes=5, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (rng.normal(size=(n, d)).astype(np.float32),
         rng.integers(0, classes, n).astype(np.int32))
        for n in sizes
    ]


@pytest.fixture(scope="module")
def setup():
    from repro.models import mnist_2nn

    model = mnist_2nn(n_classes=5, d_in=12)
    params = model.init(jax.random.PRNGKey(1))
    return model, params, _clients()


def _engine(setup, pool, **kw):
    model, params, clients = setup
    cfg = kw.pop("cfg", FedAvgConfig(C=0.5, E=2, B=8, lr=0.2,
                                     lr_decay=0.99, seed=3))
    return RoundEngine(model.loss, params, clients, cfg, pool=pool, **kw)


def _assert_same_run(a, b):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(
        jax.tree.leaves(a.outer_state), jax.tree.leaves(b.outer_state)
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert [r.train_loss for r in a.history.records] == \
        [r.train_loss for r in b.history.records]
    assert [r.round for r in a.history.records] == \
        [r.round for r in b.history.records]


# ---------------------------------------------------------------------------
# the pool store itself
# ---------------------------------------------------------------------------

def test_streamed_pool_gather_matches_pack_clients():
    clients = _clients([9, 24, 17, 8, 3, 30, 12])
    packed = pack_clients(clients, 8)
    pool = StreamedClientPool.build(clients, 8, shard_clients=3)
    assert pool.num_shards == 3  # multi-shard path exercised
    ids = np.array([5, 0, 6, 2, 2, 4])
    x, y = pool.gather(ids)
    np.testing.assert_array_equal(x, packed.x[ids])
    np.testing.assert_array_equal(y, packed.y[ids])
    np.testing.assert_array_equal(pool.counts, packed.counts)
    np.testing.assert_array_equal(pool.steps_per_epoch,
                                  packed.steps_per_epoch)
    assert pool.meta.batch_size == packed.batch_size
    assert pool.meta.bucket_sizes == packed.bucket_sizes
    dx, dy = DeviceClientPool.build(clients, 8).gather(ids)
    np.testing.assert_array_equal(dx, x)
    np.testing.assert_array_equal(dy, y)


def test_streamed_pool_full_batch_lane_and_generator():
    clients = _clients([9, 24, 17])
    packed = pack_clients(clients, None)  # B=None: FedSGD full batch
    pool = StreamedClientPool.from_generator(
        (c for c in clients), None, shard_clients=2
    )
    x, _ = pool.gather(np.arange(3))
    np.testing.assert_array_equal(x, packed.x)
    assert pool.meta.max_steps_per_epoch == packed.max_steps_per_epoch


def test_streamed_pool_roundtrips_clients():
    clients = _clients([5, 11, 7])
    pool = StreamedClientPool.build(clients, 4, shard_clients=2)
    for (x, y), (px, py) in zip(clients, pool.iter_clients()):
        np.testing.assert_array_equal(x, px)
        np.testing.assert_array_equal(y, py)


def test_pack_clients_budget_guard_names_streamed_pool():
    clients = _clients([9, 24])
    with pytest.raises(ValueError, match="pool='streamed'"):
        pack_clients(clients, 8, max_bytes=100)
    # Under budget: packs normally.
    assert pack_clients(clients, 8, max_bytes=10**9).x is not None


# ---------------------------------------------------------------------------
# streamed == device, bit for bit
# ---------------------------------------------------------------------------

LANES = {
    "plain-host": (dict(), dict()),
    "plain-device": (dict(device_sampling=True), dict(rounds_per_step=1)),
    "codec": (dict(device_sampling=True, codec=quantize_codec(8)),
              dict(rounds_per_step=1)),
    "superstep": (dict(device_sampling=True), dict(rounds_per_step=3)),
    "fedavgm": (dict(strategy=FedAvgM(momentum=0.9)), dict()),
}


@pytest.mark.parametrize("lane", sorted(LANES))
def test_streamed_matches_device_bitwise(setup, lane):
    eng_kw, run_kw = LANES[lane]
    dev = _engine(setup, "device", **eng_kw)
    st = _engine(setup, "streamed", **eng_kw)
    assert dev.pool_kind == "device" and st.pool_kind == "streamed"
    dev.run(6, **run_kw)
    st.run(6, **run_kw)
    _assert_same_run(dev, st)
    # Warmed streamed loop keeps the static-shape claim.
    assert st.num_compilations <= 2


def test_streamed_ragged_superstep_matches_device(setup):
    # 7 = 3 + 3 + 1: the final ragged chunk discards the prefetched
    # 3-round bundle and must rewind the sampling stream exactly.
    dev = _engine(setup, "device", device_sampling=True)
    st = _engine(setup, "streamed", device_sampling=True)
    dev.run(7, rounds_per_step=3)
    st.run(7, rounds_per_step=3)
    _assert_same_run(dev, st)


def test_streamed_prefetch_depth_zero_matches(setup):
    base = _engine(setup, "streamed")
    off = _engine(setup, "streamed", prefetch=0)
    base.run(4)
    off.run(4)
    assert off._prefetched is None
    _assert_same_run(base, off)


def test_streamed_engine_accepts_prebuilt_pool(setup):
    model, params, clients = setup
    cfg = FedAvgConfig(C=0.5, E=2, B=8, lr=0.2, lr_decay=0.99, seed=3)
    pool = StreamedClientPool.build(clients, cfg.B, shard_clients=2)
    st = RoundEngine(model.loss, params, None, cfg, pool=pool)
    dev = _engine(setup, "device")
    st.run(4)
    dev.run(4)
    _assert_same_run(dev, st)


def test_materialize_round_batch_matches(setup):
    dev = _engine(setup, "device")
    st = _engine(setup, "streamed")
    key = jax.random.PRNGKey(11)
    ids = np.array([1, 4, 0])
    (bd, md, wd), (bs, ms, ws) = (
        e.materialize_round_batch(ids, key) for e in (dev, st)
    )
    for x, y in zip(jax.tree.leaves(bd), jax.tree.leaves(bs)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(md), np.asarray(ms))
    np.testing.assert_array_equal(np.asarray(wd), np.asarray(ws))


# ---------------------------------------------------------------------------
# checkpoint/resume across backends (incl. the prefetch-rollback hazard)
# ---------------------------------------------------------------------------

def test_resume_across_backends_bitwise(setup, tmp_path):
    straight = _engine(setup, "device", device_sampling=True)
    straight.run(6, rounds_per_step=3)
    # device writes at round 3, streamed resumes
    d = _engine(setup, "device", device_sampling=True)
    d.run(3, rounds_per_step=3)
    d.save(tmp_path / "a")
    s = _engine(setup, "streamed", device_sampling=True)
    assert s.restore(tmp_path / "a") == 3
    s.run(3, rounds_per_step=3)
    _assert_same_run(straight, s)


def test_streamed_checkpoint_discards_pending_prefetch(setup, tmp_path):
    straight = _engine(setup, "device", device_sampling=True)
    straight.run(6, rounds_per_step=3)
    st = _engine(setup, "streamed", device_sampling=True)
    st.run(3, rounds_per_step=3)
    # The double buffer staged the NEXT chunk and advanced the sampling
    # stream; save must rewind so the checkpoint matches the device lane.
    assert st._prefetched is not None
    st.save(tmp_path / "b")
    assert st._prefetched is None
    d = _engine(setup, "device", device_sampling=True)
    d.restore(tmp_path / "b")
    d.run(3, rounds_per_step=3)
    _assert_same_run(straight, d)
    # ... and the saver itself replays the discarded draw identically.
    st.run(3, rounds_per_step=3)
    _assert_same_run(straight, st)


def test_streamed_numpy_stream_resume(setup, tmp_path):
    straight = _engine(setup, "device")
    straight.run(6)
    st = _engine(setup, "streamed")
    st.run(3)
    st.save(tmp_path / "c")
    st2 = _engine(setup, "streamed")
    st2.restore(tmp_path / "c")
    st2.run(3)
    _assert_same_run(straight, st2)


# ---------------------------------------------------------------------------
# backend selection + guards
# ---------------------------------------------------------------------------

def test_auto_pool_selects_by_budget(setup, monkeypatch):
    eng = _engine(setup, "auto")
    assert eng.pool_kind == "device"  # tiny population: resident pack
    monkeypatch.setenv("REPRO_DEVICE_POOL_BUDGET", "64")
    eng = _engine(setup, "auto")
    assert eng.pool_kind == "streamed"
    # explicit device over budget: the loud pack_clients error
    with pytest.raises(ValueError, match="pool='streamed'"):
        _engine(setup, "device")


def test_streamed_rejects_incompatible_lanes(setup):
    from repro.core.latency import LatencyModel
    from repro.core.scheduler import AsyncConfig
    from repro.launch.mesh import make_client_mesh

    with pytest.raises(ValueError, match="mesh"):
        _engine(setup, "streamed", mesh=make_client_mesh())
    with pytest.raises(ValueError, match="latency/async"):
        _engine(setup, "streamed", latency=LatencyModel(mean_s=1.0))
    with pytest.raises(ValueError, match="latency/async"):
        _engine(setup, "streamed",
                cfg=FedAvgConfig(C=0.5, E=2, B=8, lr=0.2, seed=3),
                async_config=AsyncConfig(buffer_k=2))
    with pytest.raises(ValueError, match="pool must be"):
        _engine(setup, "banana")


def test_streamed_pool_batch_size_mismatch_raises(setup):
    model, params, clients = setup
    pool = StreamedClientPool.build(clients, 4, shard_clients=2)
    cfg = FedAvgConfig(C=0.5, E=1, B=8, lr=0.2, seed=3)
    with pytest.raises(ValueError, match="batch_size"):
        RoundEngine(model.loss, params, None, cfg, pool=pool)


def test_from_spec_streamed_pool(setup):
    from repro.specs import (
        ExecutionSpec,
        ExperimentSpec,
        ModelSpec,
        PartitionSpec,
    )

    model, params, clients = setup
    cfg = FedAvgConfig(C=0.5, E=2, B=8, lr=0.2, lr_decay=0.99, seed=3)
    spec = ExperimentSpec(
        name="pool_test",
        model=ModelSpec("mnist_2nn"),
        partition=PartitionSpec("iid", n_clients=len(clients)),
        fedavg=cfg,
        execution=ExecutionSpec(pool="streamed", pool_shard_clients=2,
                                device_sampling=True),
    )
    # Round-trips through JSON with the new fields intact.
    back = ExperimentSpec.from_json(spec.to_json())
    assert back == spec
    eng = RoundEngine.from_spec(
        spec, clients, loss_fn=model.loss, init_params=params
    )
    assert eng.pool_kind == "streamed"
    assert eng.pool.num_shards >= 2
    dev = _engine(setup, "device", device_sampling=True)
    eng.run(4)
    dev.run(4)
    _assert_same_run(dev, eng)


# ---------------------------------------------------------------------------
# the (K, n_pad, F) row layout == assembly from the unflattened pool
# ---------------------------------------------------------------------------

FEATURES = {
    "image": (28, 28, 1),
    "flat": (784,),
    "tokens": (5,),
}


def _featured_clients(features, k=10, seed=0):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(5, 14, k)
    if features == "tokens":
        return [(rng.integers(0, 7, (n, 5)).astype(np.int32),
                 rng.integers(0, 7, (n, 5)).astype(np.int32)) for n in sizes]
    return [(rng.normal(size=(n,) + FEATURES[features]).astype(np.float32),
             rng.integers(0, 10, n).astype(np.int32)) for n in sizes]


def _unflattened_assembly(monkeypatch):
    """The assembly the row layout replaced: the pool kept as (K, n_pad,
    *feature_shape), the cohort gathered whole (``take(px, ids)``), then
    ``take(rows, perm)``. ``perm`` is the engine's own draw, read off a
    pool of row indices."""
    from repro.core import engine

    draw = engine._assemble_cohort_batches

    def assemble(px, py, ids, w, spe_k, key, *, feature_shape, has_labels,
                 **kw):
        xs = jnp.take(px, ids, axis=0)
        ys = jnp.take(py, ids, axis=0) if has_labels else None
        m, n_pad = xs.shape[:2]
        rows = jnp.broadcast_to(
            jnp.arange(n_pad, dtype=jnp.int32)[None, :, None], (m, n_pad, 1)
        )
        (perm,), mask, w = draw(rows, None, jnp.arange(m), w, spe_k, key,
                                feature_shape=(), has_labels=False, **kw)
        take = jax.vmap(lambda r, p: jnp.take(r, p, axis=0))
        batch = (take(xs, perm),) + ((take(ys, perm),) if has_labels else ())
        return batch, mask, w

    monkeypatch.setattr(engine, "_as_rows", lambda x: x)
    monkeypatch.setattr(engine, "_assemble_cohort_batches", assemble)


@pytest.mark.parametrize("C", [0.1, 1.0], ids=["m1", "m10"])
@pytest.mark.parametrize("lane", ["device", "streamed", "sharded"])
@pytest.mark.parametrize("features", sorted(FEATURES))
def test_row_pool_matches_unflattened_assembly_bitwise(features, lane, C,
                                                        monkeypatch):
    from repro.launch.mesh import make_client_mesh
    from repro.models import char_lstm, mnist_2nn

    model = (char_lstm(7, embed_dim=4, hidden=8) if features == "tokens"
             else mnist_2nn())
    params = model.init(jax.random.PRNGKey(1))
    clients = _featured_clients(features)
    cfg = FedAvgConfig(C=C, E=2, B=4, lr=0.1, seed=5)

    def build():
        kw = ({"mesh": make_client_mesh()} if lane == "sharded"
              else {"pool": lane})
        return RoundEngine(model.loss, params, clients, cfg, **kw)

    def one_round(e):
        ids, key = build()._sample_ids_host()  # round 0's draw, replayed
        batch = e.materialize_round_batch(ids, key)
        loss = e.round()["loss"]
        return jax.tree.map(np.asarray, (batch, e.params, e.outer_state, loss))

    eng = build()
    if lane != "streamed":
        assert eng._x.ndim == 3  # (K, n_pad, F)
    got = one_round(eng)
    _unflattened_assembly(monkeypatch)
    ref = build()
    want = one_round(ref)
    (bx, _), _, _ = want[0]
    assert bx.shape[3:] == FEATURES[features]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
