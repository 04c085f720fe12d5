# fedlint: disable-file=F3  (one-shot jit-and-call is fine in tests: each
# executable runs exactly once, so there is no cache to defeat)
"""RoundEngine: static-shape round pipeline + Pallas-backed aggregation.

Covers the acceptance criteria of the engine refactor:
- Pallas fedavg_aggregate(interpret=True) vs the tree_weighted_mean oracle
  for bf16/fp32 inputs, ragged N (padding path), K in {1, 2, 17};
- FedAvgConfig(E=1, B=None) FedSGD equivalence through the new engine;
- >=5 rounds of unbalanced non-IID simulation with at most 2 distinct
  compilations, measured via jax.jit cache stats;
- the engine's jitted round == the vmapped-ClientUpdate + weighted-mean
  reference on identical materialized batches;
- History.rounds_to_target first-round crossing regression;
- the unified round_step protocol on the production (local_sgd) path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedAvgConfig, RoundEngine, fedsgd_config
from repro.core.engine import History, RoundBatch, RoundRecord, RoundState
from repro.core.fedavg import client_update
from repro.kernels.fedavg_agg import fedavg_aggregate
from repro.models import mnist_2nn
from repro.utils.tree import (
    tree_ravel,
    tree_ravel_stacked,
    tree_unravel,
    tree_weighted_mean,
)


def _unbalanced_noniid_clients(rng, sizes, d=20, classes=5):
    """Label-skewed clients of wildly different sizes (the engine's hardest
    shape case: many buckets, masked steps)."""
    out = []
    for i, n in enumerate(sizes):
        x = rng.normal(size=(n, d)).astype(np.float32)
        # each client sees ~2 of the classes
        lo = i % classes
        y = rng.choice([lo, (lo + 1) % classes], n).astype(np.int32)
        out.append((x, y))
    return out


# ---------------------------------------------------------------------------
# Pallas kernel vs reference oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 2, 17])
@pytest.mark.parametrize("N,block", [(33, 64), (1000, 128)])  # ragged: N % block != 0
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_aggregate_matches_weighted_mean(rng, K, N, block, dtype):
    stacked = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32)).astype(dtype)
    w = jnp.asarray(rng.uniform(0.1, 5.0, K).astype(np.float32))
    out = fedavg_aggregate(stacked, w / w.sum(), block_n=block, interpret=True)
    assert out.dtype == dtype and out.shape == (N,)
    # fp32 oracle: the kernel accumulates in fp32 regardless of storage
    # dtype, so its only bf16 error is the final store rounding (1 ulp).
    ref = tree_weighted_mean(stacked.astype(jnp.float32), w)
    atol = 1e-6 if dtype == jnp.float32 else float(
        np.abs(np.asarray(ref)).max()) * 2 ** -8 + 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=atol)


def test_fedavg_aggregate_rejects_unnormalized_weights(rng):
    stacked = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
    with pytest.raises(ValueError, match="pre-normalized"):
        fedavg_aggregate(stacked, jnp.asarray([1.0, 2.0, 3.0]), interpret=True)


def test_accum_dtype_exposed_fp32_beats_bf16(rng):
    """The documented reason accum_dtype exists: bf16 accumulation over many
    clients visibly degrades vs the fp32 default."""
    K, N = 64, 256
    stacked = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32)).astype(
        jnp.bfloat16
    )
    w = jnp.ones(K, jnp.float32) / K
    ref = np.asarray(tree_weighted_mean(stacked.astype(jnp.float32), w))
    err32 = np.abs(np.asarray(
        fedavg_aggregate(stacked, w, interpret=True,
                         accum_dtype=jnp.float32), np.float32) - ref).max()
    err16 = np.abs(np.asarray(
        fedavg_aggregate(stacked, w, interpret=True,
                         accum_dtype=jnp.bfloat16), np.float32) - ref).max()
    assert err32 <= err16


def test_tree_ravel_roundtrip(rng):
    model = mnist_2nn(n_classes=3, d_in=6)
    params = model.init(jax.random.PRNGKey(0))
    flat, spec = tree_ravel(params)
    assert flat.shape == (spec.total_size,)
    back = tree_unravel(spec, flat)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    stacked = jax.vmap(lambda s: model.init(jax.random.PRNGKey(s)))(jnp.arange(4))
    flat2, spec2 = tree_ravel_stacked(stacked)
    assert flat2.shape == (4, spec2.total_size)
    one = tree_unravel(spec2, flat2[2])
    for a, b in zip(jax.tree.leaves(one),
                    jax.tree.leaves(jax.tree.map(lambda l: l[2], stacked))):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# engine semantics
# ---------------------------------------------------------------------------

def test_engine_compile_count_unbalanced_noniid(rng):
    """>=5 rounds of unbalanced non-IID simulation, at most 2 distinct
    compilations (jax.jit cache stats). The whole point of the refactor:
    cohort-shape changes must not re-trace the round executable."""
    sizes = [7, 64, 13, 40, 25, 9, 31, 18, 55, 12]
    clients = _unbalanced_noniid_clients(rng, sizes)
    model = mnist_2nn(n_classes=5, d_in=20)
    params = model.init(jax.random.PRNGKey(0))
    eng = RoundEngine(model.loss, params, clients,
                      FedAvgConfig(C=0.4, E=2, B=10, lr=0.1, seed=3))
    assert len(eng.packed.bucket_sizes) > 1, "want a genuinely multi-bucket case"
    h = eng.run(5)
    assert len(h.records) == 5
    assert all(np.isfinite(r.train_loss) for r in h.records)
    assert eng.num_compilations <= 2
    # a further round with a freshly sampled cohort reuses the executable too
    eng.round()
    assert eng.num_compilations <= 2


def test_engine_fedsgd_equivalence(rng):
    """FedAvgConfig(E=1, B=None) == one FedSGD step through the engine.

    Client sizes divide the packed pool size (powers of two), so tiling
    repeats every example the same number of times and the full-batch
    gradient is EXACT — machine-precision equivalence, as in the paper's
    Section 2 identity."""
    sizes = [8, 16, 32]
    clients = _unbalanced_noniid_clients(rng, sizes)
    model = mnist_2nn(n_classes=5, d_in=20)
    params = model.init(jax.random.PRNGKey(1))
    lr = 0.5
    eng = RoundEngine(model.loss, params, clients,
                      fedsgd_config(C=1.0, lr=lr, seed=0))
    assert eng.packed.batch_size == 32  # next_pow2(max n_k)
    eng.round()

    n = sum(sizes)

    def global_loss(p):
        tot = 0.0
        for x, y in clients:
            l, _ = model.loss(p, (jnp.asarray(x), jnp.asarray(y)))
            tot = tot + (len(x) / n) * l
        return tot

    ref = jax.tree.map(lambda p, g: p - lr * g, params,
                       jax.grad(global_loss)(params))
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_engine_round_matches_reference_on_same_batches(rng):
    """The jitted engine round == vmapped ClientUpdate + tree_weighted_mean
    on the identical materialized batches (Pallas agg vs oracle end to
    end, fp32 tolerance)."""
    sizes = [9, 24, 17, 40]
    clients = _unbalanced_noniid_clients(rng, sizes)
    model = mnist_2nn(n_classes=5, d_in=20)
    params = model.init(jax.random.PRNGKey(2))
    eng = RoundEngine(model.loss, params, clients,
                      FedAvgConfig(C=0.75, E=2, B=8, lr=0.2, seed=7))
    ids, valid, key, lr = eng._next_round_inputs()
    np.testing.assert_array_equal(np.asarray(valid), 1.0)  # unsharded: no ghosts
    batch, mask, w = eng.materialize_round_batch(ids, key)

    upd = jax.vmap(lambda b, msk: client_update(model.loss, params, b, msk, lr))
    client_params, _ = upd(batch, mask)
    want = tree_weighted_mean(client_params, w)

    got, _, metrics = eng._round_jit(
        eng.params, eng.outer_state, eng._x, eng._y, eng._counts, eng._spe,
        ids, valid, key, lr,
    )
    assert np.isfinite(float(metrics["loss"]))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_engine_masks_padded_steps(rng):
    """Clients smaller than one batch take exactly one real step per epoch;
    the rest of the padded schedule must be no-ops."""
    sizes = [4, 100]
    clients = _unbalanced_noniid_clients(rng, sizes)
    model = mnist_2nn(n_classes=5, d_in=20)
    params = model.init(jax.random.PRNGKey(0))
    eng = RoundEngine(model.loss, params, clients,
                      FedAvgConfig(C=1.0, E=1, B=10, lr=0.1, seed=0))
    ids = jnp.asarray([0, 1], jnp.int32)
    _, mask, w = eng.materialize_round_batch(ids, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(w), [4.0, 100.0])
    assert float(mask[0].sum()) == 1.0          # n=4 < B=10 -> 1 masked-in step
    assert float(mask[1].sum()) == 10.0         # 100 // 10 real steps


def test_engine_second_run_still_evaluates_final_round(rng):
    """run() twice on one engine: the second call's last round must still
    evaluate (regression: the old cumulative-round check never fired)."""
    clients = _unbalanced_noniid_clients(rng, [16, 24])
    model = mnist_2nn(n_classes=5, d_in=20)
    eng = RoundEngine(model.loss, model.init(jax.random.PRNGKey(0)), clients,
                      FedAvgConfig(C=1.0, E=1, B=8, lr=0.1, seed=0),
                      eval_fn=lambda p: {"acc": 0.5, "loss": 1.0})
    eng.run(2, eval_every=5)
    eng.run(2, eval_every=5)
    assert eng.history.records[-1].test_acc is not None
    # overhead() works on the stripped (device-uploaded) pack
    assert eng.packed.overhead() >= 1.0


def test_engine_epoch_sampling_without_replacement(rng):
    """Active steps must sample a client's REAL examples without
    replacement, even though its pool is tiled with duplicates (regression:
    permuting the tiled pool over-sampled low-index examples)."""
    # client 0: 25 unique rows, client 1 forces n_pad = 40 > 25
    x0 = np.arange(25, dtype=np.float32).reshape(25, 1)
    x1 = rng.normal(size=(40, 1)).astype(np.float32) + 1000.0
    clients = [(x0, np.zeros(25, np.int32)), (x1, np.ones(40, np.int32))]
    model = mnist_2nn(n_classes=2, d_in=1)
    params = model.init(jax.random.PRNGKey(0))
    eng = RoundEngine(model.loss, params, clients,
                      FedAvgConfig(C=1.0, E=3, B=5, lr=0.1, seed=0))
    (bx, _), mask, _ = eng.materialize_round_batch(
        jnp.asarray([0, 1], jnp.int32), jax.random.PRNGKey(42)
    )
    spe = eng.packed.max_real_steps_per_epoch
    assert int(mask[0].sum()) == 3 * 5  # 25 // 5 real steps per epoch, E=3
    for e in range(3):
        epoch = np.asarray(bx[0, e * spe : e * spe + 5]).reshape(-1)
        # 5 active steps x B=5 = 25 rows: every unique example exactly once
        assert len(set(epoch.tolist())) == 25, sorted(epoch.tolist())


# ---------------------------------------------------------------------------
# buffer donation on the per-round executable
# ---------------------------------------------------------------------------

def test_round_jit_donation_no_warning_and_unchanged(rng):
    """_round_jit donates the params argument (dead after every round, so
    the server update is in-place). The donation must actually take — no
    'donated buffers were not usable' warning — and donating must not
    change the result vs an undonated jit of the identical round body."""
    import warnings

    sizes = [9, 24, 17, 40]
    clients = _unbalanced_noniid_clients(rng, sizes)
    model = mnist_2nn(n_classes=5, d_in=20)
    params = model.init(jax.random.PRNGKey(2))
    eng = RoundEngine(model.loss, params, clients,
                      FedAvgConfig(C=0.75, E=2, B=8, lr=0.2, seed=7))
    ids, valid, key, lr = eng._next_round_inputs()
    args = (eng._x, eng._y, eng._counts, eng._spe, ids, valid, key, lr)
    # Undonated reference first — it leaves eng.params alive.
    want, _, want_metrics = jax.jit(eng._round_body)(
        eng.params, eng.outer_state, *args
    )
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*[Dd]onat.*")
        got, _, got_metrics = eng._round_jit(eng.params, eng.outer_state,
                                             *args)
    assert float(got_metrics["loss"]) == float(want_metrics["loss"])
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the donated input really was consumed (in-place server update)
    with pytest.raises(RuntimeError):
        _ = np.asarray(jax.tree.leaves(eng.params)[0])


def test_engine_copies_init_params_against_donation(rng):
    """Donation must never eat the CALLER's init_params: two engines built
    from the same params tree stay independent after one of them rounds."""
    clients = _unbalanced_noniid_clients(rng, [16, 24])
    model = mnist_2nn(n_classes=5, d_in=20)
    params = model.init(jax.random.PRNGKey(0))
    cfg = FedAvgConfig(C=1.0, E=1, B=8, lr=0.1, seed=0)
    a = RoundEngine(model.loss, params, clients, cfg)
    b = RoundEngine(model.loss, params, clients, cfg)
    a.round()
    b.round()  # would crash on a deleted shared buffer without the copy
    np.testing.assert_array_equal(  # caller's tree untouched too
        np.asarray(jax.tree.leaves(params)[0]),
        np.asarray(jax.tree.leaves(model.init(jax.random.PRNGKey(0)))[0]),
    )


# ---------------------------------------------------------------------------
# lr schedule / early-stop guard regressions
# ---------------------------------------------------------------------------

def _tiny_engine(rng, cfg, **kw):
    clients = _unbalanced_noniid_clients(rng, [16, 24])
    model = mnist_2nn(n_classes=5, d_in=20)
    return RoundEngine(model.loss, model.init(jax.random.PRNGKey(0)), clients,
                       cfg, **kw)


@pytest.mark.parametrize("R", [1, 3])
def test_lower_round_is_inspection_only(rng, R):
    """lower_round(R) lowers the executable run() dispatches without
    running it: no stream advances, no compilation is cached, and the
    following run matches a twin engine that never lowered."""
    cfg = FedAvgConfig(C=0.5, E=1, B=8, lr=0.1, seed=0)
    a = _tiny_engine(rng, cfg, device_sampling=True)
    b = _tiny_engine(np.random.default_rng(0), cfg, device_sampling=True)
    text = a.lower_round(R).as_text()
    # interpret mode on the CPU: the kernel is plain XLA, no TPU call
    assert "tpu_custom_call" not in text and len(text) > 0
    assert a.num_compilations == 0 and a.round_idx == 0
    ha, hb = a.run(R, rounds_per_step=R), b.run(R, rounds_per_step=R)
    assert [r.train_loss for r in ha.records] == [
        r.train_loss for r in hb.records
    ]


def test_lr_at_scalar_applies_decay(rng):
    eng = _tiny_engine(rng, FedAvgConfig(C=1.0, lr=0.2, lr_decay=0.5, seed=0))
    assert eng.lr_at(0) == pytest.approx(0.2)
    assert eng.lr_at(3) == pytest.approx(0.2 * 0.5**3)


def test_lr_at_schedule_not_double_decayed(rng):
    """Regression: a callable cfg.lr was additionally multiplied by
    lr_decay**round, so schedule+decay configs decayed twice."""
    sched = lambda r: 0.2 * 0.9**r
    eng = _tiny_engine(rng, FedAvgConfig(C=1.0, lr=sched, lr_decay=0.5, seed=0))
    assert eng.lr_at(0) == pytest.approx(0.2)
    assert eng.lr_at(4) == pytest.approx(0.2 * 0.9**4)   # NOT * 0.5**4


def test_run_target_acc_without_eval_fn_raises(rng):
    """Regression: target_acc with eval_fn=None silently never early-stopped
    (the accuracy is never measured) and ran all n_rounds."""
    from repro.core.simulation import FederatedTrainer

    eng = _tiny_engine(rng, FedAvgConfig(C=1.0, E=1, B=8, lr=0.1, seed=0))
    with pytest.raises(ValueError, match="eval_fn"):
        eng.run(3, target_acc=0.9)
    assert eng.round_idx == 0  # raised at call time, before any round ran

    clients = _unbalanced_noniid_clients(rng, [16, 24])
    model = mnist_2nn(n_classes=5, d_in=20)
    tr = FederatedTrainer(model.loss, model.init(jax.random.PRNGKey(0)),
                          clients, FedAvgConfig(C=1.0, E=1, B=8, lr=0.1, seed=0))
    with pytest.raises(ValueError, match="eval_fn"):
        tr.run(3, target_acc=0.9)


def test_run_eval_every_zero_raises_up_front(rng):
    """Regression: run(eval_every=0) used to crash mid-loop with a bare
    ZeroDivisionError from ``round_idx % eval_every``. Validate at call
    time with an actionable message, before any round runs."""
    eng = _tiny_engine(rng, FedAvgConfig(C=1.0, E=1, B=8, lr=0.1, seed=0),
                       eval_fn=lambda p: {"acc": 0.5, "loss": 1.0})
    for bad in (0, -1):
        with pytest.raises(ValueError, match="eval_every"):
            eng.run(3, eval_every=bad)
    assert eng.round_idx == 0


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

def test_engine_checkpoint_resume_bit_for_bit(rng, tmp_path):
    """Save (params, round_idx, rng state) mid-run, restore into a FRESH
    engine, and the resumed run must reproduce the uninterrupted run's
    params and per-round losses bit-for-bit — the client sampling stream,
    per-round PRNG keys, and lr schedule all resume where they left off."""
    sizes = [7, 64, 13, 40, 25, 9]
    cfg = FedAvgConfig(C=0.5, E=2, B=10, lr=0.1, lr_decay=0.99, seed=11)
    model = mnist_2nn(n_classes=5, d_in=20)

    def fresh():
        r = np.random.default_rng(123)
        return RoundEngine(model.loss, model.init(jax.random.PRNGKey(4)),
                           _unbalanced_noniid_clients(r, sizes), cfg)

    straight = fresh()
    h_straight = straight.run(6)

    interrupted = fresh()
    interrupted.run(3)
    interrupted.save(tmp_path)

    resumed = fresh()
    assert resumed.restore(tmp_path) == 3
    # restore() also rehydrates the pre-interruption history (it used to
    # come back empty, losing the first 3 records from every resumed
    # run's curve), so the FULL histories must now be equal.
    assert [r.train_loss for r in resumed.history.records] == [
        r.train_loss for r in h_straight.records[:3]
    ]
    h_resumed = resumed.run(3)

    assert [r.train_loss for r in h_resumed.records] == [
        r.train_loss for r in h_straight.records
    ]
    for a, b in zip(jax.tree.leaves(resumed.params),
                    jax.tree.leaves(straight.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# History regression
# ---------------------------------------------------------------------------

def test_rounds_to_target_first_round_crossing():
    h = History([RoundRecord(round=1, train_loss=0.0, test_acc=0.95)])
    # Old code interpolated from a fictitious (0, 0.0) point -> ~0.947.
    assert h.rounds_to_target(0.90) == 1.0


def test_rounds_to_target_interpolates_between_rounds():
    h = History([
        RoundRecord(round=1, train_loss=0.0, test_acc=0.50),
        RoundRecord(round=2, train_loss=0.0, test_acc=1.00),
    ])
    assert h.rounds_to_target(0.75) == pytest.approx(1.5)
    assert h.rounds_to_target(0.50) == 1.0
    assert h.rounds_to_target(1.01) is None


# ---------------------------------------------------------------------------
# round_step protocol on the production path
# ---------------------------------------------------------------------------

def test_local_sgd_round_step_protocol(rng):
    from repro.core.local_sgd import (
        LocalSGDConfig,
        as_round_step,
        build_fedavg_round_step,
        replicate_for_groups,
    )
    from repro.optim import sgd

    model = mnist_2nn(n_classes=5, d_in=12)
    p = model.init(jax.random.PRNGKey(0))
    G, H = 3, 2
    cfg = LocalSGDConfig(num_groups=G, local_steps=H)
    pg = replicate_for_groups(p, G)
    sg = jax.vmap(sgd(0.1).init)(pg)
    batches = (
        jnp.asarray(rng.normal(size=(H, G, 8, 12)).astype(np.float32)),
        jnp.asarray(rng.integers(0, 5, (H, G, 8)).astype(np.int32)),
    )
    w = jnp.asarray([1.0, 2.0, 3.0])

    legacy = build_fedavg_round_step(model.loss, sgd(0.1), cfg)
    pg_a, _, _, m_a = jax.jit(legacy)(pg, sg, None, batches, w)

    step = as_round_step(model.loss, sgd(0.1), cfg)
    state, m_b = jax.jit(step)(RoundState(pg, sg, None), RoundBatch(batches, None, w))
    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]), atol=1e-7)
    for a, b in zip(jax.tree.leaves(pg_a), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(a, b, atol=1e-7)
