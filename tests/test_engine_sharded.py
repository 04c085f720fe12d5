"""Cohort-sharded RoundEngine: shard_map client parallelism with
psum-finished Pallas aggregation.

The contract under test: an engine built with a client mesh over D devices
must match the unsharded engine ROUND FOR ROUND — same cohorts, same
per-client batch permutations and codec draws (all randomness is keyed by
global cohort slot), same aggregated params up to fp32 reassociation — while
keeping the single-executable guarantee (num_compilations <= 2).

These tests use however many devices the backend exposes (D=1 still
exercises the full shard_map + psum code path). The dedicated CI lane runs
them under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so D=8
actually splits the cohort, including the ghost-client padding case where
m % D != 0.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import (
    FedAvgConfig,
    RoundEngine,
    identity_codec,
    mask_codec,
    quantize_codec,
    topk_codec,
)
from repro.data.batching import pad_cohort
from repro.kernels.fedavg_agg import fedavg_aggregate
from repro.kernels.ops import (
    sharded_fedavg_aggregate,
    sharded_sparse_fedavg_aggregate,
)
from repro.kernels.ref import densify_ref
from repro.launch.mesh import make_client_mesh
from repro.models import mnist_2nn
from repro.utils.tree import tree_weighted_mean

D = len(jax.devices())


def _clients(rng, sizes, d=12, classes=5):
    return [
        (rng.normal(size=(n, d)).astype(np.float32),
         rng.integers(0, classes, n).astype(np.int32))
        for n in sizes
    ]


# ---------------------------------------------------------------------------
# pad_cohort
# ---------------------------------------------------------------------------

def test_pad_cohort_shapes_and_validity():
    ids, valid = pad_cohort(np.asarray([3, 1, 4, 1, 5], np.int64), 4)
    assert len(ids) == 8 and len(valid) == 8
    np.testing.assert_array_equal(valid, [1, 1, 1, 1, 1, 0, 0, 0])
    np.testing.assert_array_equal(ids[:5], [3, 1, 4, 1, 5])
    ids2, valid2 = pad_cohort(np.arange(6), 3)  # already a multiple
    assert len(ids2) == 6 and valid2.min() == 1.0
    with pytest.raises(ValueError):
        pad_cohort(np.arange(3), 0)


# ---------------------------------------------------------------------------
# sharded aggregation kernel adapter vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K_per_shard", [1, 3])
def test_sharded_fedavg_aggregate_matches_oracle(rng, K_per_shard):
    """shard_map(sharded_fedavg_aggregate) over the full (K, N) stack ==
    tree_weighted_mean, including zero-weight (ghost) rows."""
    mesh = make_client_mesh()
    K = D * K_per_shard
    tree = {
        "w": jnp.asarray(rng.normal(size=(K, 33, 3)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(K, 7)).astype(np.float32)),
    }
    w = rng.uniform(0.5, 4.0, K).astype(np.float32)
    if K > 1:
        w[-1] = 0.0  # ghost row: must vanish from the average
    w = jnp.asarray(w)

    f = jax.shard_map(
        lambda t, ww: sharded_fedavg_aggregate(
            t, ww, axis_name="clients", interpret=True
        ),
        mesh=mesh,
        in_specs=(P("clients"), P("clients")),
        out_specs=P(),
        check_vma=False,
    )
    got = f(tree, w)
    want = tree_weighted_mean(tree, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("K_per_shard", [1, 3])
def test_sharded_sparse_aggregate_matches_oracle(rng, K_per_shard):
    """The sparse scatter-add's partial-sum mode:
    shard_map(sharded_sparse_fedavg_aggregate) over the (K, k) top-k
    payloads == densify -> dense weighted mean, including zero-weight
    (ghost) rows."""
    mesh = make_client_mesh()
    K, n, k = D * K_per_shard, 257, 9
    idx = jnp.asarray(
        np.stack([rng.choice(n, size=k, replace=False) for _ in range(K)]),
        jnp.int32,
    )
    vals = jnp.asarray(rng.normal(size=(K, k)).astype(np.float32))
    w = rng.uniform(0.5, 4.0, K).astype(np.float32)
    if K > 1:
        w[-1] = 0.0  # ghost row: must vanish from the average
    w = jnp.asarray(w)

    f = jax.shard_map(
        lambda i, v, ww: sharded_sparse_fedavg_aggregate(
            i, v, ww, n, axis_name="clients"
        ),
        mesh=mesh,
        in_specs=(P("clients"), P("clients"), P("clients")),
        out_specs=P(),
        check_vma=False,
    )
    got = f(idx, vals, w)
    want = fedavg_aggregate(densify_ref(idx, vals, n), w / w.sum(),
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# engine equivalence: sharded == unsharded, round for round
# ---------------------------------------------------------------------------

def _equiv_case(rng, codec, n_rounds, param_atol, loss_atol, sizes=None,
                C=0.75, strategy=None):
    """Run the same config sharded (mesh over all devices) and unsharded;
    compare the loss trajectory round for round and the final params."""
    sizes = sizes or [9, 24, 17, 40, 8, 33, 21, 14]
    clients = _clients(rng, sizes)
    model = mnist_2nn(n_classes=5, d_in=12)
    params = model.init(jax.random.PRNGKey(0))
    cfg = FedAvgConfig(C=C, E=2, B=8, lr=0.2, seed=7)
    base = RoundEngine(model.loss, params, clients, cfg, codec=codec,
                       strategy=strategy)
    shrd = RoundEngine(model.loss, params, clients, cfg, codec=codec,
                       strategy=strategy, mesh=make_client_mesh())
    h_base = base.run(n_rounds)
    h_shrd = shrd.run(n_rounds)
    for rb, rs in zip(h_base.records, h_shrd.records):
        assert abs(rb.train_loss - rs.train_loss) <= loss_atol, (
            rb.train_loss, rs.train_loss)
    for a, b in zip(jax.tree.leaves(base.params), jax.tree.leaves(shrd.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=param_atol)
    return shrd


def test_sharded_engine_matches_unsharded_plain(rng):
    """Plain (Pallas fedavg_aggregate) path: the partial-sum + psum finish
    only reassociates the fp32 weighted sum, so multi-round trajectories
    stay within tight fp32 tolerance — with m % D != 0 exercising the
    zero-weight ghost padding (m=6 with D=8 forced in CI)."""
    shrd = _equiv_case(rng, None, n_rounds=4, param_atol=1e-5, loss_atol=1e-5)
    assert shrd.num_compilations <= 2


def test_sharded_engine_matches_unsharded_quantize_codec(rng):
    """Quantize-codec path: encode draws are slot-keyed so the codes match;
    the psum-finished ``quantized_aggregate`` reassociates fp32 sums, and a
    1-ulp param difference in round t can flip one stochastic-rounding
    draw in round t+1 (one quantization level at one coordinate), so the
    multi-round tolerance is one code step rather than pure fp32."""
    shrd = _equiv_case(rng, quantize_codec(8, chunk=256), n_rounds=4,
                       param_atol=1e-3, loss_atol=1e-4)
    assert shrd.num_compilations <= 2


def test_sharded_engine_matches_unsharded_packed_quantize_codec(rng):
    """Sub-byte (bit-packed) quantize path: the packed uint32 wire words go
    through the psum-finished ``packed_quantized_aggregate`` kernel. Same
    tolerance rationale as q8 — a 1-ulp param drift can flip one
    stochastic-rounding draw, and 4-bit code steps are coarser."""
    shrd = _equiv_case(rng, quantize_codec(4, chunk=256), n_rounds=3,
                       param_atol=2e-3, loss_atol=1e-3)
    assert shrd.num_compilations <= 2


def test_sharded_engine_matches_unsharded_topk_codec(rng):
    """Sparse top-k path: the scatter kernel's partial-sum mode vs the
    unsharded scatter. Encode is deterministic, but fp32 reassociation in
    earlier rounds can flip near-tied top-k MEMBERSHIP in later ones, so
    the multi-round tolerance is looser than the plain path's 1e-5."""
    shrd = _equiv_case(rng, topk_codec(0.05), n_rounds=3,
                       param_atol=1e-3, loss_atol=1e-4)
    assert shrd.num_compilations <= 2


def test_sharded_engine_matches_unsharded_mask_codec(rng):
    """Mask path (generic vmap-decode + psum): the Bernoulli mask depends
    only on the slot-folded codec key, never on param values, so sharded ==
    unsharded stays fp32-tight across rounds."""
    shrd = _equiv_case(rng, mask_codec(0.25), n_rounds=3,
                       param_atol=1e-5, loss_atol=1e-5)
    assert shrd.num_compilations <= 2


def test_sharded_engine_matches_unsharded_fedavgm(rng):
    """Server-strategy seam under shard_map: FedAvgM applies AFTER the
    psum, so every shard steps the replicated velocity and params
    identically — sharded == unsharded at fp32 tolerance, and the strategy
    state itself stays replicated (same leaves on every shard)."""
    from repro.core.strategies import FedAvgM

    shrd = _equiv_case(rng, None, n_rounds=4, param_atol=1e-5,
                       loss_atol=1e-5, strategy=FedAvgM(momentum=0.9))
    assert shrd.num_compilations <= 2
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(shrd.outer_state))


def test_sharded_engine_matches_unsharded_identity_codec(rng):
    """Identity codec: no quantization noise to amplify — the sharded codec
    decode+aggregate (generic psum path) stays at fp32 tolerance."""
    _equiv_case(rng, identity_codec(), n_rounds=3, param_atol=1e-5,
                loss_atol=1e-5)


@pytest.mark.skipif(D < 2, reason="needs >1 device to shard a cohort")
def test_sharded_engine_ghost_padding_single_client_cohort(rng):
    """C small enough that m=1 < D: every shard but one computes a pure
    ghost, and the result must still equal the unsharded single-client
    round."""
    _equiv_case(rng, None, n_rounds=2, param_atol=1e-5, loss_atol=1e-5,
                sizes=[9, 24, 17, 40], C=0.25)


def test_sharded_engine_checkpoint_resume(tmp_path):
    """save/restore on a sharded engine: restore re-replicates the params
    across the mesh and the resumed run reproduces the straight run."""
    model = mnist_2nn(n_classes=5, d_in=12)
    cfg = FedAvgConfig(C=0.5, E=1, B=8, lr=0.1, seed=3)
    mesh = make_client_mesh()

    def fresh():
        return RoundEngine(
            model.loss, model.init(jax.random.PRNGKey(2)),
            _clients(np.random.default_rng(5), [9, 24, 17, 40]), cfg,
            mesh=mesh,
        )

    straight = fresh()
    h_straight = straight.run(4)

    interrupted = fresh()
    interrupted.run(2)
    interrupted.save(tmp_path)
    resumed = fresh()
    assert resumed.restore(tmp_path) == 2
    h_resumed = resumed.run(2)
    # full-history equality: restore() rehydrates the first 2 records
    assert [r.train_loss for r in h_resumed.records] == [
        r.train_loss for r in h_straight.records
    ]
    for a, b in zip(jax.tree.leaves(resumed.params),
                    jax.tree.leaves(straight.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_engine_rejects_bad_axis(rng):
    clients = _clients(rng, [9, 24])
    model = mnist_2nn(n_classes=5, d_in=12)
    with pytest.raises(ValueError, match="client_axis"):
        RoundEngine(model.loss, model.init(jax.random.PRNGKey(0)), clients,
                    FedAvgConfig(C=1.0, E=1, B=8, lr=0.1, seed=0),
                    mesh=make_client_mesh(), client_axis="nope")
