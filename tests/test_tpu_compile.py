"""Compile the aggregation kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed with jaxlib, compiles for a chip
described by ``get_topology_desc`` and raises what it would raise on the
chip — tiles that break the (8, 128) rule, blocks that overflow scoped
VMEM, primitives Mosaic cannot lower. Interpret-mode tests cannot see any
of that. Widths are the paper's 2NN and CNN (N = 199,210 and 1,663,370
parameters), cohorts K in {10, 100}, plus the large-cohort fedavg case.

The topology is described inside a module fixture (never at import, in a
``parametrize`` argument or in ``conftest.py``): only one process may load
the TPU library, and test collection happens in every worker.
"""
import pytest

import jax
import jax.numpy as jnp

WIDTHS = [199_210, 1_663_370]
COHORTS = [10, 100]
CHUNK = 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("k", COHORTS + [512])
def test_fedavg_aggregate_compiles_for_v5e(one_chip, k, n):
    from repro.kernels.fedavg_agg import fedavg_aggregate

    text = _compile(
        lambda x, w: fedavg_aggregate(x, w), one_chip,
        ((k, n), jnp.float32), ((k,), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("k", COHORTS)
def test_quantized_aggregate_compiles_for_v5e(one_chip, k, n):
    from repro.kernels.quantized_agg import quantized_aggregate

    c = -(-n // CHUNK)
    text = _compile(
        lambda q, lo, s, w: quantized_aggregate(
            q, lo, s, w, chunk=CHUNK, levels=255
        ),
        one_chip, ((k, c * CHUNK), jnp.uint8), ((k, c), jnp.float32),
        ((k, c), jnp.float32), ((k,), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("k", COHORTS)
@pytest.mark.parametrize("bits", [4, 2])
def test_packed_quantized_aggregate_compiles_for_v5e(one_chip, bits, k, n):
    from repro.kernels.quantized_agg import packed_quantized_aggregate
    from repro.utils.bitpack import words_per_chunk

    c = -(-n // CHUNK)
    text = _compile(
        lambda q, lo, s, w: packed_quantized_aggregate(
            q, lo, s, w, bits=bits, chunk=CHUNK, levels=2**bits - 1
        ),
        one_chip, ((k, c * words_per_chunk(CHUNK, bits)), jnp.uint32),
        ((k, c), jnp.float32), ((k, c), jnp.float32), ((k,), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("k", COHORTS)
def test_sparse_aggregate_compiles_for_v5e(one_chip, k, n):
    """The top-k lane's XLA scatter-add at keep_frac = 0.05."""
    from repro.kernels.ops import sparse_fedavg_aggregate

    kept = n // 20
    text = _compile(
        lambda i, v, w: sparse_fedavg_aggregate(i, v, w, n), one_chip,
        ((k, kept), jnp.int32), ((k, kept), jnp.float32),
        ((k,), jnp.float32),
    )
    assert "scatter" in text


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("nodes", COHORTS)
@pytest.mark.parametrize("kind", ["ring", "full"])
def test_gossip_mix_compiles_for_v5e(one_chip, kind, nodes, n):
    from repro.core.topology import TOPOLOGIES
    from repro.kernels.gossip_mix import gossip_mix

    slots = TOPOLOGIES[kind]().build(nodes).idx.shape[1]
    text = _compile(
        lambda x, i, w: gossip_mix(x, i, w), one_chip,
        ((nodes, n), jnp.float32), ((nodes, slots), jnp.int32),
        ((nodes, slots), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_batch_assembly_gathers_rows_for_v5e(one_chip):
    """The q4 benchmark cell's assembly (K=100 clients of 600 28x28x1
    examples, m=100, FedSGD: E=1, B=600) feeding the 2NN's loss, whose first
    op flattens each example. The pool stored as (K, n_pad, 784) rows makes
    the cohort gather a row copy; stored as (K, n_pad, 28, 28, 1), the
    example index lands in the lanes and this program reads 66.9 GB. Alone,
    without a consumer, the assembly must also lay out its 28x28x1 output,
    so the guard compiles it with the model it feeds."""
    from repro.core.engine import _assemble_batches
    from repro.models import mnist_2nn

    K, n_pad, m, B = 100, 600, 100, 600
    model = mnist_2nn()

    def first_step_loss(params, px, py, counts, spe_arr, ids, key):
        (bx, by), mask, w = _assemble_batches(
            px, py, counts, spe_arr, ids, key, E=1, spe=1, B=B,
            feature_shape=(28, 28, 1), has_labels=True,
        )
        losses = jax.vmap(lambda x, y: model.loss(params, (x[0], y[0]))[0])(
            bx, by
        )
        return jnp.sum(losses * mask[:, 0] * w)

    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
    )
    shapes = [((K, n_pad, 784), jnp.float32), ((K, n_pad), jnp.int32),
              ((K,), jnp.float32), ((K,), jnp.int32), ((m,), jnp.int32),
              ((2,), jnp.uint32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    cost = jax.jit(first_step_loss).lower(params, *args).compile()
    cost = cost.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 5e9, cost["bytes accessed"]
