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
import numpy as np
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


def test_grouped_expert_matmul_computes_each_row_once_for_v5e(one_chip):
    """The held experts' grouped matmul (``jax.lax.ragged_dot``) at the
    DeepSeek-V2-Lite share's widths: all T*k = 49,152 assignments of a
    B = 4 step sorted into 8 expert groups. The chip's lowering is one
    Mosaic kernel whose cost is one pass over the rows, not one per group
    (which a dense fallback would pay: 8x)."""
    M, d, f, held = 49_152, 2048, 1408, 8
    compiled = jax.jit(jax.lax.ragged_dot).lower(
        jax.ShapeDtypeStruct((M, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((held, d, f), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["flops"] == pytest.approx(2.0 * M * d * f, rel=1e-3)


def test_deepseek_share_round_fits_one_v5e(one_chip):
    """The benchmark's ``dsv2lite_silo4_seq2048`` round (4 silos of 16
    sequences of 2,048 tokens, B = 4, one silo a chunk) at the share's
    published widths, 535 M float32 parameters: it compiles for one v5e,
    its grouped matmuls become kernels, and its buffers fit the chip's
    16 GiB."""
    import json
    from functools import partial
    from pathlib import Path

    from repro.core.engine import _engine_round
    from repro.core.strategies import FedAvg
    from repro.specs.spec import MODELS

    conf = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip" / "configs" / "deepseek_v2_lite_share8.json")
                      .read_text())
    model = MODELS["transformer"](**conf["model"]["kwargs"])
    K, n, S, B = 4, 16, 2048, 4
    body = partial(
        _engine_round, model.loss, E=1, spe=n // B, B=B, feature_shape=(S,),
        has_labels=True, codec=None, strategy=FedAvg(), interpret=False,
        accum_dtype=jnp.float32, clients_per_chunk=1,
    )
    on_chip = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    params = jax.tree.map(lambda a: on_chip(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    args = [on_chip((K, n, S), jnp.int32), on_chip((K, n, S), jnp.int32),
            on_chip((K,), jnp.float32), on_chip((K,), jnp.int32),
            on_chip((K,), jnp.int32), on_chip((K,), jnp.float32),
            on_chip((2,), jnp.uint32), on_chip((), jnp.float32)]
    compiled = jax.jit(body, donate_argnums=(0, 1)).lower(
        params, (), *args).compile()
    assert "ragged-dot" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 15.5 * 2**30, total


def test_cohort_sharded_round_compiles_for_v5e_2x2(topo):
    """The cohort-sharded lane's round (``shard_map`` over a four-chip
    client axis, the Pallas aggregation in partial-sum mode finished by a
    ``psum``) for the MNIST CNN at the paper's full cohort (m = 100, 25 a
    chip, E = 5, B = 10) on a described 2x2 v5e host."""
    from functools import partial

    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.engine import _engine_round
    from repro.core.strategies import FedAvg
    from repro.models import mnist_cnn

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        mesh = Mesh(np.asarray(topo.devices).reshape(4), ("clients",))
        rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
        model = mnist_cnn()
        body = jax.shard_map(
            partial(_engine_round, model.loss, E=5, spe=60, B=10,
                    feature_shape=(28, 28, 1), has_labels=True, codec=None,
                    strategy=FedAvg(), interpret=False,
                    accum_dtype=jnp.float32, axis_name="clients"),
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P(), P("clients"),
                      P("clients"), P(), P()),
            out_specs=(P(), P(), P()), check_vma=False,
        )
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        shapes = [((100, 600, 784), jnp.float32, rep),
                  ((100, 600), jnp.int32, rep), ((100,), jnp.float32, rep),
                  ((100,), jnp.int32, rep), ((100,), jnp.int32, split),
                  ((100,), jnp.float32, split), ((2,), jnp.uint32, rep),
                  ((), jnp.float32, rep)]
        args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in shapes]
        text = jax.jit(body).lower(params, (), *args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "all-reduce" in text
    assert "tpu_custom_call" in text
