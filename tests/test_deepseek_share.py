"""One chip's share of DeepSeek-V2-Lite on the normal path, at a tiny
DeepSeek shape on the CPU: the engine's round against the benchmark's plain
float32 reference, the expert-parallel share against the uncut layer, the
published gate and YaRN numbers, and the full-size parameter count.

The tiny shape: hidden 64, 4 heads, kv_lora 16, rope 8, 16 routed experts
of which this share holds 4 (experts 4..7), top 3, 1 shared expert, 1 dense
+ 2 MoE layers, vocabulary 128, sequences of 32 tokens.
"""
import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from repro.configs import get_config  # noqa: E402
from repro.core import RoundEngine  # noqa: E402
from repro.models import layers  # noqa: E402
from repro.models.layers import (  # noqa: E402
    compact_rows,
    mla_softmax_gain,
    moe_apply,
    moe_init,
    rope_freqs,
    yarn_mscale,
)
from repro.specs import ExperimentSpec  # noqa: E402

BENCH = ROOT / "benchmarks" / "chip"
SEQ = 32
E, HELD, FIRST, K = 16, 4, 4, 3
# The balance loss's coefficient, a hundred times the published 0.001 so
# that its gradient shows at the comparison's tolerances.
ALPHA = 0.1

TINY_KWARGS = {
    "preset": "deepseek_v2_lite_16b", "n_layers": 3, "vocab_size": 128,
    "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
    "param_dtype": "float32", "compute_dtype": "float32", "ce_chunk": 0,
    "remat": False,
    "mla": {"kv_lora_rank": 16, "qk_rope_dim": 8, "qk_nope_dim": 16,
            "v_head_dim": 16},
    "moe": {"n_experts": E, "topk": K, "n_shared_experts": 1, "d_ff": 32,
            "dense_d_ff": 96, "n_held": HELD, "held_first": FIRST,
            "router_aux_weight": ALPHA},
}


def _reference():
    spec = importlib.util.spec_from_file_location(
        "dsv2_share_reference", BENCH / "configs" / "deepseek_v2_lite_share8.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _tiny_config(held=(FIRST, HELD)):
    cfg = json.loads((BENCH / "configs" / "deepseek_v2_lite_share8.json")
                     .read_text())
    cfg.update({
        "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 16,
        "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "n_shared_experts": 1, "num_experts_per_tok": K,
        "num_hidden_layers": 3, "vocab_size": 128, "seq_len": SEQ,
        "held_experts": list(held), "aux_loss_alpha": ALPHA,
        "published": dict(cfg["published"], n_routed_experts=E),
    })
    return cfg


def _model_cfg(**moe):
    cfg = get_config("deepseek-v2-lite-16b")
    kw = {k: v for k, v in TINY_KWARGS.items()
          if k not in ("preset", "mla", "moe")}
    return dataclasses.replace(
        cfg, **kw,
        mla=dataclasses.replace(cfg.mla, **TINY_KWARGS["mla"]),
        moe=dataclasses.replace(cfg.moe, **{**TINY_KWARGS["moe"], **moe}),
    )


def _clients(n_clients=4, per_client=2, seed=0):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, 128, size=(n_clients * per_client, SEQ + 1))
    tok = tok.astype(np.int32)
    return [(tok[i::n_clients, :-1], tok[i::n_clients, 1:])
            for i in range(n_clients)]


def _spec(clients_per_chunk=1, lr=0.05):
    return ExperimentSpec.from_json(json.dumps({
        "name": "tiny_dsv2",
        "model": {"kind": "transformer", "kwargs": TINY_KWARGS},
        "partition": {"kind": "iid", "n_clients": 4, "seed": 3},
        "fedavg": {"C": 1.0, "E": 1, "B": 2, "lr": lr, "seed": 3},
        "strategy": {"kind": "fedavg"}, "codec": None,
        "execution": {"clients_per_chunk": clients_per_chunk},
    }))


def test_engine_round_matches_the_reference():
    """Loss of each round and every weight after one and three rounds, the
    program's round (through ExperimentSpec -> RoundEngine.from_spec)
    against the benchmark's float32 reference on the same seeded weights
    and batches."""
    from benchmarks.chip import reference

    cfg = _tiny_config()
    clients = _clients()
    init = jax.jit(lambda k: REF.init(k, cfg))
    p0 = init(jax.random.PRNGKey(1))
    spec = _spec()
    eng = RoundEngine.from_spec(spec, clients, init_params=p0)
    snaps = {}
    for r in (1, 2, 3):
        eng.run(1)
        if r in (1, 3):
            snaps[r] = jax.tree.map(np.asarray, eng.params)
    traffic = json.loads(spec.to_json())
    ref = reference.run_reference(
        lambda p, x, prec: REF.apply(p, x, prec, config=cfg), clients,
        jax.tree.map(np.asarray, p0), traffic, 3, 3, (1, 3),
        clients_per_block=1)
    losses = [r.train_loss for r in eng.history.records]
    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-5)
    for r in (1, 3):
        for a, b in zip(jax.tree.leaves(snaps[r]),
                        jax.tree.leaves(ref["params"][r])):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-6)
    # The MoE counter: a mean over the cohort's steps, per MoE layer.
    held = eng.history.records[0].counters["moe_held_assignments"]
    assert len(held) == 2
    assert all(0 < h <= 2 * SEQ * K for h in held)
    assert all(h <= compact_rows(2 * SEQ, K, HELD, E) for h in held)
    assert eng.history.records[0].counters["moe_compact_buffer"] == [1.0, 1.0]


def test_shares_sum_to_the_uncut_layer():
    """The four 4-expert shares of a 16-expert layer, with the shared
    expert counted once, add up to the uncut layer (the reference's dense
    evaluation of all 16 experts)."""
    cfg = _model_cfg(n_held=E, held_first=0)
    p = moe_init(jax.random.PRNGKey(2), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64), jnp.float32)
    shared = REF._mlp(p["shared"], x, jnp.dot)
    total = shared
    for first in range(0, E, HELD):
        share = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_held=HELD,
                                         held_first=first))
        ps = dict(p, **{k: p[k][first:first + HELD]
                        for k in ("we_i", "we_g", "we_o")})
        y, _, _ = moe_apply(ps, share, x)
        total = total + (y - shared)
    m = REF._dims(_tiny_config(held=(0, E)))
    whole = REF._moe(p, x, m, jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(total, whole, atol=2e-6)


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("routing", ["fits", "past_compact"])
def test_compact_and_full_buffers_match_the_reference(monkeypatch, routing,
                                                      remat):
    """The held experts over the compact buffer, over the full T*k buffer
    (the only one built when the compact size is not under T*k), and the
    reference's dense evaluation give the same layer and the same
    gradients for x, the router and the three expert weights. At 64 tokens
    the compact buffer has 128 of the 192 assignment rows. A router biased
    towards the held experts sends them every assignment, past the compact
    buffer: the layer then takes the full buffer and says so in its
    counter."""
    T = 2 * SEQ
    rows = compact_rows(T, K, HELD, E)
    assert rows == 128 < T * K
    cfg = _model_cfg(router_aux_weight=0.0)
    p = moe_init(jax.random.PRNGKey(10), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, SEQ, 64), jnp.float32)
    if routing == "past_compact":
        u = jnp.full((64,), 1 / 8.0)                          # unit norm
        x = x + 4 * u
        p["router"] = p["router"].at[:, FIRST:FIRST + HELD].add(4 * u[:, None])
    cot = jax.random.normal(jax.random.PRNGKey(12), x.shape, jnp.float32)
    m = REF._dims(_tiny_config())
    m["alpha"] = 0.0

    def grads(layer):
        layer = jax.checkpoint(layer) if remat else layer
        y, vjp = jax.vjp(layer, p, x)
        return y, vjp(cot)

    def program(p, x):
        return moe_apply(p, cfg, x)[0]

    y, g = grads(program)
    counters = moe_apply(p, cfg, x)[2]
    ref_y, ref_g = grads(lambda p, x: REF._moe(p, x, m,
                                               jax.lax.Precision.HIGHEST))
    monkeypatch.setattr(layers, "compact_rows", lambda *a: T * K)
    full_y, full_g = grads(program)
    assert int(moe_apply(p, cfg, x)[2]["moe_compact_buffer"]) == 0

    held = int(counters["moe_held_assignments"])
    if routing == "fits":
        assert 0 < held <= rows
        assert int(counters["moe_compact_buffer"]) == 1
    else:
        assert held == T * K
        assert int(counters["moe_compact_buffer"]) == 0
    np.testing.assert_allclose(y, full_y, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(y, ref_y, rtol=1e-5, atol=2e-6)
    (gp, gx), (fp, fx), (rp, rx) = g, full_g, ref_g
    np.testing.assert_allclose(gx, fx, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(gx, rx, rtol=1e-4, atol=1e-6)
    for name in ("router", "we_i", "we_g", "we_o"):
        assert float(jnp.max(jnp.abs(rp[name]))) > 0, name
        np.testing.assert_allclose(gp[name], fp[name], rtol=1e-5, atol=1e-7,
                                   err_msg=name)
        np.testing.assert_allclose(gp[name], rp[name], rtol=1e-4, atol=1e-6,
                                   err_msg=name)


def test_balance_loss_as_published():
    """The sequence-level balance loss (MoEGate with seq_aux): the layer's
    value is the reference's formula, written from the published code, and
    the model's loss reads the cross-entropy alone while its gradient
    carries the balance loss's (AddAuxiliaryLoss)."""
    from repro.specs.spec import MODELS

    cfg = _model_cfg(n_held=E, held_first=0)
    assert cfg.moe.seq_aux and cfg.moe.router_aux_weight == ALPHA
    assert get_config("deepseek-v2-lite-16b").moe.router_aux_weight == 0.001
    p = moe_init(jax.random.PRNGKey(6), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 64), jnp.float32)
    _, aux, _ = moe_apply(p, cfg, x)
    scores = jax.nn.softmax(x.reshape(-1, 64) @ p["router"], axis=-1)
    idx = jax.lax.top_k(scores, K)[1]
    want = REF._seq_aux_loss(scores.reshape(2, SEQ, E), idx, {"k": K}, ALPHA)
    np.testing.assert_allclose(aux, want, rtol=1e-6)
    # A balanced router reads alpha: counts S*k/E and scores 1/E each.
    assert 0.5 * ALPHA < float(aux) < 2 * ALPHA

    model = MODELS["transformer"](**TINY_KWARGS)
    params = model.init(jax.random.PRNGKey(8))
    tok = jnp.asarray(_clients()[0][0])
    batch = (tok, jnp.roll(tok, -1, axis=1))
    loss, metrics = model.loss(params, batch)
    assert float(metrics["aux"]) > 0
    assert float(loss) == float(metrics["ce"])
    g_loss = jax.grad(lambda q: model.loss(q, batch)[0])(params)
    g_ce = jax.grad(lambda q: model.loss(q, batch)[1]["ce"])(params)
    g_aux = jax.grad(lambda q: model.loss(q, batch)[1]["aux"])(params)
    router = lambda g: g["layers"][1]["sub0"]["ffn"]["router"]
    assert float(jnp.max(jnp.abs(router(g_aux)))) > 0
    np.testing.assert_allclose(router(g_loss), router(g_ce) + router(g_aux),
                               rtol=1e-5, atol=1e-9)


def test_gates_are_not_renormalised_as_published():
    """norm_topk_prob false keeps the router's softmax weights of the top
    k; true divides them by their sum. The routed parts differ by exactly
    that sum, token by token."""
    cfg = _model_cfg(n_held=E, held_first=0, n_shared_experts=0)
    p = moe_init(jax.random.PRNGKey(4), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64), jnp.float32)
    assert get_config("deepseek-v2-lite-16b").moe.norm_topk_prob is False
    y_pub, _, _ = moe_apply(p, cfg, x)
    norm = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, norm_topk_prob=True))
    y_norm, _, _ = moe_apply(p, norm, x)
    scores = jax.nn.softmax(x.reshape(-1, 64) @ p["router"], axis=-1)
    gsum = jnp.sum(jax.lax.top_k(scores, K)[0], axis=-1)
    assert float(jnp.max(gsum)) < 1.0
    np.testing.assert_allclose(y_pub.reshape(-1, 64),
                               y_norm.reshape(-1, 64) * gsum[:, None],
                               rtol=1e-5, atol=1e-6)


def test_yarn_at_the_published_numbers():
    """factor 40 over 4,096 positions, beta_fast 32, beta_slow 1, rope dim
    64, theta 1e4: the correction range is channels 10..23 (floor 10.47,
    ceil 22.51); below it the original frequencies, above it a fortieth of
    them, a linear ramp between. mscale(40, 0.707) = 1 + 0.0707 ln 40."""
    cfg = get_config("deepseek-v2-lite-16b")
    y = cfg.rope_scaling
    freqs = rope_freqs(64, 1e4, y)
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(freqs[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(freqs[23:], base[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(freqs[16], base[16] * (1 - 6 / 13)
                               + base[16] / 40 * 6 / 13, rtol=1e-12)
    np.testing.assert_allclose(freqs, base / 40 * ramp + base * (1 - ramp),
                               rtol=1e-12)
    np.testing.assert_allclose(
        REF.yarn_inv_freq(64, 1e4, _tiny_config()["rope_scaling"]), freqs,
        rtol=1e-12)
    mscale = 1 + 0.1 * 0.707 * math.log(40)
    assert yarn_mscale(40, 0.707) == pytest.approx(mscale, rel=1e-12)
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert mla_softmax_gain(cfg) == pytest.approx(mscale**2, rel=1e-12)
    assert mla_softmax_gain(cfg) == pytest.approx(1.5896, abs=1e-4)


def test_full_size_share_has_the_published_parameter_count():
    from repro.specs.spec import MODELS

    conf = json.loads((BENCH / "configs" / "deepseek_v2_lite_share8.json")
                      .read_text())
    model = MODELS["transformer"](**conf["model"]["kwargs"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == conf["params"] == 535_060_992
    moe = shapes["layers"][1]["sub0"]["ffn"]
    assert moe["router"].shape == (4, 2048, 64)
    assert moe["we_i"].shape == (4, 8, 2048, 1408)
    assert shapes["layers"][0]["sub0"]["ffn"]["wi"].shape == (1, 2048, 10944)
