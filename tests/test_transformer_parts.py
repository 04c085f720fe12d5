"""Unit tests for substrate pieces: MoE dispatch, MLA, chunked CE, M-RoPE,
mLSTM chunkwise form, mamba decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig, MoEConfig, reduced
from repro.models.layers import (
    apply_rope,
    init_mla_cache,
    mla_apply,
    mla_init,
    moe_apply,
    moe_init,
)
from repro.models.transformer import chunked_cross_entropy, layer_plan, segment_plan
from repro.models.xlstm import _mlstm_chunkwise, _mlstm_step


def _moe_cfg(E=4, k=2, cf=8.0, shared=0):
    return ModelConfig(
        name="t", arch_type="moe", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab_size=64,
        moe=MoEConfig(n_experts=E, topk=k, d_ff=32, capacity_factor=cf,
                      n_shared_experts=shared, group_size=8),
    )


def _moe_dense_oracle(p, cfg, x, act="silu"):
    """Every token through its top-k experts, no capacity drop."""
    mo = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    logits = xt.astype(jnp.float32) @ p["router"]
    scores = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(scores, mo.topk)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    y = jnp.zeros_like(xt)
    for e in range(mo.n_experts):
        h = (xt @ p["we_i"][e]) * jax.nn.silu(xt @ p["we_g"][e])
        out_e = h @ p["we_o"][e]
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1, keepdims=True)
        y = y + out_e * w.astype(xt.dtype)
    return y.reshape(B, S, d)


def test_moe_matches_dense_oracle(rng):
    cfg = _moe_cfg()
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 8, 16)).astype(np.float32))
    y, aux, _ = moe_apply(p, cfg, x)
    want = _moe_dense_oracle(p, cfg, x)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert float(aux) >= 0


def test_moe_capacity_drops_tokens(rng):
    """With capacity_factor ~0, almost everything is dropped -> output ~0."""
    cfg = _moe_cfg(cf=0.01)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 8, 16)).astype(np.float32))
    y, _, _ = moe_apply(p, cfg, x)
    dense = _moe_dense_oracle(p, cfg, x)
    assert float(jnp.sum(jnp.abs(y))) < float(jnp.sum(jnp.abs(dense)))


def test_moe_shared_expert_added(rng):
    cfg = _moe_cfg(shared=1)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 8, 16)).astype(np.float32))
    y, _, _ = moe_apply(p, cfg, x)
    cfg0 = _moe_cfg(shared=0)
    y0, _, _ = moe_apply({k: v for k, v in p.items() if k != "shared"}, cfg0, x)
    from repro.models.layers import mlp_apply
    np.testing.assert_allclose(y, y0 + mlp_apply(p["shared"], x, "silu"), atol=1e-5)


def test_mla_decode_equals_train(rng):
    cfg = dataclasses.replace(
        reduced(get_config("deepseek-v2-lite-16b")), moe=None
    )
    p = mla_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    B, S = 1, 8
    x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)).astype(np.float32))
    pos = jnp.arange(S)[None, :]
    y_full, _, _ = mla_apply(p, cfg, x, positions=pos, mode="train")
    cache = init_mla_cache(cfg, B, S, jnp.float32)
    ys = []
    for t in range(S):
        y_t, cache, _ = mla_apply(
            p, cfg, x[:, t : t + 1], positions=pos[:, t : t + 1],
            cache=cache, mode="decode",
        )
        ys.append(y_t)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_full, atol=1e-5)


def test_mla_cache_is_latent_sized():
    cfg = get_config("deepseek-v3-671b")
    cache = jax.eval_shape(lambda: init_mla_cache(cfg, 1, 100, jnp.bfloat16))
    # latent (kv_lora 512) + rope (64), NOT heads*head_dim*2 = 32768 per token
    per_token = cache["latent"].shape[-1] + cache["k_rope"].shape[-1]
    assert per_token == 512 + 64
    full_kv = 2 * cfg.n_kv_heads * cfg.resolved_head_dim
    assert per_token * 20 < full_kv  # >20x cache compression


def test_chunked_ce_matches_full(rng):
    B, S, d, V = 2, 24, 8, 50
    hid = jnp.asarray(rng.normal(size=(B, S, d)).astype(np.float32))
    head = jnp.asarray(rng.normal(size=(d, V)).astype(np.float32))
    lbl = jnp.asarray(rng.integers(0, V, (B, S)).astype(np.int32))
    full = chunked_cross_entropy(hid, head, lbl, 0)
    for chunk in (5, 8, 24):
        np.testing.assert_allclose(
            chunked_cross_entropy(hid, head, lbl, chunk), full, rtol=1e-6
        )


def test_chunked_ce_grad_matches(rng):
    B, S, d, V = 1, 16, 8, 30
    hid = jnp.asarray(rng.normal(size=(B, S, d)).astype(np.float32))
    head = jnp.asarray(rng.normal(size=(d, V)).astype(np.float32))
    lbl = jnp.asarray(rng.integers(0, V, (B, S)).astype(np.int32))
    g1 = jax.grad(lambda h: chunked_cross_entropy(hid, h, lbl, 0))(head)
    g2 = jax.grad(lambda h: chunked_cross_entropy(hid, h, lbl, 7))(head)
    np.testing.assert_allclose(g1, g2, atol=1e-6)


def test_mrope_text_tokens_match_standard_rope(rng):
    """M-RoPE with equal (t,h,w) position ids == standard RoPE (paper claim)."""
    B, S, H, D = 1, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    pos = jnp.arange(S)[None, :]
    pos3 = jnp.broadcast_to(pos[..., None], (B, S, 3))
    a = apply_rope(x, pos, 10000.0)
    b = apply_rope(x, pos3, 10000.0, mrope_sections=(4, 2, 2))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_mrope_distinguishes_spatial_positions(rng):
    x = jnp.asarray(rng.normal(size=(1, 2, 1, 16)).astype(np.float32))
    p1 = jnp.asarray([[[0, 0, 0], [1, 1, 1]]])
    p2 = jnp.asarray([[[0, 0, 0], [1, 2, 1]]])  # different height id
    a = apply_rope(x, p1, 10000.0, mrope_sections=(4, 2, 2))
    b = apply_rope(x, p2, 10000.0, mrope_sections=(4, 2, 2))
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4


def test_mlstm_chunkwise_matches_sequential(rng):
    B, S, H, hd = 1, 29, 2, 4
    mk = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    q, k, v = mk(B, S, H, hd), mk(B, S, H, hd), mk(B, S, H, hd)
    ig = mk(B, S, H) * 2
    fg = jax.nn.log_sigmoid(mk(B, S, H) + 2)
    carry0 = (jnp.zeros((B, H, hd, hd)), jnp.zeros((B, H, hd)),
              jnp.full((B, H), -1e30))
    c = carry0
    ys = []
    for t in range(S):
        c, y = _mlstm_step(c, (q[:, t], k[:, t], v[:, t], ig[:, t], fg[:, t]))
        ys.append(y)
    y_seq = jnp.stack(ys, 1)
    (C, n, m), y_chk = _mlstm_chunkwise(carry0, q, k, v, ig, fg, chunk=8)
    np.testing.assert_allclose(y_chk, y_seq, atol=1e-4)
    np.testing.assert_allclose(C, c[0], atol=1e-4)


def test_layer_plans():
    jamba = get_config("jamba-v0.1-52b")
    plan = layer_plan(jamba)
    assert sum(1 for s in plan if s.mixer == "attn") == 4   # 1:7 over 32 layers
    assert sum(1 for s in plan if s.ffn == "moe") == 16     # every other layer
    segs = segment_plan(plan)
    assert len(segs) == 1 and len(segs[0].specs) == 8 and segs[0].repeats == 4
    v3 = get_config("deepseek-v3-671b")
    plan = layer_plan(v3)
    assert sum(1 for s in plan if s.ffn == "mlp") == 3      # first_dense
    assert sum(1 for s in plan if s.ffn == "moe") == 58
    segs = segment_plan(plan)
    assert [s.repeats for s in segs] == [3, 58]
    xl = get_config("xlstm-350m")
    plan = layer_plan(xl)
    assert sum(1 for s in plan if s.mixer == "slstm") == 3  # 1 per 8 of 24
