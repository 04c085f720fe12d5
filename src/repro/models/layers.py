"""Transformer building blocks for the assigned architectures.

Every block is an (init, apply) pair over dict pytrees:

    apply(params, cfg, x, *, positions, cache, mode) -> (y, new_cache, aux)

``mode`` is one of "train" (no cache), "prefill" (build cache), "decode"
(one-token step against the cache). All matmuls run in cfg.compute_dtype;
norms and softmax statistics in float32.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import nn
from repro.models.attention_core import blocked_attention, decode_attention

# ---------------------------------------------------------------------------
# Norms / embeddings / RoPE
# ---------------------------------------------------------------------------


def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def embed_init(rng, vocab, d, dtype):
    return {"table": 0.02 * jax.random.normal(rng, (vocab, d), dtype)}


def embed_lookup(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1`` (1 for
    ``factor <= 1``), as DeepSeek-V2's ``yarn_get_mscale``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations, dim, theta, max_positions):
    """The rotary channel that turns ``rotations`` times over
    ``max_positions``."""
    return (dim * math.log(max_positions / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_freqs(head_dim: int, theta: float, scaling=None):
    """Inverse rotary frequencies, one per channel pair. With a
    ``YaRNConfig``: DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` blend,
    the original frequencies below the correction range that
    ``beta_fast``..``beta_slow`` rotations span at the original context,
    the ``factor``-interpolated ones above it, and a linear ramp between."""
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    if scaling is None:
        return freqs
    n = scaling.original_max_position_embeddings
    low = max(math.floor(_yarn_correction_dim(
        scaling.beta_fast, head_dim, theta, n)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        scaling.beta_slow, head_dim, theta, n)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x, positions, theta: float, mrope_sections=None,
               scaling=None):
    """x: (B, S, H, D); positions: (B, S) or (B, S, 3) for M-RoPE.

    M-RoPE (Qwen2-VL, arXiv:2409.12191): the D/2 rotary frequency channels
    are partitioned into (temporal, height, width) sections; each section is
    rotated by the corresponding component of the 3-D position id. For text
    tokens all three components are equal, recovering standard RoPE.

    ``scaling``: a ``YaRNConfig`` (:func:`rope_freqs`); cos and sin then
    carry ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
    """
    B, S, H, D = x.shape
    freqs = jnp.asarray(rope_freqs(D, theta, scaling), jnp.float32)  # (D/2,)
    if mrope_sections is None:
        if positions.ndim == 3:
            positions = positions[..., 0]
        angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    else:
        assert positions.ndim == 3 and positions.shape[-1] == 3
        sec = np.asarray(mrope_sections)
        assert sec.sum() == D // 2, (sec, D)
        comp = np.repeat(np.arange(3), sec)  # (D/2,) -> which position axis
        pos_per_freq = jnp.take(
            positions.astype(jnp.float32), jnp.asarray(comp), axis=-1
        )  # (B,S,D/2)
        angles = pos_per_freq * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None:
        gain = (yarn_mscale(scaling.factor, scaling.mscale)
                / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if gain != 1.0:
            cos, sin = cos * gain, sin * gain
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA / MQA attention (with optional QKV bias, sliding window, KV cache)
# ---------------------------------------------------------------------------


def attention_init(rng, cfg: ModelConfig, dtype):
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": nn.glorot(ks[0], (d, H * hd), dtype),
        "wk": nn.glorot(ks[1], (d, K * hd), dtype),
        "wv": nn.glorot(ks[2], (d, K * hd), dtype),
        "wo": nn.glorot(ks[3], (H * hd, d), dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _prefill_write(cache_buf, fresh):
    """Write S freshly-computed entries into a length-L preallocated cache.

    S == L: the fresh tensor IS the cache (pure relayout — no scatter).
    S <  L: zero-pad up to L (the preallocated cache is zeros; masking is by
            idx, so padding value is irrelevant) — still scatter-free, which
            matters because the cache length dim is sharded over the tensor
            axis (see sharding.cache_pspecs).
    S >  L: rolling window — slot t%L scatter (only reachable if a caller
            prefills past the window; the dry-run shapes never do)."""
    L, S = cache_buf.shape[1], fresh.shape[1]
    if S == L:
        return fresh.astype(cache_buf.dtype)
    if S < L:
        pad = [(0, 0)] * fresh.ndim
        pad[1] = (0, L - S)
        return jnp.pad(fresh, pad).astype(cache_buf.dtype)
    slots = jnp.arange(S) % L
    return cache_buf.at[:, slots].set(fresh.astype(cache_buf.dtype))


def init_attn_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cache_len, K, hd), dtype),
        "v": jnp.zeros((batch, cache_len, K, hd), dtype),
        "idx": jnp.zeros((), jnp.int32),  # absolute count of tokens written
    }


def attention_apply(
    p,
    cfg: ModelConfig,
    x,
    *,
    positions,
    cache=None,
    mode="train",
    window: int = 0,
):
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"] + p.get("bq", 0)).reshape(B, S, H, hd)
    k = (x @ p["wk"] + p.get("bk", 0)).reshape(B, S, K, hd)
    v = (x @ p["wv"] + p.get("bv", 0)).reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    if cfg.shard_attn_batch_over_model and mode != "decode":
        # Head-gated archs can't tensor-shard attention; fold the model axis
        # into batch parallelism instead (one all-to-all in, one out).
        from jax.sharding import PartitionSpec as _P

        q = jax.lax.with_sharding_constraint(q, _P(("data", "model"), None, None, None))
        k = jax.lax.with_sharding_constraint(k, _P(("data", "model"), None, None, None))
        v = jax.lax.with_sharding_constraint(v, _P(("data", "model"), None, None, None))

    if mode == "decode":
        assert cache is not None and S == 1
        cache_len = cache["k"].shape[1]
        slot = cache["idx"] % cache_len  # rolling for sliding-window caches
        k_c = cache["k"].at[:, slot].set(k[:, 0])
        v_c = cache["v"].at[:, slot].set(v[:, 0])
        valid = jnp.minimum(cache["idx"] + 1, cache_len)
        out = decode_attention(q, k_c, v_c, valid)
        new_cache = {"k": k_c, "v": v_c, "idx": cache["idx"] + 1}
    else:
        out = blocked_attention(
            q,
            k,
            v,
            causal=(mode != "encode"),  # encoder stacks are bidirectional
            window=window,
            q_chunk=cfg.attn_q_chunk,
            k_chunk=cfg.attn_k_chunk,
        )
        if mode == "prefill":
            if cache is not None:
                new_cache = {
                    "k": _prefill_write(cache["k"], k),
                    "v": _prefill_write(cache["v"], v),
                    "idx": jnp.asarray(S, jnp.int32),
                }
            else:
                new_cache = {"k": k, "v": v, "idx": jnp.asarray(S, jnp.int32)}
        else:
            new_cache = None
    y = out.reshape(B, S, H * hd) @ p["wo"]
    return y, new_cache, 0.0


def cross_attention_init(rng, cfg: ModelConfig, dtype):
    return attention_init(rng, dataclasses.replace(cfg, attn_bias=False), dtype)


def cross_attention_apply(p, cfg: ModelConfig, x, memory, *, cache=None, mode="train"):
    """Encoder-decoder cross attention; K/V from encoder memory are position-
    free (no RoPE on cross attention, per standard enc-dec practice). In
    decode mode the projected memory K/V are computed once at prefill and
    carried in the cache."""
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    if cache is not None and mode == "decode":
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        M = memory.shape[1]
        k = (memory @ p["wk"]).reshape(B, M, K, hd)
        v = (memory @ p["wv"]).reshape(B, M, K, hd)
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    out = blocked_attention(
        q, k, v, causal=False, q_chunk=cfg.attn_q_chunk, k_chunk=cfg.attn_k_chunk
    )
    y = out.reshape(B, S, H * hd) @ p["wo"]
    return y, new_cache, 0.0


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek V2/V3)
# ---------------------------------------------------------------------------


def mla_init(rng, cfg: ModelConfig, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(rng, 6)
    p = {}
    if m.q_lora_rank:
        p["wq_a"] = nn.glorot(ks[0], (d, m.q_lora_rank), dtype)
        p["q_norm"] = rmsnorm_init(m.q_lora_rank, dtype)
        p["wq_b"] = nn.glorot(ks[1], (m.q_lora_rank, H * qk_dim), dtype)
    else:
        p["wq"] = nn.glorot(ks[0], (d, H * qk_dim), dtype)
    # Down-projection to the shared latent + the shared rope key.
    p["wkv_a"] = nn.glorot(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim), dtype)
    p["kv_norm"] = rmsnorm_init(m.kv_lora_rank, dtype)
    # Up-projection from latent to per-head K_nope and V.
    p["wkv_b"] = nn.glorot(
        ks[3], (m.kv_lora_rank, H * (m.qk_nope_dim + m.v_head_dim)), dtype
    )
    p["wo"] = nn.glorot(ks[4], (H * m.v_head_dim, d), dtype)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    m = cfg.mla
    return {
        "latent": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, m.qk_rope_dim), dtype),
        "idx": jnp.zeros((), jnp.int32),
    }


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank:
        q = rmsnorm(p["q_norm"], x @ p["wq_a"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, S, H, qk_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta,
                        scaling=cfg.rope_scaling)
    return q_nope, q_rope


def mla_softmax_gain(cfg: ModelConfig) -> float:
    """The factor on MLA's ``1/sqrt(qk_head_dim)`` softmax scale: YaRN's
    ``mscale(factor, mscale_all_dim)**2`` (DeepseekV2Attention), else 1."""
    y = cfg.rope_scaling
    if y is None or not y.mscale_all_dim:
        return 1.0
    return yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def mla_apply(p, cfg: ModelConfig, x, *, positions, cache=None, mode="train", window: int = 0):
    """MLA. Train/prefill: naive up-projection (matches the reference
    formulation). Decode: ABSORBED form — W_UK folded into the query and W_UV
    into the output so attention runs directly against the cached latent
    (this is the TPU-friendly inference path; see DESIGN.md)."""
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _mla_q(p, cfg, x, positions)

    kv = x @ p["wkv_a"]
    latent = rmsnorm(p["kv_norm"], kv[..., : m.kv_lora_rank], cfg.norm_eps)
    k_rope_in = kv[..., m.kv_lora_rank :][:, :, None, :]  # (B,S,1,rope)
    k_rope = apply_rope(k_rope_in, positions, cfg.rope_theta,
                        scaling=cfg.rope_scaling)[:, :, 0, :]
    gain = mla_softmax_gain(cfg)

    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H, m.qk_nope_dim + m.v_head_dim)
    w_uk = wkv_b[..., : m.qk_nope_dim]  # (R, H, nope)
    w_uv = wkv_b[..., m.qk_nope_dim :]  # (R, H, vdim)

    if mode == "decode":
        assert cache is not None and S == 1
        cache_len = cache["latent"].shape[1]
        slot = cache["idx"] % cache_len
        lat_c = cache["latent"].at[:, slot].set(latent[:, 0])
        kr_c = cache["k_rope"].at[:, slot].set(k_rope[:, 0])
        valid = jnp.minimum(cache["idx"] + 1, cache_len)
        # Absorbed scores: q_eff = q_nope . W_UK  -> latent space.
        q_eff = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
        s_lat = jnp.einsum("bshr,btr->bhst", q_eff, lat_c.astype(jnp.float32))
        s_rope = jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32), kr_c.astype(jnp.float32))
        scale = gain / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
        s = (s_lat + s_rope) * scale
        pos = jnp.arange(cache_len)
        maskv = pos[None, :] < valid
        if window:
            maskv &= True  # rolling cache: all resident entries are in-window
        s = jnp.where(maskv[:, None, None, :] if maskv.ndim == 2 else maskv, s, -1e30)
        probs = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, lat_c.astype(jnp.float32))
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv.astype(jnp.float32)).astype(x.dtype)
        new_cache = {"latent": lat_c, "k_rope": kr_c, "idx": cache["idx"] + 1}
    else:
        k_nope = jnp.einsum("btr,rhn->bthn", latent, w_uk)
        v = jnp.einsum("btr,rhv->bthv", latent, w_uv)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_dim))],
            axis=-1,
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        if gain != 1.0:
            # blocked_attention scales by 1/sqrt(qk_dim); YaRN's temperature
            # rides on the query.
            q_full = q_full * jnp.asarray(gain, q_full.dtype)
        # Pad V up to the qk head dim so we can reuse blocked_attention, then
        # slice back (vdim <= qk_dim always holds for the deepseek configs).
        qk_dim = m.qk_nope_dim + m.qk_rope_dim
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - m.v_head_dim)))
        out = blocked_attention(
            q_full, k_full, v_pad, causal=True, window=window,
            q_chunk=cfg.attn_q_chunk, k_chunk=cfg.attn_k_chunk,
        )[..., : m.v_head_dim]
        if mode == "prefill":
            if cache is not None:
                new_cache = {
                    "latent": _prefill_write(cache["latent"], latent),
                    "k_rope": _prefill_write(cache["k_rope"], k_rope),
                    "idx": jnp.asarray(S, jnp.int32),
                }
            else:
                new_cache = {
                    "latent": latent,
                    "k_rope": k_rope,
                    "idx": jnp.asarray(S, jnp.int32),
                }
        else:
            new_cache = None
    y = out.reshape(B, S, H * m.v_head_dim) @ p["wo"]
    return y, new_cache, 0.0


# ---------------------------------------------------------------------------
# MLPs: SwiGLU / GeGLU / ReLU
# ---------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True), "relu": jax.nn.relu}


def mlp_init(rng, d_model, d_ff, dtype, gated=True):
    ks = jax.random.split(rng, 3)
    p = {
        "wi": nn.glorot(ks[0], (d_model, d_ff), dtype),
        "wo": nn.glorot(ks[1], (d_ff, d_model), dtype),
    }
    if gated:
        p["wg"] = nn.glorot(ks[2], (d_model, d_ff), dtype)
    return p


def mlp_apply(p, x, act="silu"):
    h = x @ p["wi"]
    if "wg" in p:
        h = h * _ACTS[act](x @ p["wg"])
    else:
        h = _ACTS[act](h)
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# Mixture of Experts: the capacity form (sort-based grouped dispatch) and one
# chip's dropless share of an expert-parallel layer (grouped matmuls)
# ---------------------------------------------------------------------------


def moe_init(rng, cfg: ModelConfig, dtype):
    """Router over all ``n_experts``; expert weights for the experts this
    layer holds (``n_held`` of them where set, else all)."""
    mo = cfg.moe
    d = cfg.d_model
    n = mo.n_held or mo.n_experts
    ks = jax.random.split(rng, 5)
    p = {
        "router": nn.normal_init(ks[0], (d, mo.n_experts), 0.02, jnp.float32),
        "we_i": nn.normal_init(ks[1], (n, d, mo.d_ff), 0.02, dtype),
        "we_g": nn.normal_init(ks[2], (n, d, mo.d_ff), 0.02, dtype),
        "we_o": nn.normal_init(ks[3], (n, mo.d_ff, d), 0.02, dtype),
    }
    if mo.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, mo.d_ff * mo.n_shared_experts, dtype)
    return p


def moe_apply(p, cfg: ModelConfig, x, act="silu"):
    """Token-choice top-k routing over all ``n_experts``, then the routed
    experts this layer holds, plus the shared experts.

    Gates are the router's softmax (or sigmoid) scores of the top k,
    renormalised to sum to 1 when ``norm_topk_prob``. With ``n_held`` set
    the layer is one chip's share of an expert-parallel layer: it computes
    only its held experts' part of the result, dropless
    (:func:`_held_experts`); otherwise every expert, through the capacity
    buffer (:func:`_capacity_experts`). Returns ``(y, aux, counters)``:
    the output, the load-balance loss (0 when ``router_aux_weight`` is 0;
    per sequence with ``seq_aux``) and, with ``n_held`` set, the layer's
    counters: the number of (token, expert) assignments that landed on held
    experts (``moe_held_assignments``) and whether they ran in the compact
    buffer (``moe_compact_buffer``); none otherwise.
    """
    mo = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    E, k = mo.n_experts, mo.topk

    with jax.named_scope("model.moe.router"):
        logits = (xt.astype(jnp.float32)) @ p["router"]
        if mo.router_scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        gate, expert_idx = jax.lax.top_k(scores, k)  # (T,k)
        if mo.norm_topk_prob:
            gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True),
                                      1e-9)
        aux = 0.0
        if mo.router_aux_weight and mo.seq_aux:
            # DeepSeek-V2's sequence-level balance loss: per sequence,
            # sum_e (E * count_e / (S*k)) * mean score_e, averaged over the
            # B sequences. The counts carry no gradient.
            counts = jnp.sum(jax.nn.one_hot(expert_idx.reshape(B, S * k), E,
                                            dtype=jnp.float32), axis=1)
            p_seq = jnp.mean(scores.reshape(B, S, E), axis=1)
            aux = mo.router_aux_weight * jnp.mean(
                jnp.sum(counts * (E / (S * k)) * p_seq, axis=-1))
        elif mo.router_aux_weight:
            # Load-balance aux loss (Switch-style): E * sum_e f_e * P_e.
            probs_mean = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
            counts = jnp.zeros((E,), jnp.float32).at[
                expert_idx.reshape(-1)].add(1.0)
            frac = counts / (T * k)
            aux = mo.router_aux_weight * E * jnp.sum(frac * probs_mean)

    if mo.n_held:
        y, counters = _held_experts(p, mo, xt, gate, expert_idx, act)
    else:
        y = _capacity_experts(p, mo, xt, gate, expert_idx, act)
        counters = {}
    y = y.reshape(B, S, d)

    if mo.n_shared_experts:
        with jax.named_scope("model.moe.shared"):
            y = y + mlp_apply(p["shared"], x, act)
    return y, aux, counters


def compact_rows(T: int, k: int, n_held: int, n_experts: int) -> int:
    """Rows of the held experts' compact assignment buffer: twice the
    ``T*k*n_held/n_experts`` assignments a uniform router sends to the
    held experts, rounded up to a multiple of 128."""
    rows = -(-2 * T * k * n_held // n_experts)
    return -(-rows // 128) * 128


def _held_experts(p, mo, xt, gate, expert_idx, act):
    """The held experts' part of the layer, dropless: every (token, expert)
    assignment to experts ``held_first .. held_first + n_held - 1`` is
    computed, by grouped matmuls (``jax.lax.ragged_dot``) over the
    assignments sorted by expert; assignments to experts held elsewhere sort
    after them and contribute nothing here.

    The experts run over the first C = :func:`compact_rows` sorted
    assignments where the held count fits in them, and over all T*k, the
    most that can land here, where it does not: the device picks the
    branch from the count, so nothing is ever dropped. Where C is not under
    T*k (a layer that holds half the experts or more, or so few assignments
    that rounding up to 128 reaches T*k) only the T*k buffer
    is built. Returns ``(y (T, d), counters)``: ``moe_held_assignments``,
    the held count, and ``moe_compact_buffer``, 1 where the compact buffer
    ran.
    """
    T = xt.shape[0]
    k = expert_idx.shape[1]
    H = mo.n_held
    with jax.named_scope("model.moe.dispatch"):
        local = expert_idx.reshape(-1) - mo.held_first        # (T*k,)
        group = jnp.where((local >= 0) & (local < H), local, H)
        order = jnp.argsort(group, stable=True)               # held first
        sizes = jnp.sum(group[:, None] == jnp.arange(H)[None, :], axis=0,
                        dtype=jnp.int32)                      # (H,)
        held = jnp.sum(sizes)
    args = (xt, gate, p["we_i"], p["we_g"], p["we_o"], (order, sizes, held))
    C = compact_rows(T, k, H, mo.n_experts)
    if C < T * k:
        y = _compact_or_full(*args, C, act)
        compact = (held <= C).astype(jnp.int32)
    else:
        y = _held_rows(*args, n_rows=T * k, act=act)
        compact = jnp.asarray(0, jnp.int32)
    return y.astype(xt.dtype), {"moe_held_assignments": held,
                                "moe_compact_buffer": compact}


def _held_rows(xt, gate, we_i, we_g, we_o, route, *, n_rows, act):
    """The held experts over the first ``n_rows`` sorted assignments, which
    hold every held one. Rows past the held groups belong to no group: they
    are zeroed on the way in and out, so nothing the grouped matmuls leave
    there reaches the result or the gradients. Combine adds each row, times
    its gate, into its token's row."""
    order, sizes, held = route
    T, d = xt.shape
    k = gate.shape[1]
    with jax.named_scope("model.moe.dispatch"):
        first = order[:n_rows]
        tok = first // k
        in_group = (jnp.arange(n_rows) < held)[:, None]
        rows = jnp.where(in_group, jnp.take(xt, tok, axis=0), 0)
    with jax.named_scope("model.moe.experts"):
        h = jax.lax.ragged_dot(rows, we_i, sizes)
        h = h * _ACTS[act](jax.lax.ragged_dot(rows, we_g, sizes))
        out = jax.lax.ragged_dot(h, we_o, sizes)          # (n_rows, d)
    with jax.named_scope("model.moe.combine"):
        w = gate.reshape(-1)[first].astype(out.dtype)
        out = jnp.where(in_group, out, 0) * w[:, None]
        return jnp.zeros((T, d), out.dtype).at[tok].add(out)


def _on_held_count(n_rows, args, at):
    """``at(n_rows)(*args)`` where the held count fits ``n_rows``, else
    ``at(T*k)(*args)``: a conditional the device evaluates."""
    T, k = args[1].shape
    held = args[5][2]
    return jax.lax.cond(held <= n_rows, at(n_rows), at(T * k), *args)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _compact_or_full(xt, gate, we_i, we_g, we_o, route, n_rows, act):
    """:func:`_held_rows` over ``n_rows`` rows where the held count fits
    them, else over all T*k. Its backward pass differentiates the branch
    taken from the inputs alone: differentiated through the conditional,
    JAX would join both branches' residuals, so the compact branch would
    carry zeros of the T*k branch's (T*k, .) shapes."""
    return _compact_or_full_fwd(xt, gate, we_i, we_g, we_o, route, n_rows,
                                act)[0]


def _compact_or_full_fwd(xt, gate, we_i, we_g, we_o, route, n_rows, act):
    args = (xt, gate, we_i, we_g, we_o, route)
    y = _on_held_count(n_rows, args,
                       lambda n: partial(_held_rows, n_rows=n, act=act))
    return y, args


def _compact_or_full_bwd(n_rows, act, args, g):
    def vjp_at(n):
        def run(xt, gate, we_i, we_g, we_o, route):
            layer = partial(_held_rows, route=route, n_rows=n, act=act)
            return jax.vjp(layer, xt, gate, we_i, we_g, we_o)[1](g)
        return run
    grads = _on_held_count(n_rows, args, vjp_at)
    # The barrier keeps XLA from moving what follows into the branches:
    # there, the transpose of a stacked layer's weight slice (a pad to the
    # stack's shape) made each branch write whole-stack weight gradients.
    return (*jax.lax.optimization_barrier(grads), None)


_compact_or_full.defvjp(_compact_or_full_fwd, _compact_or_full_bwd)


def _capacity_experts(p, mo, xt, gate, expert_idx, act):
    """Every expert's part through an (E, capacity) buffer per token group.

    Tokens are split into groups (so scatter indices stay group-local and the
    dispatch buffers shard over the data axis); within a group, the (token,
    expert) assignments are sorted by expert and packed into an (E, C)
    capacity buffer; overflow tokens are dropped (capacity_factor). Expert
    FFNs run as one batched einsum sharded over the expert axis. Returns
    ``y (T, d)``.
    """
    T, d = xt.shape
    E, k = mo.n_experts, mo.topk
    # Group tokens so dispatch indices stay group-local: (G, gs). Everything
    # below is BATCHED GATHERS over the G axis (no forward scatter): GSPMD
    # partitions batched take_along_axis over the data axis cleanly, whereas
    # scattering into the (E, cap) buffer degenerates to an all-gather of
    # the whole buffer.
    gs = min(mo.group_size, T)
    while T % gs:
        gs //= 2
    G = T // gs
    cap = int(np.ceil(gs * k / E * mo.capacity_factor))

    with jax.named_scope("model.moe.dispatch"):
        e_g = expert_idx.reshape(G, gs * k)
        g_g = gate.reshape(G, gs * k).astype(xt.dtype)
        x_g = xt.reshape(G, gs, d)

        sort_idx = jnp.argsort(e_g, axis=-1, stable=True)      # (G, gs*k)
        sorted_e = jnp.take_along_axis(e_g, sort_idx, axis=-1)
        # first[e] / counts[e]: range of expert e's assignments in sorted
        # order.
        eye = jnp.arange(E)
        first = jax.vmap(
            lambda se: jnp.searchsorted(se, eye, side="left"))(sorted_e)
        cnt_e = jax.vmap(
            lambda se: jnp.searchsorted(se, eye, side="right"))(sorted_e) - first

        # Dispatch: slot (e, c) reads sorted position first[e] + c (masked).
        slot_pos = first[:, :, None] + jnp.arange(cap)[None, None, :]
        valid = jnp.arange(cap)[None, None, :] < cnt_e[:, :, None]
        slot_pos = jnp.clip(slot_pos, 0, gs * k - 1).reshape(G, E * cap)
        assign = jnp.take_along_axis(sort_idx, slot_pos, axis=1)  # (G,E*cap)
        tok = assign // k
        buf = jnp.take_along_axis(x_g, tok[..., None], axis=1)   # (G,E*cap,d)
        buf = jnp.where(valid.reshape(G, E * cap, 1), buf, 0).reshape(
            G, E, cap, d)

    with jax.named_scope("model.moe.experts"):
        h = jnp.einsum("gecd,edf->gecf", buf, p["we_i"])
        h = h * _ACTS[act](jnp.einsum("gecd,edf->gecf", buf, p["we_g"]))
        out_buf = jnp.einsum("gecf,efd->gecd", h, p["we_o"])    # (G,E,cap,d)

    with jax.named_scope("model.moe.combine"):
        # Combine: assignment j reads back its slot e_j*cap + rank_j (gather,
        # not scatter-add: the k contributions per token reduce with a dense
        # sum).
        inv = jnp.argsort(sort_idx, axis=-1, stable=True)       # (G, gs*k)
        rank_sorted = jnp.arange(gs * k)[None, :] - jnp.take_along_axis(
            first, sorted_e, axis=1
        )
        rank_j = jnp.take_along_axis(rank_sorted, inv, axis=1)  # (G, gs*k)
        keep_j = rank_j < cap
        slot_j = e_g * cap + jnp.minimum(rank_j, cap - 1)
        contrib = jnp.take_along_axis(
            out_buf.reshape(G, E * cap, d), slot_j[..., None], axis=1
        )  # (G, gs*k, d)
        w = (g_g * keep_j)[..., None]
        y = jnp.sum((contrib * w).reshape(G, gs, k, d), axis=2)
    return y.reshape(T, d)
