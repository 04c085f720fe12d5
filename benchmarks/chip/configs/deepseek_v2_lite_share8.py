"""Plain reference of one chip's share of DeepSeek-V2-Lite, built from
``deepseek_v2_lite_share8.json``.

The layers follow the published modeling code (``DeepseekV2Attention``,
``DeepseekV2MoE``, ``DeepseekV2YarnRotaryEmbedding``) in straightforward
``jax.numpy``, written from the config's keys:

- RMSNorm in float32, cast back to the compute dtype.
- Multi-head latent attention without q-LoRA: q from one projection, the
  shared latent ``c`` (RMS-normed) and the shared rope key from a
  down-projection, per-head no-rope keys and values from ``c``; causal
  softmax over the full score matrix, at ``mscale(factor,
  mscale_all_dim)**2 / sqrt(qk_nope + qk_rope)``.
- YaRN rotary frequencies from the published formula; the rotary channels
  in the half-split layout (``rotate_half``), not the published interleave
  (the config's ``assumed``).
- The MoE layer: a float32 softmax router over all ``published``
  ``n_routed_experts``, top ``num_experts_per_tok``, gates not
  renormalised (``norm_topk_prob`` false); each held expert (``held_experts``:
  first index, count) applied densely to every token and weighted by its
  gate, which is zero where the token did not choose it; the experts held
  on other chips are left out, as on this chip; plus the shared experts.
  The sequence-level balance loss (``seq_aux``, ``aux_loss_alpha``) is
  added as the published ``AddAuxiliaryLoss`` adds it: its gradient joins
  the backward pass, and the logits (so the reported loss) are unchanged.
- Leading dense layers (``first_k_dense_replace``) of width
  ``intermediate_size``; SiLU-gated feed-forwards throughout.

Parameters are the program's layout: layers stacked per run of identical
layers (the dense layer, then the MoE layers), and ``apply`` loops over
each stack. Each layer is checkpointed, which changes no number and lets
the reference fit the chip.
"""
from __future__ import annotations

import functools
import json
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _glorot(key, shape, dtype):
    lim = math.sqrt(6.0 / (shape[0] + shape[1]))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def _normal(key, shape, dtype):
    return 0.02 * jax.random.normal(key, shape, dtype)


def _dims(config):
    c = config
    return dict(
        d=c["hidden_size"], heads=c["num_attention_heads"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
        v=c["v_head_dim"], lora=c["kv_lora_rank"],
        dense=c["intermediate_size"], expert=c["moe_intermediate_size"],
        shared=c["moe_intermediate_size"] * c["n_shared_experts"],
        experts=c["published"]["n_routed_experts"],
        held=c["held_experts"][1], first=c["held_experts"][0],
        k=c["num_experts_per_tok"], vocab=c["vocab_size"],
        dense_layers=c["first_k_dense_replace"],
        layers=c["num_hidden_layers"],
        alpha=c["aux_loss_alpha"] if c["seq_aux"] else 0.0,
    )


def _mlp_init(key, d, f, dtype):
    k = jax.random.split(key, 3)
    return {"wi": _glorot(k[0], (d, f), dtype), "wo": _glorot(k[1], (f, d), dtype),
            "wg": _glorot(k[2], (d, f), dtype)}


def _layer_init(key, m, moe, dtype):
    d, H = m["d"], m["heads"]
    k = jax.random.split(key, 6)
    p = {
        "norm1": {"scale": jnp.ones((d,), dtype)},
        "norm2": {"scale": jnp.ones((d,), dtype)},
        "mixer": {
            "wq": _glorot(k[0], (d, H * (m["nope"] + m["rope"])), dtype),
            "wkv_a": _glorot(k[1], (d, m["lora"] + m["rope"]), dtype),
            "kv_norm": {"scale": jnp.ones((m["lora"],), dtype)},
            "wkv_b": _glorot(k[2], (m["lora"], H * (m["nope"] + m["v"])), dtype),
            "wo": _glorot(k[3], (H * m["v"], d), dtype),
        },
    }
    if not moe:
        p["ffn"] = _mlp_init(k[4], d, m["dense"], dtype)
        return p
    e = jax.random.split(k[5], 4)
    p["ffn"] = {
        "router": _normal(e[0], (d, m["experts"]), dtype),
        "we_i": _normal(e[1], (m["held"], d, m["expert"]), dtype),
        "we_g": _normal(e[2], (m["held"], d, m["expert"]), dtype),
        "we_o": _normal(e[3], (m["held"], m["expert"], d), dtype),
        "shared": _mlp_init(k[4], d, m["shared"], dtype),
    }
    return p


def init(key, config, dtype=jnp.float32):
    m = _dims(config)
    k = jax.random.split(key, 4)
    n_dense, n_moe = m["dense_layers"], m["layers"] - m["dense_layers"]
    stack = lambda k_seg, n, moe: jax.vmap(
        lambda kk: {"sub0": _layer_init(kk, m, moe, dtype)}
    )(jax.random.split(k_seg, n))
    return {
        "embed": {"table": _normal(k[0], (m["vocab"], m["d"]), dtype)},
        "layers": [stack(k[1], n_dense, False), stack(k[2], n_moe, True)],
        "final_norm": {"scale": jnp.ones((m["d"],), dtype)},
        "lm_head": _normal(k[3], (m["d"], m["vocab"]), dtype),
    }


# -- YaRN rotary frequencies (DeepseekV2YarnRotaryEmbedding) -----------------

def yarn_get_mscale(scale, mscale):
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, base, rs):
    def correction_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low),
                   0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / rs["factor"]
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def _rope_tables(config, seq_len):
    rs = config["rope_scaling"]
    inv_freq = yarn_inv_freq(config["qk_rope_head_dim"], config["rope_theta"], rs)
    freqs = np.outer(np.arange(seq_len, dtype=np.float64), inv_freq)
    gain = (yarn_get_mscale(rs["factor"], rs["mscale"])
            / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
    return (jnp.asarray(np.cos(freqs) * gain, jnp.float32),
            jnp.asarray(np.sin(freqs) * gain, jnp.float32))


def _rotate(x, cos, sin):
    """Rotary embedding of ``x`` (B, S, H, D) in the half-split layout."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


# -- layers -------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mlp(p, x, dot):
    return dot(jax.nn.silu(dot(x, p["wg"])) * dot(x, p["wi"]), p["wo"])


def _attention(p, x, m, rope, softmax_scale, eps, precision):
    dot = partial(jnp.dot, precision=precision)
    dot_e = partial(jnp.einsum, precision=precision)
    B, S, _ = x.shape
    H, nope, r, vd = m["heads"], m["nope"], m["rope"], m["v"]
    q = dot(x, p["wq"]).reshape(B, S, H, nope + r)
    q_nope, q_pe = q[..., :nope], _rotate(q[..., nope:], *rope)
    kv = dot(x, p["wkv_a"])
    c = _rmsnorm(kv[..., :m["lora"]], p["kv_norm"]["scale"], eps)
    k_pe = _rotate(kv[..., None, m["lora"]:], *rope)             # (B,S,1,r)
    kv = dot(c, p["wkv_b"]).reshape(B, S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    qf = jnp.concatenate([q_nope, q_pe], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, r))], -1)
    scores = dot_e("bshd,bthd->bhst", qf, kf).astype(jnp.float32) * softmax_scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = dot_e("bhst,bthd->bshd", probs, v).reshape(B, S, H * vd)
    return dot(out, p["wo"])


@jax.custom_vjp
def _add_aux_loss(y, aux_loss):
    """``y`` unchanged; the backward pass gives ``aux_loss`` a gradient of
    one (DeepSeek-V2's ``AddAuxiliaryLoss``)."""
    return y


def _add_aux_loss_fwd(y, aux_loss):
    return y, aux_loss


def _add_aux_loss_bwd(aux_loss, g):
    return g, jnp.ones_like(aux_loss)


_add_aux_loss.defvjp(_add_aux_loss_fwd, _add_aux_loss_bwd)


def _seq_aux_loss(scores, idx, m, alpha):
    """MoEGate's sequence-level balance loss: per sequence, the count of
    each expert's picks over ``S * k / E``, times the expert's mean score;
    summed over experts, averaged over sequences, times ``alpha``."""
    B, S, E = scores.shape
    picks = idx.reshape(B, S * m["k"])
    ce = jnp.zeros((B, E), jnp.float32).at[
        jnp.arange(B)[:, None], picks].add(1.0) / (S * m["k"] / E)
    return jnp.mean(jnp.sum(ce * jnp.mean(scores, axis=1), axis=1)) * alpha


def _moe(p, x, m, precision):
    dot = partial(jnp.dot, precision=precision)
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    logits = dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    scores = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(scores, m["k"])                      # not renormalised
    aux = _seq_aux_loss(scores.reshape(B, S, -1), idx, m, m["alpha"])
    held = m["first"] + jnp.arange(m["held"])
    # (T, held): the gate of each held expert, zero where it was not chosen.
    g = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                          gate[:, :, None], 0.0), axis=1).astype(x.dtype)
    # Every held expert on every token, then weighted by its gate.
    ein = partial(jnp.einsum, precision=precision)
    h = jax.nn.silu(ein("td,edf->etf", xt, p["we_g"])) * ein(
        "td,edf->etf", xt, p["we_i"])
    out = ein("etf,efd->etd", h, p["we_o"])                     # (held, T, d)
    y = jnp.sum(g.T[:, :, None] * out, axis=0)
    return _add_aux_loss(y.reshape(B, S, d) + _mlp(p["shared"], x, dot), aux)


def _layer(p, h, *, m, moe, rope, softmax_scale, eps, precision):
    h = h + _attention(p["mixer"], _rmsnorm(h, p["norm1"]["scale"], eps), m,
                       rope, softmax_scale, eps, precision)
    a = _rmsnorm(h, p["norm2"]["scale"], eps)
    if moe:
        return h + _moe(p["ffn"], a, m, precision)
    return h + _mlp(p["ffn"], a, partial(jnp.dot, precision=precision))


def apply(params, x, precision, config=None):
    """Next-token logits (B, S, V) of token ids x (B, S), positions 0..S-1.
    ``config``: the configuration's keys (default: the ``.json`` beside
    this file)."""
    config = config or _config()
    m = _dims(config)
    eps = config["rms_norm_eps"]
    rs = config["rope_scaling"]
    softmax_scale = (yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
                     / math.sqrt(m["nope"] + m["rope"]))
    rope = _rope_tables(config, x.shape[1])
    h = params["embed"]["table"][x]
    for seg, moe in zip(params["layers"], (False, True)):
        layer = jax.checkpoint(partial(
            _layer, m=m, moe=moe, rope=rope, softmax_scale=softmax_scale,
            eps=eps, precision=precision))
        h, _ = jax.lax.scan(lambda h, p: (layer(p, h), None), h, seg["sub0"])
    h = _rmsnorm(h, params["final_norm"]["scale"], eps)
    return jnp.dot(h, params["lm_head"], precision=precision)


@functools.cache
def _config():
    return json.loads(Path(__file__).with_suffix(".json").read_text())
