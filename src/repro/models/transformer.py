"""Transformer substrate assembly: plan -> segments -> scanned stacks.

A ``ModelConfig`` is compiled to a per-layer *plan* (mixer kind + FFN kind),
the plan is grouped into *segments* (either N identical layers, or a
P-periodic super-block pattern like Jamba's [attn 1 : mamba 7]); each segment
is a ``lax.scan`` over stacked parameters so the HLO stays compact for
80-layer models. Caches thread through the same scans.

Public entry points (used by launch/, tests and benchmarks):

    model = TransformerLM(cfg)
    params = model.init(rng)
    loss, metrics = model.train_loss(params, batch)
    caches, logits = model.prefill(params, batch)
    logits, caches = model.decode_step(params, batch, caches)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import nn
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (
    attention_apply,
    attention_init,
    cross_attention_apply,
    cross_attention_init,
    embed_init,
    embed_lookup,
    init_attn_cache,
    init_mla_cache,
    mla_apply,
    mla_init,
    mlp_apply,
    mlp_init,
    moe_apply,
    moe_init,
    rmsnorm,
    rmsnorm_init,
)

# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str   # attn | mla | mamba | mlstm | slstm
    ffn: str     # mlp | moe | none
    cross: bool = False  # decoder cross-attention (enc-dec)
    dense_ff: int = 0    # ff size when ffn == mlp


def layer_plan(cfg: ModelConfig, *, decoder: bool = True) -> List[LayerSpec]:
    n = cfg.n_layers if decoder else cfg.encoder_layers
    plan = []
    for i in range(n):
        if not decoder:
            plan.append(LayerSpec("attn", "mlp", dense_ff=cfg.d_ff))
            continue
        # mixer
        if cfg.xlstm_pattern:
            kind = cfg.xlstm_pattern[i % len(cfg.xlstm_pattern)]
            plan.append(LayerSpec("mlstm" if kind == "m" else "slstm", "none"))
            continue
        if cfg.attn_period:
            # Jamba: one attention layer per period (at the middle slot, per
            # the released model), Mamba elsewhere; MoE every other layer.
            mixer = "attn" if i % cfg.attn_period == cfg.attn_period // 2 else "mamba"
        elif cfg.mla is not None:
            mixer = "mla"
        else:
            mixer = "attn"
        # ffn
        if cfg.moe is not None:
            mo = cfg.moe
            if i < mo.first_dense:
                # DeepSeek-style leading dense layers use a wider dense FFN.
                ffn, dff = "mlp", (_dense_ff(cfg) if cfg.arch_type == "moe" else cfg.d_ff)
            elif mo.every > 1 and i % mo.every != 1:
                # Jamba: MoE every other layer, plain MLP elsewhere.
                ffn, dff = "mlp", cfg.d_ff
            else:
                ffn, dff = "moe", 0
        else:
            ffn, dff = "mlp", cfg.d_ff
        plan.append(
            LayerSpec(mixer, ffn, cross=cfg.encoder_layers > 0, dense_ff=dff)
        )
    return plan


def _dense_ff(cfg: ModelConfig) -> int:
    """Dense-layer FFN width for MoE archs' leading dense layers (the
    DeepSeek model cards use a wider dense FFN than the per-expert width):
    ``moe.dense_d_ff`` where the config gives it, else the per-expert width
    times the experts a token reaches (routed top-k plus shared)."""
    mo = cfg.moe
    return mo.dense_d_ff or mo.d_ff * (mo.topk + mo.n_shared_experts)


@dataclasses.dataclass
class Segment:
    specs: Tuple[LayerSpec, ...]  # one period of the pattern
    repeats: int


def segment_plan(plan: List[LayerSpec]) -> List[Segment]:
    """Split the plan into scannable segments (see module docstring)."""
    if not plan:
        return []
    n = len(plan)
    # whole-plan periodicity (only useful when it yields >1 repeat)
    for P in range(1, n // 2 + 1):
        if n % P:
            continue
        if all(plan[i] == plan[i % P] for i in range(n)):
            return [Segment(tuple(plan[:P]), n // P)]
    # strip the longest identical prefix, recurse
    j = 1
    while j < n and plan[j] == plan[0]:
        j += 1
    return [Segment((plan[0],), j)] + segment_plan(plan[j:])


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def _sublayer_init(rng, spec: LayerSpec, cfg: ModelConfig, dtype):
    ks = jax.random.split(rng, 4)
    p: Dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model, dtype)}
    if spec.mixer == "attn":
        p["mixer"] = attention_init(ks[0], cfg, dtype)
    elif spec.mixer == "mla":
        p["mixer"] = mla_init(ks[0], cfg, dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = ssm_mod.mamba_init(ks[0], cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mixer"] = xlstm_mod.mlstm_init(ks[0], cfg, dtype)
    elif spec.mixer == "slstm":
        p["mixer"] = xlstm_mod.slstm_init(ks[0], cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.cross:
        p["cross_norm"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = cross_attention_init(ks[2], cfg, dtype)
    if spec.ffn == "mlp":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = mlp_init(ks[1], cfg.d_model, spec.dense_ff, dtype, gated=cfg.act != "relu")
    elif spec.ffn == "moe":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = moe_init(ks[1], cfg, dtype)
    return p


def _sublayer_cache(spec: LayerSpec, cfg: ModelConfig, batch, cache_len, window, dtype, memory_len=0):
    c: Dict[str, Any] = {}
    eff_len = min(cache_len, window) if window else cache_len
    if spec.mixer == "attn":
        c["mixer"] = init_attn_cache(cfg, batch, eff_len, dtype)
    elif spec.mixer == "mla":
        c["mixer"] = init_mla_cache(cfg, batch, eff_len, dtype)
    elif spec.mixer == "mamba":
        c["mixer"] = ssm_mod.init_mamba_cache(cfg, batch, dtype)
    elif spec.mixer == "mlstm":
        c["mixer"] = xlstm_mod.init_mlstm_cache(cfg, batch, dtype)
    elif spec.mixer == "slstm":
        c["mixer"] = xlstm_mod.init_slstm_cache(cfg, batch, dtype)
    if spec.cross:
        K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        c["cross"] = {
            "k": jnp.zeros((batch, memory_len, K, hd), dtype),
            "v": jnp.zeros((batch, memory_len, K, hd), dtype),
        }
    return c


def _sublayer_apply(
    p, spec: LayerSpec, cfg: ModelConfig, x, *, positions, cache, mode, window, memory
):
    new_cache: Dict[str, Any] = {}
    aux = 0.0
    held = None
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.mixer == "attn":
        out, mc, _ = attention_apply(
            p["mixer"], cfg, h, positions=positions, cache=None if cache is None else cache["mixer"],
            mode=mode, window=window,
        )
    elif spec.mixer == "mla":
        with jax.named_scope("model.mla"):
            out, mc, _ = mla_apply(
                p["mixer"], cfg, h, positions=positions,
                cache=None if cache is None else cache["mixer"], mode=mode,
                window=window,
            )
    elif spec.mixer == "mamba":
        out, mc = ssm_mod.mamba_apply(
            p["mixer"], cfg, h, cache=None if cache is None else cache["mixer"], mode=mode
        )
    elif spec.mixer == "mlstm":
        out, mc = xlstm_mod.mlstm_apply(
            p["mixer"], cfg, h, cache=None if cache is None else cache["mixer"], mode=mode
        )
    else:  # slstm
        out, mc = xlstm_mod.slstm_apply(
            p["mixer"], cfg, h, cache=None if cache is None else cache["mixer"], mode=mode
        )
    if mc is not None:
        new_cache["mixer"] = mc
    x = x + out
    if spec.cross:
        h = rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        out, cc, _ = cross_attention_apply(
            p["cross"], cfg, h, memory, cache=None if cache is None else cache.get("cross"),
            mode=mode,
        )
        if cc is not None:
            new_cache["cross"] = cc
        x = x + out
    if spec.ffn == "mlp":
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        with jax.named_scope("model.ffn"):
            x = x + mlp_apply(p["ffn"], h, cfg.act)
    elif spec.ffn == "moe":
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        with jax.named_scope("model.moe"):
            out, moe_aux, held = moe_apply(p["ffn"], cfg, h, cfg.act)
        aux = aux + moe_aux
        x = x + out
    return x, new_cache, aux, held


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------


def _stack_init(rng, segments: List[Segment], cfg: ModelConfig, dtype):
    params = []
    for si, seg in enumerate(segments):
        seg_rngs = jax.random.split(jax.random.fold_in(rng, si), seg.repeats)

        def one_repeat(r):
            ks = jax.random.split(r, len(seg.specs))
            return {
                f"sub{j}": _sublayer_init(ks[j], seg.specs[j], cfg, dtype)
                for j in range(len(seg.specs))
            }

        stacked = jax.vmap(one_repeat)(seg_rngs)
        params.append(stacked)
    return params


def _stack_cache(segments, cfg, batch, cache_len, window, dtype, memory_len=0):
    caches = []
    for seg in segments:
        one = {
            f"sub{j}": _sublayer_cache(
                seg.specs[j], cfg, batch, cache_len, window, dtype, memory_len
            )
            for j in range(len(seg.specs))
        }
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (seg.repeats,) + x.shape), one
        )
        caches.append(stacked)
    return caches


def _stack_apply(
    stack_params,
    segments: List[Segment],
    cfg: ModelConfig,
    x,
    *,
    positions,
    caches,
    mode,
    window,
    memory=None,
):
    """Returns ``(x, new_caches, aux, held)``: ``held`` is the (n_moe,)
    count of each MoE layer's assignments to the experts it holds, in layer
    order (empty without MoE layers)."""
    new_caches = []
    aux_total = 0.0
    held_all = []
    for si, seg in enumerate(segments):
        p_seg = stack_params[si]
        c_seg = None if caches is None else caches[si]

        def body(carry, xs):
            h, aux = carry
            p_rep, c_rep = xs
            nc_rep = {}
            held_rep = []
            for j in range(len(seg.specs)):
                h, nc, a, held = _sublayer_apply(
                    p_rep[f"sub{j}"],
                    seg.specs[j],
                    cfg,
                    h,
                    positions=positions,
                    cache=None if c_rep is None else c_rep[f"sub{j}"],
                    mode=mode,
                    window=window,
                    memory=memory,
                )
                nc_rep[f"sub{j}"] = nc
                aux = aux + a
                if held is not None:
                    held_rep.append(held)
            return (h, aux), (nc_rep, jnp.stack(held_rep) if held_rep else None)

        if cfg.remat:
            body = jax.checkpoint(body)

        if cfg.scan_layers and seg.repeats > 1:
            (x, aux_total), (nc, held) = jax.lax.scan(
                body, (x, aux_total), (p_seg, c_seg)
            )
            if held is not None:
                held_all.append(held.reshape(-1))
        else:
            ncs = []
            for r in range(seg.repeats):
                p_rep = jax.tree.map(lambda a: a[r], p_seg)
                c_rep = None if c_seg is None else jax.tree.map(lambda a: a[r], c_seg)
                (x, aux_total), (nc_rep, held) = body(
                    (x, aux_total), (p_rep, c_rep))
                ncs.append(nc_rep)
                if held is not None:
                    held_all.append(held)
            nc = (
                jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
                if ncs and any(jax.tree.leaves(n) for n in ncs)
                else {}
            )
        new_caches.append(nc)
    held = (jnp.concatenate(held_all) if held_all
            else jnp.zeros((0,), jnp.int32))
    return x, new_caches, aux_total, held


# ---------------------------------------------------------------------------
# Chunked cross-entropy
# ---------------------------------------------------------------------------


def chunked_cross_entropy(hidden, head_w, labels, chunk: int):
    """Mean next-token CE without materializing (B, S, V) logits: scan over
    sequence chunks, recomputing logits per chunk (memory-roofline
    optimization for 100k+ vocabularies)."""
    B, S, d = hidden.shape
    if chunk <= 0 or S <= chunk:
        logits = (hidden @ head_w).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    nc = hidden.shape[1] // chunk
    hc = hidden.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, chunk).transpose(1, 0, 2)
    valid = jnp.arange(nc * chunk).reshape(nc, chunk) < S

    def body(tot, inp):
        h, l, vmask = inp
        logits = (h @ head_w).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        ce = jnp.where(vmask[None, :], logz - gold, 0.0)
        return tot + jnp.sum(ce), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc, valid))
    return tot / (B * S)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


class TransformerLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan = layer_plan(cfg, decoder=True)
        self.segments = segment_plan(self.plan)
        if cfg.encoder_layers:
            self.enc_plan = layer_plan(cfg, decoder=False)
            self.enc_segments = segment_plan(self.enc_plan)
        else:
            self.enc_segments = []
        self.dtype = jnp.dtype(cfg.param_dtype)

    # -- init ---------------------------------------------------------------
    def init(self, rng):
        cfg = self.cfg
        ks = jax.random.split(rng, 5)
        params = {
            "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, self.dtype),
            "layers": _stack_init(ks[1], self.segments, cfg, self.dtype),
            "final_norm": rmsnorm_init(cfg.d_model, self.dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = nn.normal_init(
                ks[2], (cfg.d_model, cfg.vocab_size), 0.02, self.dtype
            )
        if self.enc_segments:
            params["encoder"] = {
                "layers": _stack_init(ks[3], self.enc_segments, cfg, self.dtype),
                "final_norm": rmsnorm_init(cfg.d_model, self.dtype),
            }
        return params

    # -- helpers ------------------------------------------------------------
    def _head(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"]["table"].T
        return params["lm_head"]

    def _embed_in(self, params, batch):
        cfg = self.cfg
        if cfg.modality == "vision" or "embeds" in batch:
            x = batch["embeds"].astype(self.dtype)
        elif cfg.embed_onehot:
            # One-hot matmul lookup: for tiny token counts (decode) this is
            # collective-free under a vocab-sharded table, where gather falls
            # back to a full table all-gather (XLA SPMD "involuntary full
            # rematerialization"). FLOPs cost B*V*d — negligible at S=1.
            tok = batch["tokens"]
            oh = jax.nn.one_hot(tok, cfg.vocab_size, dtype=self.dtype)
            x = oh @ params["embed"]["table"]
        else:
            x = embed_lookup(params["embed"], batch["tokens"])
        if cfg.tie_embeddings:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
        return x.astype(jnp.dtype(cfg.compute_dtype))

    def _positions(self, batch, S, offset=0):
        if "positions" in batch:
            return batch["positions"]
        B = (batch.get("tokens") if "tokens" in batch else batch["embeds"]).shape[0]
        return jnp.broadcast_to(offset + jnp.arange(S)[None, :], (B, S))

    def _encode(self, params, batch):
        cfg = self.cfg
        x = batch["enc_embeds"].astype(jnp.dtype(cfg.compute_dtype))
        B, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        # Bidirectional: reuse the attn stack with causal off via window trick?
        # Cleanest: temporarily run attention non-causally.
        x, _, _, _ = _stack_apply(
            params["encoder"]["layers"],
            self.enc_segments,
            dataclasses.replace(cfg, sliding_window=0),
            x,
            positions=pos,
            caches=None,
            mode="encode",
            window=0,
        )
        return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)

    # -- forward ------------------------------------------------------------
    def forward(self, params, batch, *, mode, caches=None, window=0):
        x, new_caches, aux, _ = self._forward(
            params, batch, mode=mode, caches=caches, window=window)
        return x, new_caches, aux

    def _forward(self, params, batch, *, mode, caches=None, window=0):
        """:meth:`forward`, plus each MoE layer's count of assignments to
        its held experts."""
        cfg = self.cfg
        with jax.named_scope("model.embed"):
            x = self._embed_in(params, batch)
        B, S, _ = x.shape
        offset = batch.get("pos_offset", 0)
        positions = self._positions(batch, S, offset)
        # In decode mode the cross-attention K/V live in the cache; skip the
        # encoder recompute entirely.
        memory = (
            self._encode(params, batch)
            if self.enc_segments and mode != "decode"
            else None
        )
        x, new_caches, aux, held = _stack_apply(
            params["layers"],
            self.segments,
            cfg,
            x,
            positions=positions,
            caches=caches,
            mode=mode,
            window=window,
            memory=memory,
        )
        with jax.named_scope("model.head"):
            x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, new_caches, aux, held

    # -- entry points ---------------------------------------------------------
    def train_loss(self, params, batch):
        """``(ce + aux, metrics)``, where ``moe.seq_aux`` gives aux its
        gradient but not its value. Where the MoE layers hold a share of
        the experts, ``metrics["counters"]["moe_held_assignments"]`` is each
        MoE layer's count of (token, expert) assignments that landed on its
        held experts."""
        cfg = self.cfg
        hidden, _, aux, held = self._forward(params, batch, mode="train",
                                             window=cfg.sliding_window)
        with jax.named_scope("model.head"):
            ce = chunked_cross_entropy(hidden, self._head(params),
                                       batch["labels"], cfg.ce_chunk)
        metrics = {"ce": ce, "aux": aux}
        if cfg.moe is not None and cfg.moe.n_held:
            metrics["counters"] = {
                "moe_held_assignments": held.astype(jnp.float32)}
        if cfg.moe is not None and cfg.moe.seq_aux:
            # As DeepSeek-V2's AddAuxiliaryLoss: the balance loss adds its
            # gradient; the loss reads the cross-entropy alone.
            return ce + (aux - jax.lax.stop_gradient(aux)), metrics
        return ce + aux, metrics

    def loss(self, params, batch):
        """(loss, aux-dict) signature compatible with core.fedavg."""
        if isinstance(batch, tuple):
            batch = {"tokens": batch[0], "labels": batch[1]}
        l, m = self.train_loss(params, batch)
        return l, m

    def init_caches(self, batch_size, cache_len, *, window=0, memory_len=0):
        return _stack_cache(
            self.segments, self.cfg, batch_size, cache_len, window, self.dtype,
            memory_len=memory_len,
        )

    def prefill(self, params, batch, *, cache_len=0, window=0):
        """Run the prompt through the stack, writing K/V (and recurrent
        states) into preallocated caches of ``cache_len`` slots (default: the
        prompt length; rolling when sliding-window is on)."""
        x = batch.get("tokens", batch.get("embeds"))
        B, S = x.shape[0], x.shape[1]
        cache_len = cache_len or S
        memory_len = batch["enc_embeds"].shape[1] if "enc_embeds" in batch else 0
        caches = self.init_caches(B, cache_len, window=window, memory_len=memory_len)
        hidden, caches, _ = self.forward(
            params, batch, mode="prefill", caches=caches, window=window
        )
        logits = (hidden[:, -1:] @ self._head(params)).astype(jnp.float32)
        return caches, logits

    def decode_step(self, params, batch, caches, *, window=0):
        """batch: {'tokens': (B,1)} or {'embeds': ...}, plus optional
        'positions'/'pos_offset'. Returns (logits (B,1,V), new_caches)."""
        hidden, new_caches, _ = self.forward(
            params, batch, mode="decode", caches=caches, window=window
        )
        logits = (hidden @ self._head(params)).astype(jnp.float32)
        return logits, new_caches


# ---------------------------------------------------------------------------
# Analytic parameter counts (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    """Parameter count from shapes (cheap — no init). active_only counts only
    topk+shared experts per MoE layer (for MODEL_FLOPS = 6*N_active*D)."""
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    if not active_only or cfg.moe is None:
        return total
    mo = cfg.moe
    per_expert = 3 * cfg.d_model * mo.d_ff  # wi, wg, wo
    n_moe_layers = sum(1 for s in layer_plan(cfg) if s.ffn == "moe")
    inactive = (mo.n_experts - mo.topk) * per_expert * n_moe_layers
    return total - inactive


# ---------------------------------------------------------------------------
# The registry's factory (specs.MODELS["transformer"])
# ---------------------------------------------------------------------------


def transformer(preset: str, **overrides):
    """A repo preset (``repro.configs.get_config(preset)``) as the federated
    engine's ``Model(init, apply, loss)``.

    ``overrides`` replace ``ModelConfig`` fields; ``moe``, ``mla`` and
    ``rope_scaling`` take a dict of the fields to replace in that group
    (e.g. ``moe={"n_held": 8, "held_first": 0}`` for one chip's share of
    an expert-parallel layer). ``apply(params, tokens)`` gives float32
    next-token logits ``(B, S, V)``; ``loss(params, (tokens, labels))`` is
    the mean next-token cross-entropy plus the router aux loss (its
    gradient only, with ``moe.seq_aux``), with ``{"ce", "aux"}`` (and the
    held-expert counters) beside it."""
    from repro.configs import get_config
    from repro.models.paper import Model

    cfg = get_config(preset)
    for group in ("moe", "mla", "rope_scaling"):
        if group in overrides:
            fields = overrides.pop(group)
            if getattr(cfg, group) is None:
                raise ValueError(f"preset {preset!r} has no {group!r} group "
                                 "to override")
            overrides[group] = dataclasses.replace(getattr(cfg, group),
                                                   **fields)
    cfg = dataclasses.replace(cfg, **overrides)
    lm = TransformerLM(cfg)

    def apply(params, tokens):
        hidden, _, _ = lm.forward(params, {"tokens": tokens}, mode="train",
                                  window=cfg.sliding_window)
        with jax.named_scope("model.head"):
            return (hidden @ lm._head(params)).astype(jnp.float32)

    return Model(lm.init, apply, lm.loss)
