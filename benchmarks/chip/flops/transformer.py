"""Training FLOPs of a DeepSeek-V2-style share (``configs/<name>.json`` with
``"model": {"kind": "transformer"}``), per sequence of ``seq_len`` tokens,
from the configuration's keys.

Conventions:

- A multiply-add counts 2 FLOPs; norms, activations, softmax, rotary
  embedding, the router's top-k and the dispatch's sorts and gathers are
  left out.
- Causal attention counts half the square: a query at position i attends to
  i + 1 keys, so the scores and the weighted values cost ``seq_len / 2``
  keys a token on average (the program's blocked kernel may compute more;
  that does not count).
- The held routed experts count at the uniform expected share: a token
  reaches ``num_experts_per_tok`` of the ``published`` ``n_routed_experts``,
  so the ``held`` experts here see ``k * held / E`` expert evaluations a
  token (0.75 for 6 of 64 with 8 held), whatever the router did in a step.
- Training is forward plus backward, three times the forward. Recomputed
  forward passes (rematerialisation) do not count.
"""
from __future__ import annotations


def _expert_share(config: dict) -> float:
    held = config["held_experts"][1]
    return (config["num_experts_per_tok"] * held
            / config["published"]["n_routed_experts"])


def _mlp(d: int, f: int) -> float:
    return 2.0 * 3 * d * f                     # gate, up, down


def held_expert_forward_flops_per_token(config: dict) -> float:
    """The held routed experts' forward FLOPs a token, all MoE layers, at
    the expected share."""
    n_moe = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return (n_moe * _expert_share(config)
            * _mlp(config["hidden_size"], config["moe_intermediate_size"]))


def forward_flops_per_token(config: dict) -> float:
    d, H = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    lora, S = config["kv_lora_rank"], config["seq_len"]
    layers = config["num_hidden_layers"]
    n_dense = config["first_k_dense_replace"]
    mla = 2.0 * (d * H * (nope + rope)          # q
                 + d * (lora + rope)            # latent and rope key
                 + lora * H * (nope + v)        # keys and values
                 + H * v * d)                   # output
    attention = 2.0 * H * ((nope + rope) + v) * (S / 2)
    dense = _mlp(d, config["intermediate_size"])
    moe = (2.0 * d * config["published"]["n_routed_experts"]   # router
           + _mlp(d, config["moe_intermediate_size"] * config["n_shared_experts"]))
    head = 2.0 * d * config["vocab_size"]
    return (layers * (mla + attention) + n_dense * dense
            + (layers - n_dense) * moe
            + held_expert_forward_flops_per_token(config) + head)


def train_flops_per_example(config: dict) -> float:
    """Training FLOPs of one sequence."""
    return 3.0 * forward_flops_per_token(config) * config["seq_len"]


def held_expert_train_flops_per_example(config: dict) -> float:
    """The held experts' share of :func:`train_flops_per_example`."""
    return 3.0 * held_expert_forward_flops_per_token(config) * config["seq_len"]
