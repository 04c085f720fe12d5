"""DeepSeek-V2-Lite 16B [arXiv:2405.04434; config.json at
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite]: 27L, d_model 2048, 16
heads, MLA (kv_lora 512, no q-lora on Lite, rope 64, nope 128, v 128), vocab
102400. MoE: 2 shared + 64 routed experts, top-6 of a softmax router with
the gates not renormalised (``norm_topk_prob: false``), expert d_ff 1408;
the first layer is dense with d_ff 10944 (``intermediate_size``); the
sequence-level balance loss at ``aux_loss_alpha`` 0.001 (``seq_aux``). YaRN rope:
factor 40 over 4096 original positions, beta_fast 32, beta_slow 1, mscale =
mscale_all_dim = 0.707. (The assignment note "160 routed" matches V2-236B,
not Lite; we follow the header's 64e as the Lite model card specifies.)"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b",
        arch_type="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=128,
        d_ff=1408,
        vocab_size=102_400,
        act="silu",
        rope_theta=10_000.0,
        rope_scaling=YaRNConfig(
            factor=40.0,
            original_max_position_embeddings=4096,
            beta_fast=32.0,
            beta_slow=1.0,
            mscale=0.707,
            mscale_all_dim=0.707,
        ),
        moe=MoEConfig(
            n_experts=64,
            n_shared_experts=2,
            topk=6,
            d_ff=1408,
            first_dense=1,
            capacity_factor=1.25,
            router_aux_weight=0.001,
            seq_aux=True,
            router_scoring="softmax",
            group_size=4096,
            norm_topk_prob=False,
            dense_d_ff=10_944,
        ),
        mla=MLAConfig(
            q_lora_rank=0,
            kv_lora_rank=512,
            qk_rope_dim=64,
            qk_nope_dim=128,
            v_head_dim=128,
        ),
        param_dtype="bfloat16",
        compute_dtype="bfloat16",
        remat=True,
        ce_chunk=512,
    )
