"""DeepSeek-V2-Lite [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H MLA (no q compression, kv_lora=512, qk 128+64, v 128,
YaRN rope factor 40) vocab=102400; first layer dense (d_ff=10944), then MoE:
64 routed experts top-6 (softmax, greedy, weights not renormalised,
routed_scaling_factor 1) + 2 shared, expert d_ff=1408, per-sequence balance
loss (aux_loss_alpha 0.001).  Total params ~15.7B, active ~2.4B.

Trained with expert parallelism in ``make_train_step_shardmap``: the experts
are sharded over the data-parallel axes and each MoE layer's tokens are
exchanged with ``fulllane_all_to_all``; everything else is data parallel
with ZeRO-1 moments, so parameters stay replicated (``fsdp=False``).
"""

from repro.configs.base import (
    AttnConfig, LayerSpec, ModelConfig, MoEConfig, ParallelConfig,
    YarnScaling,
)

_YARN = YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    d_ff=10944,
    vocab_size=102400,
    attn=AttnConfig(
        kind="mla",
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        rope_theta=10_000.0,
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        yarn=_YARN,
    ),
    moe=MoEConfig(
        num_experts=64, top_k=6, d_ff_expert=1408, num_shared_experts=2,
        router_aux_weight=0.001, norm_topk_prob=False,
        routed_scaling_factor=1.0, seq_aux=True,
    ),
    layer_pattern=(LayerSpec("attn", "moe"),),
    first_k_dense=1,
    norm_eps=1e-6,
    parallel=ParallelConfig(fsdp=False),
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    d_ff=128,
    vocab_size=256,
    attn=AttnConfig(
        kind="mla",
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        q_lora_rank=None,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        yarn=_YARN,
    ),
    moe=MoEConfig(
        num_experts=16, top_k=6, d_ff_expert=32, num_shared_experts=2,
        router_aux_weight=0.001, norm_topk_prob=False,
        routed_scaling_factor=1.0, seq_aux=True,
    ),
    layer_pattern=(LayerSpec("attn", "moe"),),
    first_k_dense=1,
    parallel=ParallelConfig(fsdp=False, attn_chunk_q=64, attn_chunk_kv=64),
)
