"""deepseek-v2-lite [moe, MLA] — hf:deepseek-ai/DeepSeek-V2-Lite.

27L, d_model=2048, 16H of multi-head latent attention with no query
low-rank step (q_lora_rank null: wq D -> 16 x 192), kv_lora=512,
qk_nope=128 + qk_rope=64 (interleaved pairs), v_head=128; RoPE theta 1e4
under YaRN (factor 40 over 4096 positions, beta 32/1, mscale =
mscale_all_dim = 0.707). Layer 0 is dense (SwiGLU 10944,
first_k_dense_replace=1); layers 1-26 route each token to 6 of 64 experts
(width 1408, softmax scores, greedy top-k, weights not renormalized,
routed_scaling_factor 1) plus 2 shared experts (one SwiGLU of 2816) with
no gate. vocab=102400, untied, rms_norm_eps 1e-6, bf16.

FULL holds all 64 routed experts (15.7B parameters). A chip of an
expert-parallel deployment holds a share: ``MoEConfig(first_held, n_held)``
(bench/configs/deepseek-v2-lite-ep8.json: 8 of 64 over 8 chips).
"""
from .base import MLAConfig, ModelConfig, MoEConfig, YarnConfig, register_arch

FULL = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,  # qk_nope + qk_rope
    d_ff=10944,    # the leading dense layer's MLP
    vocab=102400,
    rope_theta=10000.0,
    rope_scaling=YarnConfig(
        factor=40.0, original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
        mscale=0.707, mscale_all_dim=0.707,
    ),
    norm_eps=1e-6,
    first_dense_layers=1,
    mla=MLAConfig(
        q_lora_rank=None, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
        v_head_dim=128, rope_interleaved=True,
    ),
    moe=MoEConfig(
        n_experts=64, top_k=6, d_ff_expert=1408, n_shared_experts=2, d_ff_shared=2816,
        norm_topk_prob=False, shared_gate=False, dropless=True,
    ),
)

REDUCED = ModelConfig(
    name="deepseek-v2-lite-reduced",
    family="moe",
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    head_dim=24,
    d_ff=128,
    vocab=256,
    rope_scaling=FULL.rope_scaling,
    norm_eps=1e-6,
    first_dense_layers=1,
    mla=MLAConfig(
        q_lora_rank=None, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, rope_interleaved=True,
    ),
    moe=MoEConfig(
        n_experts=8, top_k=3, d_ff_expert=32, n_shared_experts=2, d_ff_shared=64,
        norm_topk_prob=False, shared_gate=False, dropless=True, first_held=0, n_held=4,
    ),
)

register_arch(FULL, REDUCED)
