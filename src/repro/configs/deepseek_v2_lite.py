"""deepseek-v2-lite [moe] — latent attention with a plain query projection
and YaRN, fine-grained experts: 2 shared + 64 routed top-6, unnormalised.
[hf:deepseek-ai/DeepSeek-V2-Lite]

27L d_model=2048 16H vocab=102400, untied head. MLA without a query
low-rank path (q_lora_rank null): q/k heads of 128 + 64 rope = 192, v 128,
kv_lora_rank 512; YaRN over the 64 rope dims (factor 40 over 4096
original positions, mscale = mscale_all_dim = 0.707; beta_fast 32 and
beta_slow 1 are ``layers.YARN_BETA_FAST`` and ``YARN_BETA_SLOW``), so the
softmax scale is 192**-0.5 * mscale(40, 0.707)**2. Layer 0 has a dense SwiGLU of 10944; layers 1..26 route each token over 64
experts of 1408 by softmax, keep the greedy top 6 with their weights as
they are (norm_topk_prob false, routed_scaling_factor 1), and add 2
shared experts. The balance term is the batch-wise form at
aux_loss_alpha 0.001 (the published file sets seq_aux, the sequence-wise
form, which is the same on one sequence a batch).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,                       # the single dense layer's hidden dim
    vocab_size=102400,
    head_dim=192,
    rope_theta=10_000.0,
    mla=True,
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_mscale_all_dim=0.707,
    moe=True,
    num_experts=64,
    router_experts=64,
    num_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    norm_topk_prob=False,
    router_aux_coef=0.001,
    first_dense=1,
    tie_embeddings=False,
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)


def smoke_config():
    """Every mechanism at a CPU size: the layer holds 4 of the router's 8
    experts."""
    return CONFIG.replace(
        num_layers=3, d_model=128, num_heads=4, num_kv_heads=4, head_dim=48,
        d_ff=256, vocab_size=512, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, yarn_original_max_position=32,
        num_experts=4, router_experts=8, num_shared_experts=1, top_k=2,
        moe_d_ff=64, param_dtype="float32", compute_dtype="float32",
        loss_chunk=64, attn_block_kv=64)
