"""deepseek-v3.2-exp [moe]: 61L d_model=7168 128H (MLA, q_lora=1536,
kv_lora=512, qk 128+64, v 128) with DeepSeek Sparse Attention: a
lightning indexer of 64 heads x 128 picks the top-2048 cached tokens
each query attends to.  MoE 256 routed experts top-8 (width 2048) + 1
shared, the first 3 layers dense (d_ff 18432), vocab 129280 untied,
163840 positions.  671.03 B parameters without the indexer, 671.88 B
with it (the MTP module's 14 B are not modelled).
[https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json;
DeepSeek-V3.2-Exp, "DSA: DeepSeek Sparse Attention"]"""
from repro.models import DSAConfig, MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v3.2-exp", family="moe",
    num_layers=61, d_model=7168, num_heads=128, num_kv_heads=128,
    head_dim=128, d_ff=18432, vocab_size=129280,
    rope_theta=10_000.0,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=1536),
    dsa=DSAConfig(index_n_heads=64, index_head_dim=128, index_topk=2048),
    moe=MoEConfig(num_experts=256, top_k=8, expert_d_ff=2048,
                  num_shared_experts=1, shared_d_ff=2048, first_dense=3),
)

REDUCED = ModelConfig(
    name="deepseek-v3.2-exp-reduced", family="moe",
    num_layers=3, d_model=128, num_heads=4, num_kv_heads=4,
    head_dim=32, d_ff=256, vocab_size=512,
    mla=MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                  qk_rope_head_dim=16, v_head_dim=32, q_lora_rank=48),
    dsa=DSAConfig(index_n_heads=4, index_head_dim=32, index_topk=8),
    moe=MoEConfig(num_experts=8, top_k=2, expert_d_ff=64,
                  num_shared_experts=1, shared_d_ff=64, first_dense=1),
    dtype="float32",
)
