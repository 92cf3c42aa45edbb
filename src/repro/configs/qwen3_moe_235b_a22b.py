"""qwen3-moe-235b-a22b [moe] — 128 experts top-8 (renormalised),
fine-grained experts 1,536 wide, every layer an expert layer, qk_norm,
GQA 64/4 (Qwen3 family). [hf:Qwen/Qwen3-235B-A22B config.json]

``d_ff`` is the published ``intermediate_size``, which no layer uses
(``mlp_only_layers`` is empty, ``decoder_sparse_step`` 1)."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-235b-a22b", family="moe",
    num_layers=94, d_model=4096, num_heads=64, num_kv_heads=4, head_dim=128,
    d_ff=12288, vocab_size=151936,
    num_experts=128, experts_per_tok=8, expert_d_ff=1536,
    qk_norm=True, rope_theta=1000000.0,
)
