"""DeepSeek-V3: latent attention on all 61 layers, 3 leading dense
layers, then 58 MoE layers of 256 routed experts (top-8) + 1 shared.

[hf:deepseek-ai/DeepSeek-V3 config.json; arXiv:2412.19437]. Every
width is the published one. Two things are left out or simplified:

- The multi-token-prediction module (``num_nextn_predict_layers`` 1)
  is not part of the model: the main model runs without it at
  inference (arXiv:2412.19437 §2.2), and it is not lowered.
- Routing is sigmoid-scored and group-limited (8 groups, the best 4
  kept, top-8 within them). The lowering (``core.network``) prices it
  at its uniform expectation, ceil(t * top_k / n_experts) tokens per
  expert; the plain model (``models.moe``) routes for real.

``head_dim`` is the query/key head width, qk_nope + qk_rope = 192.
"""

from ..config import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v3",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,
    vocab=129280,
    head_dim=192,
    n_experts=256,
    n_shared_experts=1,
    top_k=8,
    expert_d_ff=2048,
    n_dense_layers=3,
    router_score="sigmoid",
    n_expert_groups=8,
    topk_groups=4,
    routed_scale=2.5,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    source="hf:deepseek-ai/DeepSeek-V3",
)
