"""DeepSeekMoE-16B [arXiv:2401.06066]: fine-grained MoE, 2 shared + 64 routed
experts top-6, per-expert hidden 1408 (d_ff field); softmax router whose
top-6 weights are not renormalized (``norm_topk_prob: false``)."""
from repro.configs.base import ArchConfig, MoEConfig, register

CONFIG = register(
    ArchConfig(
        name="deepseek-moe-16b",
        family="moe",
        n_layers=28,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1408,
        vocab=102_400,
        head_dim=128,
        moe=MoEConfig(
            num_experts=64,
            top_k=6,
            num_shared_experts=2,
            d_expert=1408,
            norm_topk_prob=False,
            parallelism="ep",
        ),
    )
)
