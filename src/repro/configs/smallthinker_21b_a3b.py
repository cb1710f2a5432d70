"""SmallThinker-21B-A3B — on-device MoE: 64 experts top-6 (ReGLU, router
before attention) in every layer; three layers of four attend a 4096-token
window with RoPE, the fourth every earlier token without positional
encoding (NoPE).  [PowerInfer SmallThinker technical report, 2025;
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct]
"""

from repro.models.transformer import ModelConfig

SOURCE = "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct"


def _layout(n_layers: int, first: int = 0) -> tuple[tuple, tuple]:
    """The published ``sliding_window_layout`` [0,1,1,1]·13, from layer
    ``first``: the windowed layers (RoPE) and the full ones (NoPE)."""
    idx = range(n_layers)
    window = tuple(i for i in idx if (first + i) % 4 != 0)
    return window, tuple(i for i in idx if i not in window)


def config() -> ModelConfig:
    n_layers = 52
    window, full = _layout(n_layers)
    return ModelConfig(
        name="smallthinker-21b-a3b", arch_type="moe",
        n_layers=n_layers, d_model=2560, n_heads=28, n_kv_heads=4, head_dim=128,
        d_ff=768, vocab_size=151936, rope_theta=1.5e6,
        block_pattern=("moe_attn",) * n_layers,
        n_experts=64, moe_top_k=6, moe_d_ff=768, moe_capacity_factor=None,
        moe_act="relu", router_before_attn=True,
        sliding_window=4096, window_layers=window, nope_layers=full,
        tie_embeddings=False, source=SOURCE,
    )


def smoke_config() -> ModelConfig:
    """One windowed and one full layer, 4 experts top-2."""
    n_layers = 2
    window, full = _layout(n_layers, first=3)
    return ModelConfig(
        name="smallthinker-21b-a3b-smoke", arch_type="moe",
        n_layers=n_layers, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=64, vocab_size=512, rope_theta=1.5e6,
        block_pattern=("moe_attn",) * n_layers,
        n_experts=4, moe_top_k=2, moe_d_ff=64, moe_capacity_factor=None,
        moe_act="relu", router_before_attn=True,
        sliding_window=16, window_layers=window, nope_layers=full,
        tie_embeddings=False, source=SOURCE,
    )
