"""trinity-mini [moe, afmoe] — 3 windowed layers then 1 full, 128 experts
top-8 beside a shared expert, sigmoid routing, gated attention.
[hf:arcee-ai/Trinity-Mini; hf]"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="trinity-mini",
    family="moe",
    source="hf:arcee-ai/Trinity-Mini; hf",
    n_layers=32,
    d_model=2048,
    n_heads=32,
    kv_heads=4,
    head_dim=128,
    d_ff=6144,          # the two leading dense layers' SwiGLU
    vocab=200192,
    norm_eps=1e-5,
    rope_theta=10_000.0,
    sliding_window=2048,
    full_attn_every=4,
    rope_full=False,
    qk_norm=True,
    attn_gate=True,
    sandwich_norm=True,
    embed_scale=2048 ** 0.5,
    moe=True,
    n_experts=128,
    top_k=8,
    d_ff_expert=1024,
    n_dense_layers=2,
    n_shared_experts=1,
    router_score="sigmoid",
    route_scale=2.826,
)
