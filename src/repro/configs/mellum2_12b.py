"""JetBrains Mellum2-12B-A2.5B — 28 sparse layers, 64 routed experts (top-8,
renormalised), GQA 32Q/4KV at head_dim 128, and a period of three
sliding-window layers (1,024 keys, plain RoPE) and one full layer (YaRN,
factor 16 over 8,192 positions). [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]

Not given by the config and taken here: no qk-norm, softmax router
scoring, no load-balance term (router_aux_coef 0, fine-tuning), and the
window "a key is visible iff 0 <= q - k < 1,024". The model card's MTP
head is left out: the config has no key for it. `intermediate_size`
(7,168) is unused, since no layer is dense.
"""
from repro.config import ModelConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=7168,
    vocab_size=98304,
    rope_theta=500_000.0,
    norm_eps=1e-6,
    moe=True,
    num_experts=64,
    experts_per_token=8,
    moe_d_ff=896,
    experts_held=64,  # one chip holds the whole layer; a share is an override
    router_aux_coef=0.0,
    sliding_window=1024,
    layer_types=("window", "window", "window", "full"),
    yarn_factor=16.0,
    yarn_original_max_position=8192,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)
