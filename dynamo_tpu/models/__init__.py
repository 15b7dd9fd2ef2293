"""Model registry: named architecture presets + HF config mapping.

The engine's forward pass (engine/model.py) natively covers the llama
decoder family — RoPE + RMSNorm + GQA paged attention, SwiGLU MLP — plus
token-choice MoE (Mixtral-style, experts shardable over "tp" = EP),
sliding-window attention (Mistral), QKV bias (Qwen2), QK-norm (Qwen3 dense + MoE), and MLA — multi-head
latent attention with a compressed paged cache (DeepSeek V2/V3, incl.
sigmoid + group-limited routing, shared experts, and the dense layer
prefix), and layer KINDS with their own KV-head count, rope base, window
and sink, K/Q heads wider than V heads, and an expert layer that holds a
share of the experts it routes over (MiMo-V2), and two hybrids whose other
layers keep a fixed-size state a sequence in slots: Mamba-2 mixers (Granite
4.0-H) and gated short convolutions (LFM2). Presets below are the shapes used by the reference's recipes (ref:
recipes/llama-3-70b, recipes/deepseek-r1, recipes/gpt-oss-120b); unsupported
architectures fail loudly rather than being approximated silently.
"""

from __future__ import annotations

from dynamo_tpu.engine.config import ModelConfig


def mistral_7b() -> ModelConfig:
    return ModelConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=10000.0,
        max_position_embeddings=32768, sliding_window=4096)


def qwen2_7b() -> ModelConfig:
    return ModelConfig(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=28, num_heads=28, num_kv_heads=4, rope_theta=1000000.0,
        max_position_embeddings=32768, qkv_bias=True)


def qwen3_8b() -> ModelConfig:
    return ModelConfig(
        vocab_size=151936, hidden_size=4096, intermediate_size=12288,
        num_layers=36, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, max_position_embeddings=40960, qk_norm=True)


def qwen3_moe_30b_a3b() -> ModelConfig:
    """Qwen3-30B-A3B: 128 experts, 8 active — EP-friendly on a tpu mesh."""
    return ModelConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=6144,
        num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
        rope_theta=1000000.0, max_position_embeddings=40960, qk_norm=True,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
        norm_topk_prob=True)


def mixtral_8x7b() -> ModelConfig:
    return ModelConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=1000000.0,
        max_position_embeddings=32768, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True)  # Mixtral renormalizes the top-k gate probs


def moe_tiny() -> ModelConfig:
    """Small MoE for tests/benches of the EP path."""
    return ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, dtype="float32",
        num_experts=4, num_experts_per_tok=2, max_position_embeddings=512,
        norm_topk_prob=True)


def mla_tiny() -> ModelConfig:
    """Small MLA+MoE (DeepSeek-V3 shaped) for tests of the latent-cache path."""
    return ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=3,
        num_heads=4, num_kv_heads=4, dtype="float32",
        max_position_embeddings=512,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        n_shared_experts=1, first_k_dense_replace=1, scoring_func="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.5, n_group=2,
        topk_group=1, moe_capacity_factor=4.0)


def _gpt_oss(num_layers: int, num_experts: int) -> ModelConfig:
    return ModelConfig(
        vocab_size=201088, hidden_size=2880, intermediate_size=2880,
        num_layers=num_layers, num_heads=64, num_kv_heads=8, head_dim=64,
        rope_theta=150000.0, max_position_embeddings=131072,
        num_experts=num_experts, num_experts_per_tok=4, norm_topk_prob=True,
        qkv_bias=True, o_bias=True, attention_sinks=True,
        moe_activation="swiglu_oss", router_logit_bias=True,
        layer_windows=tuple(128 if i % 2 == 0 else 0
                            for i in range(num_layers)))


def gpt_oss_20b() -> ModelConfig:
    """gpt-oss-20b: alternating sliding/full attention with sink logits,
    32-expert clamped-GLU MoE (ref workload: recipes/gpt-oss-120b)."""
    return _gpt_oss(24, 32)


def gpt_oss_120b() -> ModelConfig:
    return _gpt_oss(36, 128)


def gptoss_tiny() -> ModelConfig:
    """Small gpt-oss-shaped config for tests of sinks/windows/oss-MoE."""
    return ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, dtype="float32",
        max_position_embeddings=512,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
        qkv_bias=True, o_bias=True, attention_sinks=True,
        moe_activation="swiglu_oss", router_logit_bias=True,
        moe_capacity_factor=4.0,
        layer_windows=(8, 0, 8, 0))


def deepseek_v2_lite() -> ModelConfig:
    """DeepSeek-V2-Lite (15.7B total / 2.4B active): MLA without q
    compression, softmax routing, 2 shared experts."""
    return ModelConfig(
        vocab_size=102400, hidden_size=2048, intermediate_size=10944,
        num_layers=27, num_heads=16, num_kv_heads=16, rope_theta=10000.0,
        max_position_embeddings=4096,
        kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        num_experts=64, num_experts_per_tok=6, moe_intermediate_size=1408,
        n_shared_experts=2, first_k_dense_replace=1,
        scoring_func="softmax", norm_topk_prob=False,
        routed_scaling_factor=1.0)


def deepseek_v3() -> ModelConfig:
    """DeepSeek-V3/R1 (671B total / 37B active): MLA with q compression,
    sigmoid + group-limited routing (ref flagship:
    recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml)."""
    return ModelConfig(
        vocab_size=129280, hidden_size=7168, intermediate_size=18432,
        num_layers=61, num_heads=128, num_kv_heads=128, rope_theta=10000.0,
        max_position_embeddings=4096,
        kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        n_shared_experts=1, first_k_dense_replace=3,
        scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, n_group=8, topk_group=4)


def _mimo_v2(*, vocab_size: int, pattern: tuple, experts_held,
             **sizes) -> ModelConfig:
    """MiMo-V2 (MiMo-V2-Flash / V2.5 language model): full (kind 0) and
    window-128 (kind 1) attention layers with their own KV-head counts and
    rope bases, a sink on the window kind only, 192-wide K/Q heads with
    RoPE on the leading 64 beside 128-wide V heads scaled by 0.707, a dense
    layer 0, then sigmoid-routed experts (top-8, bias-corrected choice,
    normalised weights, no shared expert)."""
    sizes = dict(
        hidden_size=4096, intermediate_size=16384, num_heads=64,
        head_dim=192, v_head_dim=128, rotary_dim=64,
        layer_kinds=((4, 1e7, 0, False), (8, 1e4, 128, True)),
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        max_position_embeddings=1048576) | sizes
    full = sizes["layer_kinds"][0]  # the published un-prefixed keys
    return ModelConfig(
        vocab_size=vocab_size, num_layers=len(pattern),
        num_kv_heads=full[0], rope_theta=full[1],
        layer_pattern=pattern, value_scale=0.707, rms_norm_eps=1e-5,
        first_k_dense_replace=1, scoring_func="sigmoid",
        norm_topk_prob=True, experts_held=experts_held, **sizes)


def mimo_v25_ep16() -> ModelConfig:
    """One chip's share of MiMo-V2.5 where 16 chips share each layer:
    experts expert-parallel (16 of 256 held here), attention data-parallel,
    the vocabulary in 8 slices (19,072 of 152,576 rows); layer 0 and one
    whole period (5 window : 1 full) of the 48 layers, the rest lying on
    further chips as pipeline stages (chipbench/configs/mimo-v25-ep16.json
    has the arithmetic)."""
    return _mimo_v2(vocab_size=19072, pattern=(0, 1, 1, 1, 1, 1, 0),
                    experts_held=(0, 16))


def mimo_tiny(experts_held=(0, 8)) -> ModelConfig:
    """MiMo-V2's shape at test size with every published RATIO kept: a
    dense layer then two periods of 5 window : 1 full, G = 8 and 16, K
    heads (24, RoPE on 8) wider than V heads (16), a window (8) smaller
    than a prompt chunk, 32 experts top-4 of which 8 are held."""
    return _mimo_v2(
        vocab_size=256, pattern=(0,) + (1, 1, 1, 1, 1, 0) * 2,
        experts_held=experts_held, hidden_size=64, intermediate_size=128,
        num_heads=16, head_dim=24, v_head_dim=16, rotary_dim=8,
        layer_kinds=((1, 1e7, 0, False), (2, 1e4, 8, True)),
        num_experts=32, num_experts_per_tok=4, moe_intermediate_size=32,
        max_position_embeddings=512, dtype="float32")


def _granite4_h(*, vocab_size: int, pattern: str, experts_held,
                **sizes) -> ModelConfig:
    """Granite 4.0-H (``granitemoehybrid``): Mamba-2 mixers ("m") and NoPE
    attention layers ("a") in the published order, every layer followed by
    softmax-routed experts (the top-k LOGITS' softmax) beside an ungated
    shared expert; embedding, residual, attention and logit multipliers are
    keys of the config; the head is the embedding, transposed."""
    sizes = dict(
        hidden_size=4096, num_heads=32, head_dim=128,
        layer_kinds=((8, 0.0, 0, False), (0, 0.0, 0, False, "mamba2")),
        query_pre_attn_scalar=128.0 ** 2,  # scores x attention_multiplier
        mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
        mamba_d_conv=4, intermediate_size=1536,
        num_experts=72, num_experts_per_tok=10, moe_intermediate_size=768,
        n_shared_experts=2,  # shared_intermediate_size 1536 = 2 x 768
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0, init_embed_std=0.25, init_out_gain=4.0,
        max_position_embeddings=131072) | sizes
    return ModelConfig(
        vocab_size=vocab_size, num_layers=len(pattern),
        num_kv_heads=sizes["layer_kinds"][0][0],
        layer_pattern=tuple("am".index(c) for c in pattern),
        position_embedding="nope", tie_word_embeddings=True,
        rms_norm_eps=1e-5, scoring_func="softmax", norm_topk_prob=True,
        experts_held=experts_held, **sizes)


def granite4_h_small_ep2() -> ModelConfig:
    """One chip's share of Granite-4.0-H-Small where 2 chips share each
    layer of a pipeline stage: the first period of ten layers (nine Mamba-2
    mixers, one attention layer), 36 of the 72 experts, half the vocabulary
    (chipbench/configs/granite4-h-small-ep2.json has the arithmetic)."""
    # tied to a 12 x embedding, the head scores the token a row just read
    # at 64 x the cosine between the final stream and that embedding (in
    # units of the other logits' sd): with sublayers at fan-in scale every
    # greedy pick repeats its input (gap 41, first chip run). At 64 x
    # fan-in the sublayers' sum is ~17 x the embedding and the picks vary
    return _granite4_h(vocab_size=50176, pattern="mmmmmammmm",
                       experts_held=(0, 36), init_out_gain=64.0)


def granite4_tiny(experts_held=(0, 4), pattern="mmammm") -> ModelConfig:
    """Granite 4.0-H's shape at test size: Mamba-2 runs either side of an
    attention layer, G = 2, 8 experts top-3 of which 4 are held, a shared
    expert twice an expert's width, float32."""
    return _granite4_h(
        vocab_size=256, pattern=pattern, experts_held=experts_held,
        hidden_size=64, num_heads=4, head_dim=16,
        layer_kinds=((2, 0.0, 0, False), (0, 0.0, 0, False, "mamba2")),
        query_pre_attn_scalar=32.0 ** 2,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        intermediate_size=64, num_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=32, max_position_embeddings=512,
        logits_scaling=2.0,  # logits of sd about 1 at this hidden size
        dtype="float32")


def _lfm2_moe(*, pattern: str, experts_held=None, **sizes) -> ModelConfig:
    """LFM2-MoE (``lfm2_moe``: LFM2-8B-A1B, LFM2-24B-A2B): gated short-
    convolution mixers ("c": ``C * conv3(B * x)``, whose state is the last
    two inputs a sequence) and GQA attention layers ("a": per-head RMSNorm
    on q and k, rotate-half RoPE) in the published order; ``num_dense``
    SwiGLU layers first, then sigmoid-routed experts whose choice adds a
    bias the weights do not carry, the weights normalised over the chosen
    (+ 1e-6); the head is the embedding. Every expert is held unless
    ``experts_held`` says otherwise; 64-wide heads are stored as whole lane
    rows (``kv_lane_pad``: the ragged kernel takes them)."""
    sizes = dict(
        hidden_size=2048, intermediate_size=11776, num_heads=32,
        layer_kinds=((8, 1e6, 0, False), (0, 0.0, 0, False, "shortconv")),
        num_experts=64, num_experts_per_tok=4, moe_intermediate_size=1536,
        first_k_dense_replace=2, vocab_size=65536,
        max_position_embeddings=128000) | sizes
    E = sizes["num_experts"]
    return ModelConfig(
        num_layers=len(pattern), num_kv_heads=sizes["layer_kinds"][0][0],
        rope_theta=sizes["layer_kinds"][0][1],
        layer_pattern=tuple("ac".index(c) for c in pattern),
        qk_norm=True, rms_norm_eps=1e-5, tie_word_embeddings=True,
        scoring_func="sigmoid", norm_topk_prob=True, router_norm_eps=1e-6,
        routed_scaling_factor=1.0, shortconv_taps=3, kv_lane_pad=True,
        experts_held=experts_held or (0, E),
        # nothing at its neutral value: norm weights ~ N(1, 0.2)
        init_norm_std=0.2, **sizes)


def lfm2_24b_a2b_pp4() -> ModelConfig:
    """One chip's share of LFM2-24B-A2B served as four pipeline stages of
    ten layers, no layer shared between chips: stage 0 — the two dense
    layers and two whole periods (attention, conv, conv, conv), ALL 64
    experts of each, the whole vocabulary — and the tied head the last
    stage would hold (chipbench/configs/lfm2-24b-a2b-pp4.json has the
    arithmetic)."""
    return _lfm2_moe(pattern="ccacccaccc")


def lfm2_tiny(experts_held=None, pattern="ccacccac") -> ModelConfig:
    """LFM2-MoE's shape at test size: two dense conv layers, then attention
    (G = 2, heads narrower than a lane row and padded to one) and conv
    layers with 8 experts top-3, float32."""
    return _lfm2_moe(
        pattern=pattern, experts_held=experts_held, hidden_size=64,
        intermediate_size=128, num_heads=4,
        layer_kinds=((2, 1e6, 0, False), (0, 0.0, 0, False, "shortconv")),
        num_experts=8, num_experts_per_tok=3, moe_intermediate_size=32,
        vocab_size=256, max_position_embeddings=512, dtype="float32")


PRESETS = {
    "tiny": ModelConfig.tiny,
    "moe_tiny": moe_tiny,
    "llama3_1b": ModelConfig.llama3_1b,
    "llama3_8b": ModelConfig.llama3_8b,
    "llama3_70b": ModelConfig.llama3_70b,
    "mistral_7b": mistral_7b,
    "qwen2_7b": qwen2_7b,
    "qwen3_8b": qwen3_8b,
    "qwen3_moe_30b_a3b": qwen3_moe_30b_a3b,
    "mixtral_8x7b": mixtral_8x7b,
    "mla_tiny": mla_tiny,
    "deepseek_v2_lite": deepseek_v2_lite,
    "deepseek_v3": deepseek_v3,
    "gptoss_tiny": gptoss_tiny,
    "gpt_oss_20b": gpt_oss_20b,
    "gpt_oss_120b": gpt_oss_120b,
    "mimo_tiny": mimo_tiny,
    "mimo_v25_ep16": mimo_v25_ep16,
    "granite4_tiny": granite4_tiny,
    "granite4_h_small_ep2": granite4_h_small_ep2,
    "lfm2_tiny": lfm2_tiny,
    "lfm2_24b_a2b_pp4": lfm2_24b_a2b_pp4,
}

#: architectures the forward pass does NOT cover yet (listed so callers
#: fail loudly instead of serving wrong numerics). DeepSeek V2/V3 (MLA)
#: graduated from this map in round 2 — engine/model.py:_mla_attention.
UNSUPPORTED = {
    "MambaForCausalLM": "no preset maps its config (Mamba-2 mixers run in "
                        "granite4_h_small_ep2; Mamba-1's scan does not)",
    "JambaForCausalLM": "no preset maps its config (Mamba-1 mixers)",
}


def get_model_config(name: str) -> ModelConfig:
    if name in PRESETS:
        return PRESETS[name]()
    raise KeyError(f"unknown model preset '{name}' (have {sorted(PRESETS)})")


def from_hf_config(d: dict) -> ModelConfig:
    arch = (d.get("architectures") or [""])[0]
    if arch in UNSUPPORTED:
        raise NotImplementedError(f"{arch}: {UNSUPPORTED[arch]}")
    return ModelConfig.from_hf_config(d)
