"""Checkpoint loading: HF safetensors → the engine's stacked params pytree.

The reference resolves model artifacts from the HF hub into its engines
(ref: lib/llm/src/local_model.rs:1-456, hub.rs); here the weights land
directly in the JAX param layout of model.py (layers stacked on a leading L
axis for lax.scan; matrices stored [in, out] so the forward pass is x @ W
with no transposes at trace time — but the attention projections wq/wk/wv,
which keep HF's [out, in] cut into heads, [heads, width, in]: the layout
their dot reads, model.py's pytree comment).

Supported families: llama/mistral/qwen2 (dense), mixtral (MoE,
block_sparse_moe names), deepseek V2/V3 (MLA + MoE with shared experts and
a dense prefix; rope-interleaved checkpoints are de-interleaved here once so
the runtime rope is plain half-split).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Optional

import numpy as np

from dynamo_tpu.engine.config import ModelConfig

logger = logging.getLogger("dynamo.engine.loader")


def _load_tensors(path: str) -> dict:
    """Load all *.safetensors under path into {name: np/jnp array}."""
    files = sorted(
        os.path.join(path, f) for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {path}")
    out = {}
    try:
        from safetensors import safe_open

        import jax.numpy as jnp
        import ml_dtypes  # numpy bf16 support ships with jax

        for f in files:
            with safe_open(f, framework="numpy") as sf:
                for name in sf.keys():
                    out[name] = sf.get_tensor(name)
    except (ImportError, TypeError, ValueError):
        # bf16 via torch fallback (torch-cpu is baked into the image)
        import torch

        from safetensors.torch import load_file

        for f in files:
            for name, t in load_file(f).items():
                out[name] = t.to(torch.float32).numpy()
    return out


def _deinterleave_rope_rows(w: np.ndarray, starts, dr: int) -> np.ndarray:
    """Permute rope-dim out-rows from interleaved to half-split layout.

    HF/DeepSeek checkpoints store rotary dims interleaved (re/im pairs); the
    runtime rope is half-split, so converting once at load (out[j]=in[2j],
    out[dr/2+j]=in[2j+1] within each rope row range) keeps the hot path free
    of per-step permutes. ``w`` is HF [out, in]; ``starts`` are the first
    rope row of each head's range.
    """
    perm = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    w = np.asarray(w).copy()
    for s in starts:
        w[s:s + dr] = w[s:s + dr][perm]
    return w


#: fp4 e2m1 value table, sign in the high bit (HF mxfp4 FP4_VALUES)
_FP4_LUT = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0,
                     -0.0, -0.5, -1.0, -1.5, -2.0, -3.0, -4.0, -6.0],
                    np.float32)


def _mxfp4_dequant(blocks: np.ndarray, scales: np.ndarray,
                   out_dtype=np.float32) -> np.ndarray:
    """[..., G, 16]u8 blocks + [..., G]u8 e8m0 scales → [..., last-two-
    swapped] in ``out_dtype``, matching transformers'
    convert_moe_packed_tensors (nibble lo/hi interleave, ldexp by
    scale-127, final transpose(1, 2)).

    Dequantizes one leading-axis (expert) slice at a time so the float32
    transient is bounded per expert, not the whole layer — and fp4 values
    times power-of-2 scales are EXACT in bf16, so emitting the target
    dtype directly loses nothing.
    """
    *prefix, G, B = blocks.shape
    out = np.empty((*prefix, G * B * 2), np.dtype(out_dtype))
    n_lead = prefix[0] if prefix else 1
    blk_l = blocks.reshape(n_lead, -1, B)
    sc_l = scales.reshape(n_lead, -1)
    out_l = out.reshape(n_lead, -1, G * B * 2)
    for ei in range(n_lead):
        blk = blk_l[ei]
        exp = sc_l[ei].astype(np.int32).reshape(-1, 1) - 127
        tmp = np.empty((blk.shape[0], B * 2), np.float32)
        tmp[:, 0::2] = _FP4_LUT[blk & 0x0F]
        tmp[:, 1::2] = _FP4_LUT[blk >> 4]
        np.ldexp(tmp, exp, out=tmp)
        out_l[ei] = tmp.reshape(-1, G * B * 2)
    return out.swapaxes(-2, -1)


#: fp4 e2m1 values ×2 are exact small integers — the basis of the lossless
#: MXFP4 → grouped-int8 re-encode below
_FP4_LUT2 = (_FP4_LUT * 2).astype(np.int8)


def _mxfp4_to_qtensor(blocks: np.ndarray, scales: np.ndarray) -> dict:
    """LOSSLESS MXFP4 → grouped-int8 QTensor (engine/quant.py layout).

    fp4 e2m1 magnitudes are {0,.5,1,1.5,2,3,4,6}: doubled they are exact
    int8 values, and the e8m0 block scale halves to stay a power of two —
    so ``q·s`` reproduces every MXFP4 weight bit-exactly in bf16, at
    1 B/weight HBM residency instead of 2 (the reference serves gpt-oss
    MXFP4 natively: recipes/gpt-oss-120b/trtllm/agg/deploy.yaml). Returns
    {"q": [..., I, O] int8, "s": [..., G, O] f32} matching
    ``_mxfp4_dequant(...)`` = dequantize(result) exactly."""
    *prefix, G, B = blocks.shape
    n_lead = prefix[0] if prefix else 1
    blk = blocks.reshape(n_lead, -1, B)
    q = np.empty((n_lead, blk.shape[1], B * 2), np.int8)
    q[..., 0::2] = _FP4_LUT2[blk & 0x0F]
    q[..., 1::2] = _FP4_LUT2[blk >> 4]
    q = q.reshape(*prefix, G * B * 2).swapaxes(-2, -1)  # [..., I, O]
    s = np.ldexp(0.5, scales.astype(np.int32) - 127).astype(np.float32)
    return {"q": q, "s": s.swapaxes(-2, -1)}  # s: [..., G, O]


def load_hf_params(cfg: ModelConfig, path: str, dtype=None) -> dict:
    """Map HF llama/mistral/qwen2/mixtral/deepseek weight names onto the
    model.py pytree."""
    import jax.numpy as jnp

    dtype = dtype or jnp.dtype(cfg.dtype)
    t = _load_tensors(path)
    raw_cfg = {}
    cfg_file = os.path.join(path, "config.json")
    if os.path.exists(cfg_file):
        with open(cfg_file) as f:
            raw_cfg = json.load(f)

    def get(name):
        return jnp.asarray(np.asarray(t[name]), dtype=dtype)

    def proj(name):  # HF stores [out, in] → we want [in, out]
        return get(name).T

    def by_heads(w, heads):  # HF's [heads·width, in] → [heads, width, in]
        return w.reshape(heads, -1, w.shape[-1])

    L = cfg.num_layers
    from dynamo_tpu.engine.quant import stack_layers as stack

    def attn_layer(i: int) -> dict:
        pre = f"model.layers.{i}.self_attn"
        if not cfg.is_mla:
            if f"{pre}.qkv_proj.weight" in t:
                # Phi-3/Phi-4 fuse q|k|v rows into one projection; split at
                # the head boundaries (rows are [H·hd | KV·hd | KV·hd])
                qkv = get(f"{pre}.qkv_proj.weight")  # [(H+2KV)·hd, D]
                nq = cfg.num_heads * cfg.head_dim
                nkv = cfg.num_kv_heads * cfg.head_dim
                qkv = (qkv[:nq], qkv[nq:nq + nkv], qkv[nq + nkv:nq + 2 * nkv])
            else:
                qkv = [get(f"{pre}.{n}_proj.weight") for n in "qkv"]
            out = {
                "wq": by_heads(qkv[0], cfg.num_heads),
                "wk": by_heads(qkv[1], cfg.num_kv_heads),
                "wv": by_heads(qkv[2], cfg.num_kv_heads),
                "wo": proj(f"{pre}.o_proj.weight"),
            }
            if cfg.qkv_bias:
                out["bq"] = get(f"{pre}.q_proj.bias")
                out["bk"] = get(f"{pre}.k_proj.bias")
                out["bv"] = get(f"{pre}.v_proj.bias")
            if cfg.qk_norm:
                out["q_norm"] = get(f"{pre}.q_norm.weight")
                out["k_norm"] = get(f"{pre}.k_norm.weight")
            if cfg.o_bias:
                out["bo"] = get(f"{pre}.o_proj.bias")
            if cfg.attention_sinks:
                out["sink"] = get(f"{pre}.sinks")
            return out
        # --- MLA (DeepSeek) ---
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        H = cfg.num_heads
        interleaved = raw_cfg.get("rope_interleave", True)

        kv_a = np.asarray(t[f"{pre}.kv_a_proj_with_mqa.weight"])  # [r+dr, D]
        if interleaved:
            kv_a = _deinterleave_rope_rows(kv_a, [r], dr)
        q_name = (f"{pre}.q_b_proj.weight" if cfg.q_lora_rank
                  else f"{pre}.q_proj.weight")
        q_w = np.asarray(t[q_name])  # [H*(dn+dr), in]
        if interleaved:
            q_w = _deinterleave_rope_rows(
                q_w, [h * (dn + dr) + dn for h in range(H)], dr)
        kv_b = np.asarray(t[f"{pre}.kv_b_proj.weight"])  # [H*(dn+dv), r]
        kv_b = kv_b.reshape(H, dn + dv, r)
        w_uk = kv_b[:, :dn].transpose(2, 0, 1).reshape(r, H * dn)
        w_uv = kv_b[:, dn:].transpose(2, 0, 1).reshape(r, H * dv)

        out = {
            "kv_a": jnp.asarray(kv_a, dtype=dtype).T,
            "kv_a_norm": get(f"{pre}.kv_a_layernorm.weight"),
            "w_uk": jnp.asarray(w_uk, dtype=dtype),
            "w_uv": jnp.asarray(w_uv, dtype=dtype),
            "wo": proj(f"{pre}.o_proj.weight"),
        }
        if cfg.q_lora_rank:
            out["q_a"] = proj(f"{pre}.q_a_proj.weight")
            out["q_a_norm"] = get(f"{pre}.q_a_layernorm.weight")
            out["q_b"] = jnp.asarray(q_w, dtype=dtype).T
        else:
            out["wq"] = by_heads(jnp.asarray(q_w, dtype=dtype), H)
        return out

    def dense_mlp_layer(i: int) -> dict:
        pre = f"model.layers.{i}.mlp"
        if f"{pre}.gate_up_proj.weight" in t:
            # Phi-3/Phi-4 fuse gate|up (HF chunks: first half gate)
            gu = proj(f"{pre}.gate_up_proj.weight")  # [D, 2F]
            F2 = gu.shape[-1] // 2
            return {
                "w_gate": gu[:, :F2],
                "w_up": gu[:, F2:],
                "w_down": proj(f"{pre}.down_proj.weight"),
            }
        return {
            "w_gate": proj(f"{pre}.gate_proj.weight"),
            "w_up": proj(f"{pre}.up_proj.weight"),
            "w_down": proj(f"{pre}.down_proj.weight"),
        }

    def oss_experts(pre: str, gu, w_down) -> dict:
        """gpt-oss expert dict from fused gate_up [E, D, 2F] (bf16 or
        dequantized MXFP4) + down [E, F, D] — ONE builder so the quantized
        and unquantized load paths cannot diverge."""
        gub = np.asarray(t[f"{pre}.experts.gate_up_proj_bias"])  # [E, 2F]
        if isinstance(gu, dict):  # MXFP4 kept quantized: slice q AND s on
            # the interleaved output dim (scales are per (group, out-col))
            w_gate = {"q": jnp.asarray(gu["q"][..., ::2]),
                      "s": jnp.asarray(gu["s"][..., ::2])}
            w_up = {"q": jnp.asarray(gu["q"][..., 1::2]),
                    "s": jnp.asarray(gu["s"][..., 1::2])}
        else:
            w_gate = jnp.asarray(gu[..., ::2], dtype=dtype)
            w_up = jnp.asarray(gu[..., 1::2], dtype=dtype)
        return {
            "router": proj(f"{pre}.router.weight"),
            "router_bias": jnp.asarray(
                np.asarray(t[f"{pre}.router.bias"]), jnp.float32),
            "w_gate": w_gate,
            "w_up": w_up,
            "b_gate": jnp.asarray(gub[..., ::2], dtype=dtype),
            "b_up": jnp.asarray(gub[..., 1::2], dtype=dtype),
            "w_down": w_down,  # [E, F, D]
            "b_down": get(f"{pre}.experts.down_proj_bias"),  # [E, D]
        }

    def moe_mlp_layer(i: int) -> dict:
        import jax.numpy as jnp

        E = cfg.num_experts
        if f"model.layers.{i}.block_sparse_moe.gate.weight" in t:  # mixtral
            pre = f"model.layers.{i}.block_sparse_moe"
            names = ("w1", "w2", "w3")  # gate, down, up
            expert = lambda e, n: proj(f"{pre}.experts.{e}.{n}.weight")  # noqa: E731
            out = {
                "router": proj(f"{pre}.gate.weight"),
                "router_bias": jnp.zeros((E,), jnp.float32),
                "w_gate": jnp.stack([expert(e, "w1") for e in range(E)]),
                "w_down": jnp.stack([expert(e, "w2") for e in range(E)]),
                "w_up": jnp.stack([expert(e, "w3") for e in range(E)]),
            }
            return out
        if f"model.layers.{i}.mlp.experts.gate_up_proj_blocks" in t:
            # MXFP4-quantized experts (the format real gpt-oss checkpoints
            # ship): e2m1 nibble pairs + e8m0 per-32 block scales (layout
            # per the HF mxfp4 integration: lo/hi nibbles interleave along
            # the last dim, stored [E, cols, groups, 16] → param
            # [E, rows, cols]). Kept QUANTIZED in HBM by default — the
            # int8 re-encode is bit-exact, at half the bf16 footprint;
            # DYN_MXFP4_DEQUANT=1 restores load-time bf16 for debugging
            pre = f"model.layers.{i}.mlp"
            if os.environ.get("DYN_MXFP4_DEQUANT"):
                gu = _mxfp4_dequant(
                    np.asarray(t[f"{pre}.experts.gate_up_proj_blocks"]),
                    np.asarray(t[f"{pre}.experts.gate_up_proj_scales"]),
                    out_dtype=dtype)
                down = _mxfp4_dequant(
                    np.asarray(t[f"{pre}.experts.down_proj_blocks"]),
                    np.asarray(t[f"{pre}.experts.down_proj_scales"]),
                    out_dtype=dtype)
                return oss_experts(pre, gu, jnp.asarray(down, dtype=dtype))
            gu = _mxfp4_to_qtensor(
                np.asarray(t[f"{pre}.experts.gate_up_proj_blocks"]),
                np.asarray(t[f"{pre}.experts.gate_up_proj_scales"]))
            down = _mxfp4_to_qtensor(
                np.asarray(t[f"{pre}.experts.down_proj_blocks"]),
                np.asarray(t[f"{pre}.experts.down_proj_scales"]))
            return oss_experts(pre, gu,
                               {k: jnp.asarray(v) for k, v in down.items()})
        if f"model.layers.{i}.mlp.experts.gate_up_proj" in t:  # gpt-oss
            pre = f"model.layers.{i}.mlp"
            # fused [E, D, 2F] with gate/up interleaved on the last dim;
            # stored [in, out] already (nn.Parameter, not a Linear)
            return oss_experts(pre, np.asarray(t[f"{pre}.experts.gate_up_proj"]),
                               get(f"{pre}.experts.down_proj"))
        pre = f"model.layers.{i}.mlp"  # deepseek/qwen-moe style
        bias_name = f"{pre}.gate.e_score_correction_bias"
        expert = lambda e, n: proj(f"{pre}.experts.{e}.{n}.weight")  # noqa: E731
        out = {
            "router": proj(f"{pre}.gate.weight"),
            "router_bias": (jnp.asarray(np.asarray(t[bias_name]), jnp.float32)
                            if bias_name in t else jnp.zeros((E,), jnp.float32)),
            "w_gate": jnp.stack([expert(e, "gate_proj") for e in range(E)]),
            "w_up": jnp.stack([expert(e, "up_proj") for e in range(E)]),
            "w_down": jnp.stack([expert(e, "down_proj") for e in range(E)]),
        }
        if cfg.n_shared_experts:
            out["ws_gate"] = proj(f"{pre}.shared_experts.gate_proj.weight")
            out["ws_up"] = proj(f"{pre}.shared_experts.up_proj.weight")
            out["ws_down"] = proj(f"{pre}.shared_experts.down_proj.weight")
        return out

    def norm_get(name):
        """Gemma RMSNorms scale by (1 + w); folding the +1 into the stored
        weight at load keeps the forward's single-norm codepath (x̂·w).
        The fold happens AND STAYS in f32 (HF computes 1.0 + weight.float()
        and multiplies pre-downcast): folding then casting to bf16 would
        flush small-w channels to exactly 1.0, compounding over Gemma-2's
        4 norms/layer (ADVICE r4). Norm vectors are negligible next to the
        weight matrices, and _rms_norm applies f32 weights before its final
        cast."""
        w = get(name)
        if not cfg.norm_plus_one:
            return w
        return np.asarray(w, np.float32) + 1.0

    def norm_layer(i: int) -> dict:
        if cfg.sandwich_norms:
            # Gemma-2: post_attention_layernorm is the POST-norm on the
            # attention OUTPUT; the pre-MLP norm is pre_feedforward_layernorm
            return {
                "attn_norm": norm_get(f"model.layers.{i}.input_layernorm.weight"),
                "post_attn_norm": norm_get(
                    f"model.layers.{i}.post_attention_layernorm.weight"),
                "mlp_norm": norm_get(
                    f"model.layers.{i}.pre_feedforward_layernorm.weight"),
                "post_mlp_norm": norm_get(
                    f"model.layers.{i}.post_feedforward_layernorm.weight"),
            }
        return {
            "attn_norm": norm_get(f"model.layers.{i}.input_layernorm.weight"),
            "mlp_norm": norm_get(f"model.layers.{i}.post_attention_layernorm.weight"),
        }

    k_dense = cfg.num_dense_prefix_layers

    def build_stack(idxs, moe: bool) -> dict:
        per_layer = []
        for i in idxs:
            d = {**norm_layer(i), **attn_layer(i)}
            d.update(moe_mlp_layer(i) if moe else dense_mlp_layer(i))
            per_layer.append(d)
        return {k: stack([d[k] for d in per_layer]) for k in per_layer[0]}

    params = {
        "embed": get("model.embed_tokens.weight"),
        "layers": build_stack(range(k_dense, L), cfg.is_moe),
        "final_norm": norm_get("model.norm.weight"),
    }
    if k_dense:
        params["dense_layers"] = build_stack(range(k_dense), False)
    if not cfg.tie_word_embeddings:
        if "lm_head.weight" in t:
            params["lm_head"] = proj("lm_head.weight")
        else:
            logger.warning("lm_head.weight missing; tying to embeddings")
            cfg.tie_word_embeddings = True
    return params


def load_model(path: str, dtype=None) -> tuple[ModelConfig, dict]:
    """Config + params from a local HF model directory."""
    cfg = ModelConfig.from_pretrained(path)
    return cfg, load_hf_params(cfg, path, dtype)
