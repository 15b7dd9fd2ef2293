"""Engine configuration: model architecture + engine runtime knobs.

The knob set mirrors the reference's engine-arg surface (ref:
components/backends/vllm/src/dynamo/vllm/args.py, mocker/protocols.rs:67-100)
— block_size / num blocks / max_num_seqs / max_num_batched_tokens /
enable_prefix_caching / enable_chunked_prefill — plus TPU-native additions
(mesh shape, dtype, bucketing).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from dataclasses import dataclass, field
from typing import NamedTuple, Optional


#: static cap on prefill chunks co-scheduled into one ragged step (the
#: chunk grid sizes for exactly this many — model.ragged_grid_shape);
#: extra chunks wait a step
RAGGED_MAX_CHUNKS = 4


class LayerKind(NamedTuple):
    """One kind of attention layer of a model whose layers differ (MiMo-V2:
    window layers and full layers with their own KV-head counts)."""

    num_kv_heads: int
    rope_theta: float
    window: int  # 0 = full attention
    sink: bool   # learned per-head sink logit joins the softmax
    #: what mixes tokens: "attention" (keys and values a token, in a cache
    #: group), or one of the recurrent mixers, which keep one fixed-size
    #: state record a SEQUENCE in a state slot (``ModelConfig.state_spec``;
    #: the fields above are unused): "mamba2" (a convolution's tail and an
    #: SSM matrix) or "shortconv" (LFM2's gated short convolution: the tail
    #: alone)
    mixer: str = "attention"


#: the mixers whose layers keep their state in a slot, not in pages
STATE_MIXERS = ("mamba2", "shortconv")


class StateSpec(NamedTuple):
    """The per-sequence recurrent state of a model's state layers (either
    recurrent mixer; a model has one of the two): one record a layer a
    slot, whatever the sequence's length. ``conv`` is the convolution's
    tail (the last ``taps - 1`` inputs). A Mamba-2 layer keeps ``ssm``
    beside it, the state matrix of every head, stored with ``pack`` heads
    side by side on the minor axis so that it is a whole 128-lane row
    (ops/mamba2.py); a short-convolution layer keeps the tail and nothing
    else (``ssm_shape`` None: ops/shortconv.py)."""

    layers: tuple      # model layer indices, in order
    conv_shape: tuple  # (taps - 1, channels of the convolution)
    ssm_shape: Optional[tuple]  # (heads // pack, d_state, pack · d_head)
    conv_dtype: str
    ssm_dtype: Optional[str]
    mixer: str = "mamba2"

    def bytes_per_slot(self) -> int:
        import numpy as np

        ssm = 0 if self.ssm_shape is None else (
            int(np.prod(self.ssm_shape)) * np.dtype(self.ssm_dtype).itemsize)
        return len(self.layers) * (
            int(np.prod(self.conv_shape)) * np.dtype(self.conv_dtype).itemsize
            + ssm)


class CacheGroup(NamedTuple):
    """The layers that share one paged-cache shape. Every group has the
    same slots and is indexed by the same block table (page i exists in
    each); a model with one group keeps one ``[L, slots, KV, hd]`` array a
    stream, a model with more keeps a tuple of them."""

    layers: tuple   # model layer indices, in order
    kv_heads: int
    k_dim: int      # stored width of a K head (ModelConfig.k_cache_dim)
    v_dim: int
    window: int     # 0 = full attention
    #: 128-lane rows a stored K head takes: the K array is
    #: [L, slots, kv_heads·k_rows, k_dim // k_rows]
    k_rows: int = 1

    @property
    def k_shape(self) -> tuple:
        """(rows, width) of one slot of the K array."""
        return self.kv_heads * self.k_rows, self.k_dim // self.k_rows

    def bytes_per_slot(self, itemsize: int) -> int:
        return (len(self.layers) * self.kv_heads
                * (self.k_dim + self.v_dim) * itemsize)


@dataclass
class ModelConfig:
    """Pre-norm decoder-only transformer: RoPE + RMSNorm + GQA paged
    attention with a SwiGLU or token-choice expert MLP. Covers the Llama
    family (Llama 2/3, Mistral, Qwen2/3, Phi-3), Gemma 1/2, gpt-oss
    (per-layer windows, sinks), DeepSeek V2/V3 (MLA, shared experts,
    sigmoid routing), MiMo-V2 (layer KINDS with their own KV-head
    count, rope base, window and sink; K/Q heads wider than V heads;
    partial rotary; an expert layer that holds a share of the experts it
    routes over) and the hybrids whose layer kinds include a recurrent
    mixer with per-sequence state in slots: Granite 4.0-H (Mamba-2) and
    LFM2 (gated short convolution)."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    rope_theta: float = 10000.0
    #: HF rope_scaling dict (yarn / llama3 supported — model.rope_params);
    #: unsupported types fail loudly at trace time
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # MoE (0 experts = dense MLP)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    #: EP dispatch capacity: each expert takes up to ceil(N*K/E * this)
    #: tokens per step (Switch-style dropping past that; >= E/K disables
    #: dropping entirely)
    moe_capacity_factor: float = 2.0
    #: expert MLP width (DeepSeek's moe_intermediate_size); None = use
    #: intermediate_size (Mixtral-style)
    moe_intermediate_size: Optional[int] = None
    #: always-on shared experts (DeepSeek): dense SwiGLU of width
    #: n_shared_experts * moe_intermediate_size added to the routed output
    n_shared_experts: int = 0
    #: leading dense (non-MoE) decoder layers (DeepSeek first_k_dense_replace)
    first_k_dense_replace: int = 0
    #: router scoring: "softmax" (Mixtral: softmax over top-k logits) or
    #: "sigmoid" (DeepSeek-V3: sigmoid scores + e_score_correction_bias for
    #: expert choice, gathered raw scores as weights)
    scoring_func: str = "softmax"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    # group-limited routing (DeepSeek: experts in n_group groups, routing
    # restricted to the best topk_group groups)
    n_group: int = 1
    topk_group: int = 1
    # attention extras
    qkv_bias: bool = False  # Qwen2-style
    #: per-head RMSNorm on q and k before RoPE (Qwen3 / Qwen3-MoE); the
    #: learned scale has head_dim width, shared across heads
    qk_norm: bool = False
    o_bias: bool = False  # gpt-oss: o_proj carries a bias too
    sliding_window: Optional[int] = None
    #: per-layer sliding windows (gpt-oss alternates sliding/full layers);
    #: entries are window sizes with 0 = full attention. Overrides
    #: ``sliding_window`` when set; length must equal num_layers.
    layer_windows: Optional[tuple] = None
    #: learned per-head attention-sink logits (gpt-oss): an extra softmax
    #: slot that absorbs probability mass without contributing output
    attention_sinks: bool = False
    #: expert MLP activation: "swiglu" (llama/mixtral/deepseek) or
    #: "swiglu_oss" (gpt-oss clamped variant with biases and (up+1) gating)
    moe_activation: str = "swiglu"
    #: add the router bias to the logits BEFORE top-k in softmax scoring
    #: (gpt-oss's router has a true bias; DeepSeek's e_score_correction_bias
    #: only steers expert CHOICE and is handled in the sigmoid branch)
    router_logit_bias: bool = False
    # --- Gemma family -----------------------------------------------------
    #: scale token embeddings by sqrt(hidden_size) (Gemma; NOT folded into
    #: the weights — the tied lm_head reads them unscaled)
    embed_scale: bool = False
    #: RMSNorm scales by (1 + w) (Gemma); folded into the stored weights at
    #: LOAD time (loader.norm_get), so the forward never branches on it
    norm_plus_one: bool = False
    #: dense-MLP activation: "silu" (llama-family SwiGLU) or "gelu_tanh"
    #: (Gemma GeGLU). Distinct from moe_activation.
    hidden_activation: str = "silu"
    #: Gemma-2 soft capping: s = cap·tanh(s/cap) on attention scores and on
    #: final logits; 0 = off. Nonzero attn cap forces the XLA attention
    #: path (the Pallas kernels' online softmax has no tanh stage).
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    #: Gemma-2 sandwich norms: post-norms applied to each sublayer's OUTPUT
    #: before the residual add (extra per-layer weights post_attn_norm /
    #: post_mlp_norm; mlp_norm holds pre_feedforward_layernorm)
    sandwich_norms: bool = False
    #: attention scale = query_pre_attn_scalar^-0.5 instead of head_dim^-0.5
    #: (Gemma-2; folded into q so every attention path inherits it)
    query_pre_attn_scalar: Optional[float] = None
    # --- MLA (multi-head latent attention, DeepSeek V2/V3) ---------------
    #: latent rank of the compressed KV; >0 switches attention to MLA and
    #: the paged cache to the latent layout (see kv_cache_spec)
    kv_lora_rank: int = 0
    q_lora_rank: Optional[int] = None  # None = full q projection (V2-Lite)
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    #: width of a V head: MLA's published one (default 128); elsewhere None
    #: = ``head_dim``, set where V heads are narrower than K/Q heads
    #: (MiMo-V2: 192 | 128). Read it as ``v_dim``.
    v_head_dim: Optional[int] = None
    # --- layer kinds (MiMo-V2) -------------------------------------------
    #: kinds of attention layer and, per layer, which kind it is. Each kind
    #: has its own parameter stack (Wk/Wv shapes differ) and its own cache
    #: group; ``num_kv_heads`` / ``rope_theta`` / ``sliding_window`` /
    #: ``attention_sinks`` above are then unused
    layer_kinds: Optional[tuple] = None
    layer_pattern: Optional[tuple] = None
    #: leading dims of a head that RoPE turns (rotate-half pairing inside
    #: them); None = all of head_dim
    rotary_dim: Optional[int] = None
    #: attention output = P·(value_scale·v)
    value_scale: float = 1.0
    #: (first, count): the experts THIS chip holds of the ``num_experts``
    #: the router scores — one rank's share of an expert-parallel
    #: deployment. The layer computes its own experts' part of the result,
    #: dropless; what the absent experts would add is left out. None =
    #: every expert is held.
    experts_held: Optional[tuple] = None
    # --- Granite 4.0-H (granitemoehybrid) ---------------------------------
    #: "rope", or "nope": attention layers turn nothing (no position term)
    position_embedding: str = "rope"
    #: h = embedding_multiplier · E[token]; every sublayer's output times
    #: residual_multiplier before it joins the stream; logits divided by
    #: logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    #: Mamba-2 mixer sizes (layer kinds whose ``mixer`` is "mamba2"): heads
    #: of ``mamba_d_head``, a state of ``mamba_d_state`` a head channel, one
    #: B/C group, a causal depthwise convolution of ``mamba_d_conv`` taps
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    #: dtype the state matrix is kept in between steps (assumed float32:
    #: what engines recommend for this family's accuracy)
    mamba_state_dtype: str = "float32"
    # --- LFM2 (lfm2_moe) ---------------------------------------------------
    #: taps of the gated short convolution (layer kinds whose ``mixer`` is
    #: "shortconv"; the published ``conv_L_cache``): a sequence carries the
    #: last ``shortconv_taps - 1`` inputs of every such layer, nothing else
    shortconv_taps: int = 3
    #: added to the sum of a token's chosen gates before they are divided by
    #: it (``norm_topk_prob``): 1e-20 is DeepSeek's and MiMo's, LFM2
    #: publishes 1e-6
    router_norm_eps: float = 1e-20
    #: store K and V heads narrower than a 128-lane row zero-padded to one,
    #: so that the ragged kernel (which strides whole lane rows) takes a
    #: model of 64-wide heads: a page doubles, the zeros add nothing to a
    #: score and the output's padding lanes are cut. False: such heads are
    #: stored as they are and the kernel refuses them (``lane_align``)
    kv_lane_pad: bool = False
    #: random init only: std of the (tied) embedding's entries where fan-in
    #: scaling would leave the logits flat, and a gain on every projection
    #: that writes into the residual stream (wo, out_proj, the experts' and
    #: the shared expert's down projections). None / 1.0 = fan-in scaling
    init_embed_std: Optional[float] = None
    init_out_gain: float = 1.0
    #: random init only: std of every RMSNorm weight around 1 (0: exactly
    #: 1, the neutral value, at which a path that forgets a norm's weight
    #: computes the same)
    init_norm_std: float = 0.0

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.v_head_dim is None and self.kv_lora_rank > 0:
            self.v_head_dim = 128
        if self.layer_kinds is not None:
            self.layer_kinds = tuple(LayerKind(*k) for k in self.layer_kinds)
            self.layer_pattern = tuple(int(i) for i in self.layer_pattern)
            if (len(self.layer_pattern) != self.num_layers
                    or not all(0 <= i < len(self.layer_kinds)
                               for i in self.layer_pattern)):
                raise ValueError(
                    f"layer_pattern {self.layer_pattern} does not name one "
                    f"of {len(self.layer_kinds)} kinds for each of "
                    f"{self.num_layers} layers")
            mixers = [k.mixer for k in self.layer_kinds]
            n_attn = mixers.count("attention")
            state = set(mixers[n_attn:])
            if (not n_attn or "attention" in state or len(state) > 1
                    or not state <= set(STATE_MIXERS)):
                # a kind's index is its cache group's: attention kinds first
                raise ValueError(
                    f"layer_kinds {mixers}: attention kinds come first, "
                    f"then the kinds of ONE state mixer of {STATE_MIXERS}")
        if self.experts_held is not None:
            first, count = self.experts_held = tuple(self.experts_held)
            if not 0 <= first < first + count <= self.num_experts:
                raise ValueError(
                    f"experts_held {self.experts_held} outside the "
                    f"{self.num_experts} routed experts")
        if self.layer_windows is not None:
            self.layer_windows = tuple(int(w or 0) for w in self.layer_windows)
            if len(self.layer_windows) != self.num_layers:
                raise ValueError(
                    f"layer_windows has {len(self.layer_windows)} entries "
                    f"for {self.num_layers} layers")

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_cache_dim(self) -> int:
        """MLA rope-part cache width: qk_rope_head_dim rounded up to a
        128-lane multiple (TPU DMA tile alignment)."""
        return -(-self.qk_rope_head_dim // 128) * 128

    @property
    def moe_ffn_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def num_dense_prefix_layers(self) -> int:
        """Layers in the separate ``dense_layers`` param stack. THE single
        source of the dense-prefix rule — loader, init, shardings, and
        forward all key off this, so the pytree contract cannot drift."""
        return self.first_k_dense_replace if self.is_moe else 0

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def num_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def k_cache_dim(self) -> int:
        """Stored width of a K head. A head wider than a lane row that is
        not a lane multiple (MiMo-V2's 192) is stored zero-padded to the
        next one, as that many whole 128-lane rows (``k_lane_rows``): the
        ragged kernel DMAs lane rows and strides their sublanes, q is
        padded alike, and the zeros add nothing to a score."""
        if self.k_lane_rows > 1:
            return 128 * self.k_lane_rows
        return self._lane_padded(self.head_dim)

    @property
    def v_cache_dim(self) -> int:
        """Stored width of a V head: ``v_dim``, or a whole lane row under
        ``kv_lane_pad``."""
        return self._lane_padded(self.v_dim)

    def _lane_padded(self, width: int) -> int:
        return 128 if self.kv_lane_pad and width < 128 else width

    @property
    def kv_lane_pad_share(self) -> float:
        """Share of a KV page's bytes that is padding (wide K heads stored
        as lane rows, narrow heads under ``kv_lane_pad``): 0 where heads
        are stored as they are."""
        if self.is_mla:
            return 0.0
        return 1.0 - (self.head_dim + self.v_dim) / (
            self.k_cache_dim + self.v_cache_dim)

    @property
    def k_lane_rows(self) -> int:
        hd = self.head_dim
        return 1 if hd < 128 or hd % 128 == 0 else -(-hd // 128)

    def layer_kind(self, layer: int) -> LayerKind:
        """The kind of ``layer``; a model without kinds has one, made of
        its global fields (``layer_windows`` still says each window)."""
        if self.layer_kinds is not None:
            return self.layer_kinds[self.layer_pattern[layer]]
        window = (self.layer_windows[layer] if self.layer_windows is not None
                  else self.sliding_window or 0)
        return LayerKind(self.num_kv_heads, self.rope_theta, window,
                         self.attention_sinks)

    @property
    def kv_cache_spec(self) -> tuple:
        """The cache groups (:class:`CacheGroup`), one per layer kind.

        MHA/GQA: K and V rows of ``[kv_heads, k_cache_dim | v_head_dim]``.
        MLA is one group that stores the compressed latent instead — K
        rows [1, kv_lora_rank] (normalized c_kv) and V rows [1, rope_pad]
        (the shared post-RoPE k_rot, zero-padded to a 128-lane multiple so
        the Pallas decode kernel can DMA cache pages tile-aligned) — the
        memory win that makes DeepSeek-class models servable.
        """
        every = tuple(range(self.num_layers))
        if self.is_mla:
            return (CacheGroup(every, 1, self.kv_lora_rank,
                               self.rope_cache_dim, 0),)
        if self.layer_kinds is None:
            return (CacheGroup(every, self.num_kv_heads, self.k_cache_dim,
                               self.v_cache_dim, self.sliding_window or 0,
                               self.k_lane_rows),)
        return tuple(
            CacheGroup(tuple(i for i in every if self.layer_pattern[i] == g),
                       k.num_kv_heads, self.k_cache_dim, self.v_cache_dim,
                       k.window, self.k_lane_rows)
            for g, k in enumerate(self.layer_kinds) if k.mixer == "attention")

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_head_pack(self) -> int:
        """Heads stored side by side on the state's minor axis."""
        return max(1, min(self.mamba_n_heads, 128 // max(1, self.mamba_d_head)))

    @property
    def state_spec(self) -> Optional[StateSpec]:
        """The recurrent state the model keeps a sequence, or None (every
        model whose layers are all attention layers)."""
        if self.layer_kinds is None:
            return None
        layers = tuple(i for i, k in enumerate(self.layer_pattern)
                       if self.layer_kinds[k].mixer != "attention")
        if not layers:
            return None
        if self.layer_kinds[-1].mixer == "shortconv":
            return StateSpec(layers,
                             (self.shortconv_taps - 1, self.hidden_size),
                             None, self.dtype, None, "shortconv")
        pack = self.mamba_head_pack
        return StateSpec(
            layers,
            (self.mamba_d_conv - 1,
             self.mamba_d_inner + 2 * self.mamba_d_state),
            (self.mamba_n_heads // pack, self.mamba_d_state,
             pack * self.mamba_d_head),
            self.dtype, self.mamba_state_dtype)

    @staticmethod
    def from_hf_config(d: dict) -> "ModelConfig":
        """Map a HuggingFace ``config.json`` dict onto ModelConfig.

        Handles llama/mistral/qwen2/mixtral keys (ref parity: the reference
        loads the same file into its ModelDeploymentCard — model_card.rs:93).
        """
        arch = (d.get("architectures") or [""])[0].lower()
        is_deepseek = "deepseek" in arch
        is_gpt_oss = "gptoss" in arch
        is_gemma2 = "gemma2" in arch
        is_gemma = "gemma" in arch  # gemma-1 OR gemma-2
        if "gemma3" in arch:
            raise NotImplementedError(
                "Gemma-3 (dual-base rope, plus-one qk-norm) is not "
                "supported yet; Gemma 1/2 are")
        is_phi3 = "phi3" in arch  # Phi-3 family AND Phi-4 (same arch class)
        if is_phi3:
            if float(d.get("partial_rotary_factor") or 1.0) != 1.0:
                raise NotImplementedError(
                    "partial rotary (phi-4-mini style) is not supported")
            sc = d.get("rope_scaling")
            if sc and sc.get("rope_type", sc.get("type")) == "longrope":
                # longrope factors live in the scaling dict but the window
                # sizes live on the top-level config — carry them together
                # (model.rope_params reads only the dict)
                sc = dict(sc)
                sc["max_position_embeddings"] = d.get(
                    "max_position_embeddings", 4096)
                sc["original_max_position_embeddings"] = d.get(
                    "original_max_position_embeddings",
                    sc.get("original_max_position_embeddings",
                           sc["max_position_embeddings"]))
                d = {**d, "rope_scaling": sc}
        if "qwen3moe" in arch:
            # the uniform layer stack (lax.scan) requires every non-prefix
            # layer to be MoE; refuse irregular sparsity loudly rather than
            # serving a silently-wrong forward
            if d.get("mlp_only_layers") or d.get("decoder_sparse_step", 1) != 1:
                raise ValueError(
                    "Qwen3-MoE checkpoints with mlp_only_layers or "
                    "decoder_sparse_step != 1 interleave dense layers mid-"
                    "stack, which the stacked-layer forward does not support")
        mla = is_deepseek and d.get("kv_lora_rank") is not None
        layer_windows = None
        kinds = {}
        if d.get("model_type") == "mimo_v2" or "mimov2" in arch:
            kinds = _mimo_v2_fields(d)
            d = {**d, "first_k_dense_replace": kinds.pop("k_dense")}
            if "experts_held" in kinds:
                # one rank's share: the key that counts the experts gives
                # how many are held, the router keeps its published width
                d["n_routed_experts"] = d["n_routed_experts_published"]
        if d.get("model_type") == "lfm2_moe":
            kinds = _lfm2_moe_fields(d)
            d = {**d, **kinds.pop("keys")}
        if is_gemma2:
            # HF Gemma2: sliding attention on EVEN layer indices
            # (Gemma2DecoderLayer: is_sliding = not bool(layer_idx % 2))
            L = d.get("num_hidden_layers", 26)
            w = d.get("sliding_window", 4096)
            layer_windows = tuple(w if i % 2 == 0 else 0 for i in range(L))
        if is_gpt_oss:
            L = d.get("num_hidden_layers", 36)
            types = d.get("layer_types") or [
                "sliding_attention" if i % 2 == 0 else "full_attention"
                for i in range(L)]
            layer_windows = tuple(
                d.get("sliding_window", 128) if t == "sliding_attention" else 0
                for t in types)
        return ModelConfig(
            vocab_size=d.get("vocab_size", 32000),
            hidden_size=d.get("hidden_size", 4096),
            intermediate_size=d.get("intermediate_size", 11008),
            num_layers=d.get("num_hidden_layers", 32),
            num_heads=d.get("num_attention_heads", 32),
            num_kv_heads=d.get("num_key_value_heads", d.get("num_attention_heads", 32)),
            head_dim=d.get("head_dim") if not is_deepseek else None,
            embed_scale=is_gemma,
            norm_plus_one=is_gemma,
            hidden_activation=("gelu_tanh" if is_gemma else "silu"),
            attn_logit_softcap=(d.get("attn_logit_softcapping") or 0.0)
            if is_gemma2 else 0.0,
            final_logit_softcap=(d.get("final_logit_softcapping") or 0.0)
            if is_gemma2 else 0.0,
            sandwich_norms=is_gemma2,
            query_pre_attn_scalar=(d.get("query_pre_attn_scalar")
                                   if is_gemma2 else None),
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=d.get("rope_scaling"),
            rms_norm_eps=d.get("rms_norm_eps",
                               d.get("layernorm_epsilon", 1e-5)),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            num_experts=(d.get("num_local_experts")       # mixtral
                         or d.get("n_routed_experts")      # deepseek
                         or d.get("num_experts", 0)        # qwen3-moe
                         or 0),
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
            moe_intermediate_size=d.get("moe_intermediate_size"),
            n_shared_experts=d.get("n_shared_experts", 0) or 0,
            first_k_dense_replace=d.get("first_k_dense_replace", 0) or 0,
            scoring_func=d.get("scoring_func",
                               "sigmoid" if "deepseekv3" in arch else "softmax"),
            # Mixtral and gpt-oss renormalize their top-k gates (their HF
            # configs have no such key); DeepSeek carries the flag explicitly
            norm_topk_prob=d.get("norm_topk_prob",
                                 "mixtral" in arch or is_gpt_oss),
            routed_scaling_factor=d.get("routed_scaling_factor") or 1.0,
            n_group=d.get("n_group", 1) or 1,
            topk_group=d.get("topk_group", 1) or 1,
            kv_lora_rank=d.get("kv_lora_rank", 0) if mla else 0,
            q_lora_rank=d.get("q_lora_rank") if mla else None,
            qk_nope_head_dim=d.get("qk_nope_head_dim", 128),
            qk_rope_head_dim=d.get("qk_rope_head_dim", 64),
            v_head_dim=(d.get("v_head_dim", 128) if mla
                        else d.get("v_head_dim") if kinds else None),
            **kinds,
            qkv_bias=("qwen2" in arch
                      or (is_gpt_oss and d.get("attention_bias", True))),
            qk_norm="qwen3" in arch or d.get("model_type") == "lfm2_moe",
            o_bias=is_gpt_oss and d.get("attention_bias", True),
            layer_windows=layer_windows,
            attention_sinks=is_gpt_oss,
            moe_activation="swiglu_oss" if is_gpt_oss else "swiglu",
            router_logit_bias=is_gpt_oss,
            # qwen2 writes sliding_window but gates it behind
            # use_sliding_window, whose HF default is False; mistral-style
            # configs apply the window unconditionally; gpt-oss windows are
            # per-layer (layer_windows above)
            sliding_window=(d.get("sliding_window")
                            if not is_gpt_oss and not kinds
                            and d.get("use_sliding_window",
                                      "qwen2" not in arch) else None),
        )

    @staticmethod
    def from_pretrained(path: str) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return ModelConfig.from_hf_config(json.load(f))

    # ---- canned architectures for tests / benches -------------------------

    @staticmethod
    def tiny(vocab_size: int = 256) -> "ModelConfig":
        return ModelConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, rope_theta=10000.0,
            max_position_embeddings=512, dtype="float32",
        )

    @staticmethod
    def llama3_8b() -> "ModelConfig":
        return ModelConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
            max_position_embeddings=8192,
        )

    @staticmethod
    def llama3_70b() -> "ModelConfig":
        return ModelConfig(
            vocab_size=128256, hidden_size=8192, intermediate_size=28672,
            num_layers=80, num_heads=64, num_kv_heads=8, rope_theta=500000.0,
            max_position_embeddings=8192,
        )

    @staticmethod
    def llama3_1b() -> "ModelConfig":
        """Llama-3.2-1B shape — fits a single v5e chip comfortably in bf16."""
        return ModelConfig(
            vocab_size=128256, hidden_size=2048, intermediate_size=8192,
            num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
            rope_theta=500000.0, max_position_embeddings=8192,
            tie_word_embeddings=True,
        )


def _lfm2_moe_fields(d: dict) -> dict:
    """LFM2-MoE's published keys → the ModelConfig fields they set:
    ``layer_types`` ("conv": the gated short convolution, anything else an
    attention layer), ``conv_L_cache``, ``num_dense_layers``, ``norm_eps``,
    ``rope_parameters`` (or a bare ``rope_theta``), ``use_expert_bias``.
    Every expert is held (``experts_held`` = all) unless the file says
    otherwise, the head is the embedding unless it says otherwise, and a
    64-wide head is stored as a whole lane row (``kv_lane_pad``)."""
    if d.get("conv_bias"):
        raise NotImplementedError("lfm2_moe with conv_bias is not supported")
    if not d.get("use_expert_bias", True):
        raise NotImplementedError(
            "lfm2_moe without use_expert_bias (a softmax router) is not "
            "supported")
    rope = d.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise NotImplementedError(f"lfm2_moe rope_parameters {rope}")
    theta = float(rope.get("rope_theta", d.get("rope_theta", 1e6)))
    types = d["layer_types"]
    E = d["num_experts"]
    return dict(
        layer_kinds=((d["num_key_value_heads"], theta, 0, False),
                     (0, 0.0, 0, False, "shortconv")),
        layer_pattern=tuple(int(t == "conv") for t in types),
        shortconv_taps=int(d.get("conv_L_cache", 3)),
        kv_lane_pad=True, router_norm_eps=1e-6,
        experts_held=tuple(d.get("experts_held") or (0, E)),
        keys={"first_k_dense_replace": d.get("num_dense_layers", 0),
              "rms_norm_eps": d.get("norm_eps", 1e-5),
              "rope_theta": theta, "scoring_func": "sigmoid",
              "tie_word_embeddings": d.get("tie_word_embeddings", True)},
    )


def _mimo_v2_fields(d: dict) -> dict:
    """MiMo-V2's published keys → the ModelConfig fields they set:
    ``hybrid_layer_pattern`` (0 full, 1 window) with the ``swa_*`` twins of
    the attention sizes, ``add_*_sink_bias``, ``moe_layer_freq``,
    ``partial_rotary_factor``, ``attention_value_scale``. ``experts_held``
    is not a published key: a configuration that stands for one rank of an
    expert-parallel deployment states it beside them."""
    hd, vd = d["head_dim"], d.get("v_head_dim", d["head_dim"])
    if (d.get("swa_head_dim", hd), d.get("swa_v_head_dim", vd),
            d.get("swa_num_attention_heads", d["num_attention_heads"])) != (
            hd, vd, d["num_attention_heads"]):
        raise NotImplementedError(
            "window layers whose head count or head widths differ from the "
            "full layers' are not supported (only their KV-head count, rope "
            "base, window and sink may)")
    moe = [bool(x) for x in d["moe_layer_freq"]]
    k_dense = moe.index(True) if True in moe else len(moe)
    if not all(moe[k_dense:]):
        raise NotImplementedError(
            "dense layers after the first expert layer (moe_layer_freq) "
            "are not supported: only a dense prefix is")
    window = d.get("sliding_window", d.get("sliding_window_size"))
    out = dict(
        layer_kinds=(
            (d["num_key_value_heads"], float(d["rope_theta"]), 0,
             bool(d.get("add_full_attention_sink_bias", False))),
            (d.get("swa_num_key_value_heads", d["num_key_value_heads"]),
             float(d.get("swa_rope_theta", d["rope_theta"])), window,
             bool(d.get("add_swa_attention_sink_bias", False)))),
        layer_pattern=tuple(d["hybrid_layer_pattern"]),
        # HF rounds the rotated width down to an even count of dims
        rotary_dim=int(hd * float(d.get("partial_rotary_factor", 1.0)))
        // 2 * 2,
        value_scale=float(d.get("attention_value_scale") or 1.0),
        k_dense=k_dense,
    )
    if d.get("experts_held") is not None:
        out["experts_held"] = tuple(d["experts_held"])
        if out["experts_held"][1] != d["n_routed_experts"]:
            raise ValueError(
                f"experts_held {d['experts_held']} does not hold the "
                f"n_routed_experts = {d['n_routed_experts']} the file states")
    return out


@dataclass
class EngineArgs:
    """Engine runtime knobs (ref: vllm/args.py + mocker/protocols.rs:67-100)."""

    block_size: int = 16
    num_blocks: Optional[int] = None  # None = size from HBM budget
    max_num_seqs: int = 64
    max_num_batched_tokens: int = 2048
    max_model_len: int = 4096
    enable_prefix_caching: bool = True
    enable_chunked_prefill: bool = True
    watermark: float = 0.01
    # TPU-native:
    tp_size: int = 1  # tensor parallel (mesh "tp" axis)
    dp_size: int = 1  # batch shards inside one engine (mesh "dp" axis)
    #: pipeline stages (mesh "pp" axis, outermost): stage-sliced layer stack
    #: + GPipe microbatching (parallel/pipeline.py). Dense GQA families only;
    #: disables multi-step decode / spec decode / int8 KV for the engine.
    pp_size: int = 1
    kv_cache_memory_fraction: float = 0.6  # of free HBM, when num_blocks is None
    decode_batch_buckets: tuple = ()  # () = powers of two up to max_num_seqs
    prefill_buckets: tuple = ()  # () = powers of two up to max_num_batched_tokens
    #: packed-token buckets for the ragged step (docs/performance.md):
    #: prefill chunks and decode rows of a plan ride ONE packed token batch
    #: served by the ragged paged-attention path (ops/ragged_attention.py)
    #: — the engine's only step path. Compiled-signature count collapses to
    #: the token buckets below (R and W derive statically from T), warmup
    #: shrinks to a handful of traces, and the scheduler plans a token
    #: budget per step. () = powers of two from 8 up to
    #: max_num_batched_tokens
    ragged_token_buckets: tuple = ()
    use_pallas_attention: bool = False  # Pallas paged-attention kernel (TPU only)
    #: decode steps fused into one jitted call when only decode work exists
    #: (amortizes per-dispatch latency; tokens deliver in bursts of this size)
    multi_step_decode: int = 1
    #: depth-2 software pipelining of single-step decode: step N+1 is
    #: dispatched with step N's sampled tokens fed device-to-device, so the
    #: host copy + commit/emit of step N overlap step N+1's device time
    #: (engine._run_decode_pipelined). Applies when multi_step_decode == 1,
    #: no speculative decoding, single host. Greedy-invariant: emits exactly
    #: the tokens the serial loop would.
    pipeline_decode: bool = True
    #: AOT bucket warmup at startup (engine.warmup()): precompile the jitted
    #: step for every configured prefill/decode bucket so the first real
    #: request does not eat XLA compilation (the TTFT p95-vs-p50 cliff).
    #: Opt-in — warmup costs one compile per bucket up front.
    warmup_buckets: bool = False
    #: speculative decoding: draft up to this many tokens and verify them in
    #: ONE forward — greedy-invariant (identical tokens to plain decode).
    #: 0 = off. Applies to temperature-0 batches without logprobs; the
    #: reference delegates spec decode to its engines and reports it via
    #: SpecDecodeStats (kv_router/protocols.rs:48-84)
    speculative_tokens: int = 0
    #: how drafts are produced: "prompt_lookup" (n-gram match in the
    #: sequence's own history — free, shines on repetitive text) or
    #: "draft_layers" (layer-skip self-drafting: the first
    #: speculative_draft_layers layers + shared LM head run as the draft
    #: model — model.make_draft_fn; drafts every step, costs
    #: draft_layers/num_layers of a forward per drafted token)
    speculative_method: str = "prompt_lookup"
    #: layer count of the layer-skip draft model (speculative_method=
    #: "draft_layers"); must be in (0, num_layers)
    speculative_draft_layers: int = 0
    # KVBM tiers (0 = tier disabled; ref: block_manager.rs:62-75 G2/G3)
    kvbm_host_bytes: int = 0
    kvbm_disk_dir: Optional[str] = None
    kvbm_disk_bytes: int = 0
    #: preempt-to-swap: under KV pressure the scheduler swaps a victim's
    #: device pages to host DRAM (gather → host bundle, same value/packed
    #: quant format the G2 tier carries) and swaps them back before the
    #: sequence's next step, instead of releasing the blocks and
    #: re-prefilling from scratch. Recompute preemption remains the
    #: fallback when the host-byte budget is exhausted or a bundle is torn
    #: down. Disabled automatically under multi-host step replication.
    preempt_swap: bool = True
    #: host-byte budget for swapped-out KV. None = share the G2 tier's
    #: budget when kvbm_host_bytes > 0 (available swap bytes shrink as G2
    #: fills), else a standalone 1 GiB allowance.
    swap_host_bytes: Optional[int] = None
    #: publish one KV stored event per prefill CHUNK instead of one per
    #: request. Per-request batching is the default — per-chunk publishing
    #: measured 11% under the 70B fleet's stored-blocks/s requirement
    #: (docs/PERF_NOTES.md fleet_bench table: 47.3k vs 53k needed; per-
    #: request reaches 119.5k). None = read the DYN_KV_EVENT_PER_CHUNK
    #: env escape hatch (unset/0/false = batched).
    kv_event_per_chunk: Optional[bool] = None
    #: speculative-decode auto-disable: when the rolling measured gain over
    #: spec_gain_window verify dispatches stays < 1 (drafts cost more than
    #: they accept — BENCH_r05: accept 0.019, gain 0.729, a 27% slowdown
    #: with nothing turning it off), fall back to plain decode and re-probe
    #: after spec_reprobe_steps engine steps. 0 disables the governor.
    spec_gain_window: int = 64
    spec_reprobe_steps: int = 4096
    #: on-device weight quantization: None (model dtype) | "int8" (per-out-
    #: channel) | "int8-gN" / "int4-gN" (grouped, N along the contraction
    #: dim). Weights stay quantized in HBM; dequant rides the matmul
    #: (engine/quant.py). GGUF/MXFP4 checkpoints can also load pre-quantized
    #: (loader keeps native groups). Ref capability: FP8 70B recipe,
    #: recipes/llama-3-70b/vllm/disagg-single-node/deploy.yaml:21-86
    quantization: Optional[str] = None
    #: paged KV cache dtype: None/"auto" (model dtype) | "int8" (symmetric
    #: per-(slot, head) scales; ~2x KV capacity and half the decode kernel's
    #: HBM page traffic — engine/cache.py int8 notes). KV-capacity role of
    #: the reference's G1 tier (lib/llm/src/block_manager/). Not yet
    #: supported for MLA latent caches (falls back to model dtype).
    kv_cache_dtype: Optional[str] = None
    #: disagg KV transfer: offer direct device-to-device page pulls
    #: (same-process registry / jax.experimental.transfer over ICI) when the
    #: decode worker advertises reach — the NIXL analog (disagg/transfer.py).
    #: False = always host-staged bundles over the response plane.
    kv_transfer_direct: bool = True
    #: layer-interleaved disagg transfer (docs/disagg.md): the TAIL chunk's
    #: bundle — the one whole-bundle transfer serializes after prefill
    #: completes — is split into this many layer groups and streamed as the
    #: gathers land, so early layers' wire/scatter overlaps later layers'
    #: host staging and decode's first step launches before the last layer
    #: arrives. Capability-negotiated per request (``kv_layers``); clamped
    #: to the model's layer count. <= 1 restores whole-bundle tails.
    kv_transfer_layer_groups: int = 4
    #: multi-tenant QoS scheduling (docs/qos.md): per-class waiting queues
    #: drained by weighted-fair virtual token counters, class-aware
    #: preemption victims, aging. With one tenant/class the drain order is
    #: exact FIFO, so this default changes nothing for untagged traffic;
    #: False restores the flat FIFO drain/victim order (bench baseline) —
    #: the swap-in starvation guard (head-of-line skip-ahead after
    #: repeated failed reservations) stays active in both modes, it is a
    #: bugfix to the swap tier, not a QoS policy.
    qos_scheduling: bool = True
    #: QoS policy override (dynamo_tpu.qos.QosConfig); None = load from the
    #: DYN_QOS_* environment at scheduler construction
    qos: Optional[object] = None
    #: structured decoding (docs/structured.md): compile guided-decoding
    #: constraints into dense device tables and run the FSM inside the
    #: sampling dispatch, so constrained rows ride the ragged step, the
    #: pipelined decode loop, the fused multi-step burst, and spec decode
    #: with no host sync. False (--no-structured-device) keeps every
    #: constraint on the host-oracle path (the pre-PR behavior). Also
    #: gated by DYN_STRUCTURED=0 at runtime.
    structured_device: bool = True
    #: byte budget (MiB) for the device FSM arena (mask bitmask + next-
    #: state tables; the next table costs 4·vocab bytes per state). None =
    #: DYN_STRUCTURED_TABLE_MB, default 64. Constraints whose reachable
    #: state closure does not fit fall back to the host oracle.
    structured_table_mb: Optional[float] = None
    seed: int = 0

    def __post_init__(self):
        if self.speculative_method not in ("prompt_lookup", "draft_layers"):
            raise ValueError(
                f"speculative_method={self.speculative_method!r} unknown "
                "(prompt_lookup or draft_layers)")
        if (self.speculative_method == "draft_layers"
                and self.speculative_tokens > 0
                and self.speculative_draft_layers < 1):
            raise ValueError("speculative_method='draft_layers' needs "
                             "speculative_draft_layers >= 1")
        if self.kv_event_per_chunk is None:
            self.kv_event_per_chunk = os.environ.get(
                "DYN_KV_EVENT_PER_CHUNK", "").lower() not in ("", "0", "false")
        if self.kv_cache_dtype not in (None, "auto", "int8"):
            # an unknown value silently serving full-precision would run a
            # deployment at half its planned KV capacity — fail loudly
            raise ValueError(
                f"kv_cache_dtype={self.kv_cache_dtype!r} not supported "
                "(None/'auto' = model dtype, or 'int8')")
        if self.quantization is not None:
            # validate the spec HERE, not at weight-load time deep in the
            # loader: int4 without grouping and unknown "-gN" grammars must
            # surface as a field-named config error, not a raw traceback
            # mid-initialization
            from dynamo_tpu.engine.quant import parse_spec
            try:
                parse_spec(self.quantization)
            except ValueError as e:
                raise ValueError(
                    f"quantization={self.quantization!r} invalid: {e}"
                ) from None
        if not self.decode_batch_buckets:
            b = [2**i for i in range(0, max(1, self.max_num_seqs).bit_length())
                 if 2**i <= self.max_num_seqs] or [1]
            if b[-1] < self.max_num_seqs:  # non-power-of-two max must be covered
                b.append(self.max_num_seqs)
            self.decode_batch_buckets = tuple(b)
        if not self.prefill_buckets:
            lo = self.block_size.bit_length()
            hi = self.max_num_batched_tokens.bit_length()
            b = [2**i for i in range(lo - 1, hi) if 2**i <= self.max_num_batched_tokens]
            b = [x for x in b if x >= self.block_size] or [self.block_size]
            if b[-1] < self.max_num_batched_tokens:
                b.append(self.max_num_batched_tokens)
            self.prefill_buckets = tuple(b)
        if not self.ragged_token_buckets:
            cap = max(8, self.max_num_batched_tokens)
            b = [2**i for i in range(3, cap.bit_length()) if 2**i <= cap]
            if b[-1] < cap:  # non-power-of-two budget must be covered
                b.append(cap)
            self.ragged_token_buckets = tuple(b)

    @property
    def max_blocks_per_seq(self) -> int:
        return math.ceil(self.max_model_len / self.block_size)

    def bucket_tokens(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def bucket_batch(self, n: int) -> int:
        for b in self.decode_batch_buckets:
            if n <= b:
                return b
        return self.decode_batch_buckets[-1]

    def bucket_ragged_tokens(self, n: int) -> int:
        """Packed-token bucket for a ragged step of ``n`` real tokens."""
        for b in self.ragged_token_buckets:
            if n <= b:
                return b
        return self.ragged_token_buckets[-1]

    def ragged_rows(self, t_bucket: int) -> int:
        """Row count of the ragged step's metadata arrays — derived
        STATICALLY from the token bucket (each row holds ≥ 1 token), so the
        compiled signature is keyed by T alone."""
        return max(1, min(self.max_num_seqs, t_bucket))

    def bucket_table_width(self, max_kv_len: int) -> int:
        """Block-table width bucket (powers of two) for a batch's longest kv."""
        need = math.ceil(max(1, max_kv_len) / self.block_size)
        w = 1
        while w < need:
            w *= 2
        return min(w, self.max_blocks_per_seq) if self.max_blocks_per_seq >= need else need

    def replace(self, **kw) -> "EngineArgs":
        return dataclasses.replace(self, **kw)
