"""LFM2-MoE (``lfm2_moe``: LFM2-8B-A1B, LFM2-24B-A2B): the forward pass in
plain ``jax.numpy`` and float32, one whole sequence at a time. No kernel,
no chunking, no cache, no batching, nothing imported from the system it is
held against.

It follows the published ``config.json`` keys (``hp`` below is that
dictionary, as ``chipbench/configs/lfm2-24b-a2b-pp4.json`` holds it). ``n``
is RMSNorm, ``weight · x / rms(x)`` with ``norm_eps``; the published module
names stand in brackets:

- ``h = E[token]``; each layer ``h ← h + Mixer(n(h))`` [operator_norm] then
  ``h ← h + FFN(n(h))`` [ffn_norm]; after the last layer ``h ← n(h)``
  [embedding_norm]; logits ``h Eᵀ`` (the head is the embedding);
- ``layer_types[i] == "conv"``: the gated short convolution [conv].
  ``[B | C | x] = u W_in`` (thirds of 3 · hidden, in that order; no bias);
  ``z = B ⊙ x``; ``c_t = Σ_{j<L} w[j] ⊙ z_{t−(L−1)+j}`` with
  L = ``conv_L_cache`` taps, depthwise and causal, ``z`` zero before the
  sequence's first token, no bias (``conv_bias`` false) and NO activation;
  ``out = (C ⊙ c) W_out``. What a sequence carries from token to token is
  ``z_{t−1} … z_{t−L+1}`` and nothing else;
- any other ``layer_types[i]``: attention [self_attn]. ``num_attention_heads``
  query and ``num_key_value_heads`` KV heads of ``hidden_size /
  num_attention_heads`` dims, no biases; q and k normed per head over those
  dims [q_layernorm, k_layernorm: one weight each, shared by the heads];
  rotate-half RoPE over all of them at ``rope_theta``; scale ``dims^−½``;
  causal, full; ``out_proj``;
- FFN, layers below ``num_dense_layers``: ``w2(silu(w1 u) ⊙ w3 u)`` at
  ``intermediate_size``. Other layers: ``s = sigmoid(u W_gate)`` over
  ``num_experts``; the ``num_experts_per_tok`` experts are the largest of
  ``s + expert_bias`` (``use_expert_bias``: the bias steers the choice only);
  their weights are ``s`` there, divided by ``(their sum + 1e-6)``
  (``norm_topk_prob``), times ``routed_scaling_factor``; each expert the same
  SwiGLU at ``moe_intermediate_size``; no shared expert.

Departures, each because the published config does not say:
- ``tie_word_embeddings`` is taken as true (the family's convention);
- ``head_dim`` is ``hidden_size / num_attention_heads`` (no such key);
- ``intermediate_size`` is used as it stands (no rounding to a multiple);
- the ``1e-6`` in the gate's normaliser is the ``lfm2_moe`` code's constant,
  not a key.

The share of one chip (model-configs guide, section 4) is a pipeline
stage: fewer layers, nothing inside a layer cut. ``hp["experts_held"] =
[first, count]`` still says which experts' weights ``layers[i]`` holds (all
of them in the configuration above); the terms of absent experts are left
out. ``expert_ids`` tells a layer which experts each token uses (the
system's own choices, so that a choice lost to rounding behind a small gap
does not compare two different functions); the gates of those experts still
come from this file's scores.

``weights``: ``{"embed" [V, D], "layers": [per layer: attn_norm
(operator_norm), then in_proj [D, 3D], conv_w [L, D], out_proj [D, D] or
wq, wk, wv, wo (x @ W orientation), q_norm, k_norm; mlp_norm (ffn_norm),
then w_gate (w1), w_up (w3) [D, F], w_down (w2) [F, D] or router [D, E],
router_bias (expert_bias) [E], w_gate/w_up [Eh, D, Fm], w_down [Eh, Fm,
D]], "final_norm" (embedding_norm) [D]}``, any dtype: each layer is widened
to float32 as it is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries scored at a time (keys all at once)
QUERY_BLOCK = 256
#: added to the sum of the chosen gates before they are divided by it
GATE_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def short_conv(u, lp, hp, leave_out=()):
    """The mixer over one whole sequence u [S, D] → (out [S, D], the last
    ``conv_L_cache − 1`` inputs of the convolution [L − 1, D])."""
    S, D = u.shape
    L = hp["conv_L_cache"]
    bcx = u @ _f32(lp["in_proj"])
    B, C, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    if "thirds_order" in leave_out:        # as if the thirds were x | B | C
        x, B, C = B, C, x
    z = x if "in_gate" in leave_out else B * x
    padded = jnp.pad(z, ((L - 1, 0), (0, 0)))   # zeros before the sequence
    w = _f32(lp["conv_w"])
    if "oldest_tap" in leave_out:          # a tail that forgets z_{t-L+1}
        w = w.at[0].set(0.0)
    c = sum(w[j][None, :] * padded[j:j + S] for j in range(L))
    if "conv_activation" in leave_out:     # Mamba's convolution has one
        c = jax.nn.silu(c)
    y = c if "out_gate" in leave_out else C * c
    return y @ _f32(lp["out_proj"]), padded[S:S + L - 1]


def rotate_half(x, theta: float):
    """[S, heads, dims]: every head turned by its token's position."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, lp, hp, leave_out=()):
    S = u.shape[0]
    H, KV = hp["num_attention_heads"], hp["num_key_value_heads"]
    hd = hp["hidden_size"] // H
    theta = float(hp["rope_parameters"]["rope_theta"])
    q = (u @ _f32(lp["wq"])).reshape(S, H, hd)
    k = (u @ _f32(lp["wk"])).reshape(S, KV, hd)
    v = (u @ _f32(lp["wv"])).reshape(S, KV, hd)
    if "qk_norm" not in leave_out:
        q = rms_norm(q, lp["q_norm"], hp["norm_eps"])
        k = rms_norm(k, lp["k_norm"], hp["norm_eps"])
    if "rope" not in leave_out:
        q, k = rotate_half(q, theta), rotate_half(k, theta)
    k = jnp.repeat(k, H // KV, axis=1)     # head h reads KV head h // G
    v = jnp.repeat(v, H // KV, axis=1)
    pos = jnp.arange(S)
    blocks = -(-S // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - S
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, H, hd)
    ip = jnp.pad(pos, (0, pad)).reshape(blocks, QUERY_BLOCK)

    def block(qb_i):
        qb, i = qb_i
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        sc = jnp.where((pos[None, :] <= i[:, None])[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    out = (block((qp[0], ip[0])) if blocks == 1
           else jax.lax.map(block, (qp, ip))).reshape(-1, H * hd)[:S]
    return out @ _f32(lp["wo"])


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def experts(u, lp, hp, expert_ids=None, leave_out=()):
    """(routed [S, D], the ids used [S, K], the choice scores [S, E])."""
    K = hp["num_experts_per_tok"]
    first, count = hp["experts_held"]
    s = jax.nn.sigmoid(u @ _f32(lp["router"]))                 # [S, E]
    choice = s if "expert_bias" in leave_out \
        else s + _f32(lp["router_bias"])[None, :]
    ids = (jax.lax.top_k(choice, K)[1] if expert_ids is None
           else jnp.asarray(expert_ids))
    gates = jnp.take_along_axis(s, ids, axis=1)   # the scores, not s + bias
    if hp["norm_topk_prob"] and "norm_topk_prob" not in leave_out:
        gates = gates / (gates.sum(-1, keepdims=True) + GATE_EPS)
    gates = gates * hp["routed_scaling_factor"]
    y = jnp.zeros_like(u)
    for e in range(count):  # the experts held here; the others add nothing
        ge = jnp.where(ids == first + e, gates, 0.0).sum(-1)   # [S]
        y = y + ge[:, None] * swiglu(u, lp["w_gate"][e], lp["w_up"][e],
                                     lp["w_down"][e])
    return y, ids, choice


def forward(weights, hp, tokens, *, expert_ids=None, rows=None,
            leave_out=()):
    """Logits [S, V] (or [len(rows), V] at positions ``rows``) of one
    sequence, float32, and what the routers and the mixers did: ``{"ids":
    [per expert layer [S, K]], "choice": [per expert layer [S, E]], "conv":
    [per conv layer [L − 1, D]]}`` — the convolutions' inputs after the
    last token.

    ``expert_ids``: per EXPERT layer the [S, K] ids to use, or None for the
    layer's own top-k. ``leave_out`` names parts of the mathematics to drop
    or swap (``in_gate``, ``out_gate``, ``oldest_tap``, ``thirds_order``,
    ``conv_activation``, ``qk_norm``, ``rope``, ``expert_bias``,
    ``norm_topk_prob``, ``embedding_norm``): the negative tests' handle,
    never the system's.
    """
    eps = hp["norm_eps"]
    did = {"ids": [], "choice": [], "conv": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"])[jnp.asarray(tokens)]
        for i, lp in enumerate(weights["layers"]):
            u = rms_norm(x, lp["attn_norm"], eps)
            if hp["layer_types"][i] == "conv":
                out, tail = short_conv(u, lp, hp, leave_out)
                did["conv"].append(tail)
            else:
                out = attention(u, lp, hp, leave_out)
            x = x + out
            u = rms_norm(x, lp["mlp_norm"], eps)
            if i < hp["num_dense_layers"]:
                x = x + swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"])
                continue
            j = len(did["ids"])
            y, ids, choice = experts(
                u, lp, hp, None if expert_ids is None else expert_ids[j],
                leave_out)
            did["ids"].append(ids)
            did["choice"].append(choice)
            x = x + y
        if "embedding_norm" not in leave_out:
            x = rms_norm(x, weights["final_norm"], eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return x @ _f32(weights["embed"]).T, did
