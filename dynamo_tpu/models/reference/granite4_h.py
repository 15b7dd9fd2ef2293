"""Granite 4.0-H (``granitemoehybrid``: Granite-4.0-H-Small and its
siblings): the forward pass in plain ``jax.numpy`` and float32, one whole
sequence at a time. No kernel, no chunking, no cache, no batching, nothing
imported from the system it is held against. The recurrence is a plain
``lax.scan`` over tokens.

It follows the published ``config.json`` keys (``hp`` below is that
dictionary, as ``chipbench/configs/granite4-h-small-ep2.json`` holds it).
``u`` is a layer's normed input; every norm is RMSNorm with ``rms_norm_eps``:

- ``h = embedding_multiplier · E[token]``; each layer
  ``h ← h + residual_multiplier · Mixer(RMSNorm(h))`` then
  ``h ← h + residual_multiplier · (Experts(RMSNorm(h)) + Shared(RMSNorm(h)))``;
  logits ``RMSNorm(h) Eᵀ / logits_scaling`` (``tie_word_embeddings``);
- ``layer_types[i] == "mamba"``: a Mamba-2 mixer, H = ``mamba_n_heads``
  heads of P = ``mamba_d_head``, N = ``mamba_d_state``, one B/C group.
  ``[z | xBC | dt] = u W_in`` (H·P | H·P + 2N | H);
  ``xBC_t ← silu(b_conv + Σ_j w_conv[j] ⊙ xBC_{t−(d_conv−1)+j})``, causal and
  depthwise, zeros before the sequence's first token
  (``mamba_conv_bias``); split x (H × P), B, C (N each);
  ``Δ_t = softplus(dt_t + dt_bias)``, ``a_t = exp(Δ_t · A)``,
  ``A = −exp(A_log)`` a head; ``S_t = a_t S_{t−1} + Δ_t · x_t ⊗ B_t``
  (S is H × P × N), ``y_t = S_t C_t + D ⊙ x_t``;
  ``y ← RMSNorm(y ⊙ silu(z)) ⊙ w`` over all H·P (the gate first, then the
  norm), ``out = y W_out``; no bias on the projections
  (``mamba_proj_bias`` false);
- ``layer_types[i] == "attention"``: ``num_attention_heads`` query heads,
  ``num_key_value_heads`` KV heads, causal, full; NO rotary and no other
  position term (``position_embedding_type: "nope"``); scores ×
  ``attention_multiplier``; no biases;
- experts: ``r = u W_r`` over ``num_local_experts_published`` experts; the
  ``num_experts_per_tok`` largest logits; gates = softmax over THOSE;
  expert e is ``(silu(u W_g,e) ⊙ (u W_u,e)) W_d,e``; the shared expert the
  same at width ``shared_intermediate_size``, ungated, added.

Departures, each because the published config does not say:
- no clamp on Δ (``time_step_limit`` is not a key of the config);
- ``mamba_chunk_size`` is taken as a tiling: it changes no equation here;
- the state S is float32 like everything else here (the system keeps it in
  float32 between steps too: assumed, what engines recommend for this
  family's accuracy).

The share of one chip (model-configs guide, section 4): ``hp["experts_held"]
= [first, count]`` says which experts' weights ``layers[i]`` holds; the
router still scores all of them, the terms of absent experts are left out,
and the shared expert is counted once. The vocabulary slice is simply a
smaller vocabulary. ``expert_ids`` tells the layer which experts each token
uses (the system's own choices, so that a choice lost to rounding behind a
small gap does not compare two different functions); the gates of those
experts still come from this file's logits.

``weights``: ``{"embed" [V, D], "layers": [per layer: attn_norm, then
in_proj [D, 2·H·P + 2N + H], conv_w [d_conv, H·P + 2N], conv_b, dt_bias [H],
A_log [H], D [H], ssm_norm [H·P], out_proj [H·P, D] or wq, wk, wv, wo (x @ W
orientation); mlp_norm, router [D, E], w_gate/w_up [Eh, D, F], w_down
[Eh, F, D], ws_gate/ws_up [D, Fs], ws_down [Fs, D]], "final_norm" [D]}``,
any dtype: each layer is widened to float32 as it is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries scored at a time (keys all at once)
QUERY_BLOCK = 256


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def mamba2(u, lp, hp, leave_out=(), state_dtype=jnp.float32):
    """The mixer over one whole sequence u [S, D] → (out [S, D], the state
    after the last token [H, P, N], the convolution's last inputs
    [d_conv − 1, H·P + 2N])."""
    S = u.shape[0]
    H, P, N = hp["mamba_n_heads"], hp["mamba_d_head"], hp["mamba_d_state"]
    W, di = hp["mamba_d_conv"], H * P
    zxd = u @ _f32(lp["in_proj"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * N], zxd[:, 2 * di + 2 * N:]
    padded = jnp.pad(xbc, ((W - 1, 0), (0, 0)))   # zeros before the sequence
    w = _f32(lp["conv_w"])
    conv = sum(w[j][None, :] * padded[j:j + S] for j in range(W))
    if "conv_bias" not in leave_out:
        conv = conv + _f32(lp["conv_b"])[None, :]
    conv = jax.nn.silu(conv)
    x = conv[:, :di].reshape(S, H, P)
    B, C = conv[:, di:di + N], conv[:, di + N:]
    if "dt_bias" not in leave_out:
        dt = dt + _f32(lp["dt_bias"])[None, :]
    delta = jax.nn.softplus(dt)                                # [S, H]
    a = jnp.exp(delta * -jnp.exp(_f32(lp["A_log"]))[None, :])

    def token(state, inp):
        a_t, dx_t, b_t, c_t = inp
        state = (a_t[:, None, None] * state.astype(jnp.float32)
                 + dx_t[:, :, None] * b_t[None, None, :]).astype(state_dtype)
        return state, jnp.einsum("hpn,n->hp", state.astype(jnp.float32), c_t)

    state, y = jax.lax.scan(token, jnp.zeros((H, P, N), state_dtype),
                            (a, x * delta[..., None], B, C))
    if "D" not in leave_out:
        y = y + _f32(lp["D"])[None, :, None] * x
    y = y.reshape(S, di)
    if "gate_before_norm" in leave_out:   # the norm first, then the gate
        y = rms_norm(y, lp["ssm_norm"], hp["rms_norm_eps"]) * jax.nn.silu(z)
    else:
        y = rms_norm(y * jax.nn.silu(z), lp["ssm_norm"], hp["rms_norm_eps"])
    return y @ _f32(lp["out_proj"]), state, padded[S:S + W - 1]


def _rotate_half(x, theta: float = 10000.0):
    """The negative test's handle: what a rotary embedding would do."""
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
    hd = _f32(lp["wq"]).shape[1] // H
    q = (u @ _f32(lp["wq"])).reshape(S, H, hd)
    k = (u @ _f32(lp["wk"])).reshape(S, KV, hd)
    v = (u @ _f32(lp["wv"])).reshape(S, KV, hd)
    if "nope" in leave_out:
        q, k = _rotate_half(q), _rotate_half(k)
    k = jnp.repeat(k, H // KV, axis=1)     # head h reads KV head h // G
    v = jnp.repeat(v, H // KV, axis=1)
    scale = (hd ** -0.5 if "attention_multiplier" in leave_out
             else hp["attention_multiplier"])
    pos = jnp.arange(S)
    blocks = -(-S // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - S
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, H, hd)
    ip = jnp.pad(pos, (0, pad)).reshape(blocks, QUERY_BLOCK)

    def block(qb_i):
        qb, i = qb_i
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        sc = jnp.where((pos[None, :] <= i[:, None])[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    out = (block((qp[0], ip[0])) if blocks == 1
           else jax.lax.map(block, (qp, ip))).reshape(-1, H * hd)[:S]
    return out @ _f32(lp["wo"])


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def experts(u, lp, hp, expert_ids=None, leave_out=()):
    """(routed + shared [S, D], the ids used [S, K], the logits [S, E])."""
    K = hp["num_experts_per_tok"]
    first, count = hp["experts_held"]
    logits = u @ _f32(lp["router"])                            # [S, E]
    ids = (jax.lax.top_k(logits, K)[1] if expert_ids is None
           else jnp.asarray(expert_ids))
    gates = jax.nn.softmax(jnp.take_along_axis(logits, ids, axis=1), -1)
    y = jnp.zeros_like(u)
    for e in range(count):  # the experts held here; the others add nothing
        ge = jnp.where(ids == first + e, gates, 0.0).sum(-1)   # [S]
        y = y + ge[:, None] * swiglu(u, lp["w_gate"][e], lp["w_up"][e],
                                     lp["w_down"][e])
    if "shared_expert" not in leave_out:
        y = y + swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y, ids, logits


def forward(weights, hp, tokens, *, expert_ids=None, rows=None,
            leave_out=(), state_dtype=jnp.float32):
    """Logits [S, V] (or [len(rows), V] at positions ``rows``) of one
    sequence, float32, and what the routers and the mixers did:
    ``{"ids": [per layer [S, K]], "choice": [per layer [S, E]], "ssm": [per
    Mamba layer [H, P, N]], "conv": [per Mamba layer [d_conv − 1, C]]}`` —
    the states after the last token.

    ``expert_ids``: per layer the [S, K] ids to use, or None for the layer's
    own top-k. ``leave_out`` names parts of the mathematics to drop or, for
    ``nope``, to add rotary (``embedding_multiplier``,
    ``residual_multiplier``, ``logits_scaling``, ``attention_multiplier``,
    ``D``, ``dt_bias``, ``conv_bias``, ``gate_before_norm``,
    ``shared_expert``, ``nope``): the negative tests' handle, never the
    system's. ``state_dtype``: the dtype S is rounded to after every token
    (the bf16-state control's handle).
    """
    eps = hp["rms_norm_eps"]
    rm = 1.0 if "residual_multiplier" in leave_out \
        else hp["residual_multiplier"]
    did = {"ids": [], "choice": [], "ssm": [], "conv": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"])[jnp.asarray(tokens)]
        if "embedding_multiplier" not in leave_out:
            x = x * hp["embedding_multiplier"]
        for i, lp in enumerate(weights["layers"]):
            u = rms_norm(x, lp["attn_norm"], eps)
            if hp["layer_types"][i] == "mamba":
                out, ssm, conv = mamba2(u, lp, hp, leave_out, state_dtype)
                did["ssm"].append(ssm)
                did["conv"].append(conv)
            else:
                out = attention(u, lp, hp, leave_out)
            x = x + rm * out
            y, ids, logits = experts(
                rms_norm(x, lp["mlp_norm"], eps), lp, hp,
                None if expert_ids is None else expert_ids[i], leave_out)
            did["ids"].append(ids)
            did["choice"].append(logits)
            x = x + rm * y
        x = rms_norm(x, weights["final_norm"], eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        logits = x @ _f32(weights["embed"]).T
        if "logits_scaling" not in leave_out:
            logits = logits / hp["logits_scaling"]
        return logits, did
