"""MiMo-V2 (MiMo-V2-Flash, MiMo-V2.5 language model): the forward pass in
plain ``jax.numpy`` and float32, one whole sequence at a time. No kernel, no
cache, no batching, nothing imported from the system it is held against.

It follows the published ``config.json`` keys (``hp`` below is that
dictionary, as ``chipbench/configs/mimo-v25-ep16.json`` holds it):

- pre-norm residual blocks, RMSNorm with ``layernorm_epsilon``, untied head;
- ``hybrid_layer_pattern``: 0 = full attention (``num_key_value_heads``,
  ``rope_theta``, ``add_full_attention_sink_bias``), 1 = window attention
  over keys j with i − ``sliding_window`` < j ≤ i
  (``swa_num_key_value_heads``, ``swa_rope_theta``,
  ``add_swa_attention_sink_bias``);
- q, k ``head_dim`` wide, v ``v_head_dim`` wide; RoPE on the leading
  int(``head_dim`` · ``partial_rotary_factor``) dims (rounded down to even);
  scores / √``head_dim``; a learned per-head sink logit joins the softmax
  where the kind has one, and its column is dropped; out = P·(
  ``attention_value_scale`` · v);
- ``moe_layer_freq``: 0 = SwiGLU of ``intermediate_size``, 1 = experts:
  σ = sigmoid(x·Wg) in float32 over ``n_routed_experts_published`` experts,
  chosen = top-``num_experts_per_tok`` of σ + e_score_correction_bias,
  w = σ[chosen] / Σσ[chosen] (``norm_topk_prob``), times
  ``routed_scaling_factor`` (null = 1); no shared expert.

Departures, each because the published config does not say:
- RoPE pairs dim i with dim i + rot/2 inside the rotated dims (rotate-half,
  the convention of the family's public code);
- ``attention_chunk_size`` and ``attention_projection_layout`` are taken as
  a kernel tiling and a weight layout: they change no equation here.

The share of one chip (model-configs guide, section 4): ``hp["experts_held"]
= [first, count]`` says which experts' weights ``layers[i]`` holds; the
router still scores all of them, and what the absent experts would add is
left out. The vocabulary slice is simply a smaller vocabulary. ``expert_ids``
tells the layer which experts each token uses (the system's own choices, so
that a choice lost to rounding behind a small gap does not compare two
different functions); the weights of those experts still come from this
file's σ.

``weights``: ``{"embed" [V, D], "layers": [per layer: attn_norm, wq, wk, wv,
wo, (sink [H]), mlp_norm, then w_gate/w_up/w_down [D, F] / [F, D] or router
[D, E], router_bias [E], w_gate/w_up [Eh, D, F], w_down [Eh, F, D]],
"final_norm" [D], "lm_head" [D, V]}``, any dtype: each layer is widened to
float32 as it is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: queries scored at a time (keys all at once): [H, 256, S] float32
QUERY_BLOCK = 256


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def rope(x, positions, theta: float, rot: int):
    """x [S, N, hd]: rotate-half on the leading ``rot`` dims."""
    half = rot // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = _f32(positions)[:, None] * _f32(inv)[None, :]       # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def attention(x, lp, hp, window: int, kv_heads: int, theta: float,
              leave_out=()):
    S = x.shape[0]
    H, hd, vd = hp["num_attention_heads"], hp["head_dim"], hp["v_head_dim"]
    rot = int(hd * hp["partial_rotary_factor"]) // 2 * 2
    pos = jnp.arange(S)
    q = rope((x @ _f32(lp["wq"])).reshape(S, H, hd), pos, theta, rot)
    k = rope((x @ _f32(lp["wk"])).reshape(S, kv_heads, hd), pos, theta, rot)
    v = (x @ _f32(lp["wv"])).reshape(S, kv_heads, vd)
    if "value_scale" not in leave_out:
        v = v * hp["attention_value_scale"]
    G = H // kv_heads
    k = jnp.repeat(k, G, axis=1)           # head h reads KV head h // G
    v = jnp.repeat(v, G, axis=1)
    has_sink = hp["add_swa_attention_sink_bias" if window
                  else "add_full_attention_sink_bias"]
    sink = lp["sink"] if has_sink and "sink" not in leave_out else None
    # one block of queries after another (lax.map is sequential, so one
    # block's scores are alive at a time), the tail padded with queries
    # whose answers are cut off again
    blocks = -(-S // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - S
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, H, hd)
    ip = jnp.pad(pos, (0, pad)).reshape(blocks, QUERY_BLOCK)

    def block(qb_i):
        qb, i = qb_i
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        i, j = i[:, None], pos[None, :]
        seen = j <= i
        if window:
            seen = seen & (j > i - window)
        sc = jnp.where(seen[None], sc, -jnp.inf)
        if sink is not None:
            # the sink is one more column of the softmax, then dropped
            col = jnp.broadcast_to(_f32(sink)[:, None, None],
                                   (H, QUERY_BLOCK, 1))
            p = jax.nn.softmax(jnp.concatenate([sc, col], -1), -1)[..., :-1]
        else:
            p = jax.nn.softmax(sc, -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = (block((qp[0], ip[0])) if blocks == 1
           else jax.lax.map(block, (qp, ip))).reshape(-1, H * vd)[:S]
    return out @ _f32(lp["wo"])


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def experts(x, lp, hp, expert_ids=None, leave_out=()):
    """(y [S, D], the ids used [S, K], the choice scores σ + bias [S, E])."""
    K = hp["num_experts_per_tok"]
    first, count = hp["experts_held"]
    sig = jax.nn.sigmoid(x @ _f32(lp["router"]))              # [S, E]
    choice = sig if "correction_bias" in leave_out \
        else sig + _f32(lp["router_bias"])[None, :]
    ids = (jax.lax.top_k(choice, K)[1] if expert_ids is None
           else jnp.asarray(expert_ids))
    w = jnp.take_along_axis(sig, ids, axis=1)
    if hp["norm_topk_prob"] and "normalisation" not in leave_out:
        w = w / w.sum(-1, keepdims=True)
    w = w * (hp.get("routed_scaling_factor") or 1.0)
    y = jnp.zeros_like(x)
    for e in range(count):  # the experts held here; the others add nothing
        we = jnp.where(ids == first + e, w, 0.0).sum(-1)      # [S]
        y = y + we[:, None] * swiglu(x, lp["w_gate"][e], lp["w_up"][e],
                                     lp["w_down"][e])
    return y, ids, choice


def forward(weights, hp, tokens, *, expert_ids=None, rows=None,
            leave_out=()):
    """Logits [S, V] (or [len(rows), V] at positions ``rows``) of one
    sequence, float32, and what the routers did: ``{"ids": [per expert
    layer [S, K]], "choice": [per expert layer [S, E]]}``.

    ``expert_ids``: per expert layer the [S, K] ids to use, or None for the
    layer's own top-k. ``leave_out`` names parts of the mathematics to drop
    (``sink``, ``value_scale``, ``rope_base``, ``correction_bias``,
    ``normalisation``): the negative tests' handle, never the system's.
    """
    eps = hp["layernorm_epsilon"]
    routed = {"ids": [], "choice": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"])[jnp.asarray(tokens)]
        moe_i = 0
        for i, lp in enumerate(weights["layers"]):
            if hp["hybrid_layer_pattern"][i]:
                kind = (hp["sliding_window"], hp["swa_num_key_value_heads"],
                        hp["swa_rope_theta"])
            else:
                kind = (0, hp["num_key_value_heads"], hp["rope_theta"])
            if "rope_base" in leave_out:
                kind = kind[:2] + (hp["rope_theta"],)
            x = x + attention(rms_norm(x, lp["attn_norm"], eps), lp, hp,
                              *kind, leave_out=leave_out)
            h = rms_norm(x, lp["mlp_norm"], eps)
            if hp["moe_layer_freq"][i]:
                y, ids, choice = experts(
                    h, lp, hp, None if expert_ids is None
                    else expert_ids[moe_i], leave_out)
                routed["ids"].append(ids)
                routed["choice"].append(choice)
                moe_i += 1
                x = x + y
            else:
                x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = rms_norm(x, weights["final_norm"], eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return x @ _f32(weights["lm_head"]), routed
