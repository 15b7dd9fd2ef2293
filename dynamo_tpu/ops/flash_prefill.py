"""Pallas TPU flash-attention kernel for chunked prefill over paged KV.

The round-1 XLA prefill path materialized an O(S·T) f32 score tensor through
HBM — at the BASELINE workload (ISL 8192: S=2048 chunk, T=8192 kv) that is
~2 GB per layer and blows both memory and TTFT. This kernel computes the
same attention with an online softmax so scores never leave VMEM.

Design (TPU-first, not a CUDA translation):
- The paged gather K/V [B,T,KV,hd] is left to XLA — at bf16 it is ~2·T·KV·hd
  bytes (tens of MB), a fused dynamic-gather XLA does well; the quadratic
  score tensor was the problem, not the gather.
- Grid (B, KV, S/TQ, T/TK), innermost axis = k-tiles. Online-softmax state
  (m, l, acc) lives in VMEM scratch which persists across grid steps on
  TPU; it is initialized at tk==0 and the output tile written at the last
  k-tile. Query tiles are processed per KV-head group so the MXU matmul is
  [G·TQ, hd] × [hd, TK] with zero wasted FLOPs (contrast: the decode
  kernel's block-expanded q, fine there because decode is DMA-bound).
- Causality is pure index math: chunked-prefill rows are consecutive
  positions (engine/_run_prefill), so q_pos = pos_base[b] + tq·TQ + row,
  key_pos = tk·TK + col; tiles entirely in the future are skipped.
- Sliding-window masking (mistral) supported via the same index math.

Contract (matches engine/model._paged_attention for one layer):
  q        [B, S, H, hd]
  k, v     [B, T, KV, hd]   (gathered pages, logically ordered)
  pos_base [B] int32        (absolute position of each row's first token)
  kv_lens  [B] int32        (valid kv length incl. the current chunk)
  → out    [B, S, H, hd]

ref parity: this stands in for the engine-side fused prefill attention the
reference delegates to vLLM (components/backends/vllm); SURVEY §7 names it
a "hard part" of the TPU build.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops.kv_pages import gather_pages
from dynamo_tpu.ops.paged_attention import kernel_interpret_mode

_NEG = -1e30


def _prefill_kernel(pos_base_ref, kv_lens_ref, window_ref,  # scalar prefetch
                    q_ref,  # [1, 1, G, TQ, hd] VMEM
                    sink_ref,  # [1, 1, G, 1] VMEM (zeros when has_sink=False)
                    k_ref, v_ref,  # [1, 1, TK, hd] VMEM
                    o_ref,  # [1, 1, G, TQ, hd] VMEM
                    m_sc, l_sc, acc_sc,  # [G·TQ, 1], [G·TQ, 1], [G·TQ, hd]
                    *, scale: float, has_sink: bool):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    tq = pl.program_id(2)
    tk = pl.program_id(3)
    n_tk = pl.num_programs(3)

    # hd (score width, = k width) and hdv (value/output width) may differ:
    # MLA attends in latent space where K carries the rope tail V lacks
    G, TQ, hd = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    hdv = v_ref.shape[3]
    TK = k_ref.shape[2]
    kv_len = kv_lens_ref[b]
    pos0 = pos_base_ref[b]
    # sliding window as a traced scalar: static for mistral, a per-layer
    # value for gpt-oss; 0 = full attention
    win = window_ref[0]

    @pl.when(tk == 0)
    def _init():
        if has_sink:
            # seed the online softmax with the sink slot (zero value):
            # row r of the [G·TQ] flattening belongs to head g = r // TQ
            s = sink_ref[0, 0].astype(jnp.float32)  # [G, 1]
            m_sc[...] = jnp.repeat(s, TQ, axis=0)
            l_sc[...] = jnp.ones_like(l_sc)
        else:
            m_sc[...] = jnp.full_like(m_sc, _NEG)
            l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    k_start = tk * TK
    q_hi = pos0 + tq * TQ + TQ - 1  # highest query position in this tile
    # tile is live unless entirely in the future, past kv_len, or (window)
    # entirely before every query's window
    live = (k_start <= q_hi) & (k_start < kv_len)
    q_lo = pos0 + tq * TQ
    live = live & ((win <= 0) | (k_start + TK - 1 > q_lo - win))

    # f32 inputs (CPU parity tests) need full-precision MXU passes; bf16
    # serving inputs take the native single-pass MXU path
    prec = (jax.lax.Precision.HIGHEST
            if q_ref.dtype == jnp.float32 else None)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].reshape(G * TQ, hd)
        k = k_ref[0, 0]  # [TK, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * scale  # [G·TQ, TK]

        rows = jax.lax.broadcasted_iota(jnp.int32, (G * TQ, TK), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (G * TQ, TK), 1)
        q_pos = pos0 + tq * TQ + jax.lax.rem(rows, TQ)
        key_pos = k_start + cols
        mask = (key_pos <= q_pos) & (key_pos < kv_len)
        mask = mask & ((win <= 0) | (key_pos > q_pos - win))
        s = jnp.where(mask, s, _NEG)

        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [G·TQ, TK]
        l_sc[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)  # [G·TQ, hd]
        acc_sc[...] = acc_sc[...] * corr + pv

    @pl.when(tk == n_tk - 1)
    def _finalize():
        out = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = out.reshape(G, TQ, hdv).astype(o_ref.dtype)


def flash_prefill(q, k, v, pos_base, kv_lens, *, sliding_window=None,
                  sinks=None, scale=None, interpret: bool = False):
    """Flash attention for a prefill chunk. See module docstring.

    ``sliding_window`` may be a traced scalar (per-layer gpt-oss windows);
    ``sinks`` [H] are optional attention-sink logits seeded into the online
    softmax with zero value contribution. ``v``'s trailing dim (= output
    width) may differ from q/k's (MLA latent attention); ``scale`` defaults
    to 1/√hd."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    hdv = v.shape[3]
    G = H // KV

    TQ = min(S, max(1, 512 // max(G, 1)))
    while S % TQ:
        TQ //= 2
    TK = min(T, 512)
    while T % TK:
        TK //= 2

    interpret = interpret or kernel_interpret_mode()

    # group-major views: q5 [B,KV,G,S,hd], k4/v4 [B,KV,T,hd]
    q5 = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
    k4 = k.transpose(0, 2, 1, 3)
    v4 = v.transpose(0, 2, 1, 3)

    has_sink = sinks is not None
    win_arr = jnp.asarray(
        [0 if sliding_window is None else sliding_window],
        jnp.int32).reshape(1)
    sink_in = (jnp.zeros((1, KV, G, 1), q.dtype) if not has_sink
               else sinks.reshape(1, KV, G, 1).astype(q.dtype))
    kernel = functools.partial(
        _prefill_kernel,
        scale=float(scale if scale is not None else 1.0 / np.sqrt(hd)),
        has_sink=has_sink)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, S // TQ, T // TK),
        in_specs=[
            pl.BlockSpec((1, 1, G, TQ, hd), lambda b, kk, tq, tk, *_: (b, kk, 0, tq, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, kk, tq, tk, *_: (0, kk, 0, 0)),
            pl.BlockSpec((1, 1, TK, hd), lambda b, kk, tq, tk, *_: (b, kk, tk, 0)),
            pl.BlockSpec((1, 1, TK, hdv), lambda b, kk, tq, tk, *_: (b, kk, tk, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, TQ, hdv), lambda b, kk, tq, tk, *_: (b, kk, 0, tq, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * TQ, 1), jnp.float32),
            pltpu.VMEM((G * TQ, 1), jnp.float32),
            pltpu.VMEM((G * TQ, hdv), jnp.float32),
        ],
    )
    out5 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, S, hdv), q.dtype),
        interpret=interpret,
    )(pos_base.astype(jnp.int32), kv_lens.astype(jnp.int32), win_arr,
      q5, sink_in, k4, v4)

    # [B,KV,G,S,hdv] → [B,S,H,hdv]
    return out5.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hdv)


def flash_mla_prefill(q_eff, q_rot, c, k_rot, pos_base, kv_lens, *,
                      scale: float, interpret: bool = False):
    """Flash prefill over the compressed MLA latent cache — scores in
    latent space, O(S·T) never leaves VMEM.

    MLA attention is exactly single-KV-head attention once absorbed: every
    query head shares the one latent stream, Q=[q_eff|q_rot] against
    K=[c|k_rot] (the rope tail rides only the scores), V=c (output stays in
    latent space; the caller expands through W_UV). The generalized flash
    kernel runs it with KV=1, G=H, hd=r+pr, hdv=r — killing the [B,H,S,T]
    HBM score tensor the XLA path materializes (r2 verdict #3; DeepSeek at
    ISL 8192 is the reference's wide-EP flagship workload,
    ref: recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml:61).

    Args: q_eff [B,S,H,r] (absorbed), q_rot [B,S,H,pr] (rope, padded like
    the cache), c [B,T,r], k_rot [B,T,pr]; → [B,S,H,r] latent output.
    """
    q_cat = jnp.concatenate([q_eff, q_rot], axis=-1)
    k_cat = jnp.concatenate([c, k_rot], axis=-1)[:, :, None, :]
    return flash_prefill(q_cat, k_cat, c[:, :, None, :], pos_base, kv_lens,
                         scale=scale, interpret=interpret)


def flash_prefill_paged(q, k_cache, v_cache, lidx, block_tables, positions,
                        kv_lens, *, block_size: int, sliding_window=None,
                        sinks=None, interpret: bool = False):
    """Gather pages at layer ``lidx`` (XLA fused gather), then flash-attend.

    Same signature family as engine/model._paged_attention; q [B,S,H,hd],
    caches [L, slots, KV, hd].
    """
    B = q.shape[0]
    W = block_tables.shape[1]
    slot_idx = (block_tables[:, :, None] * block_size
                + jnp.arange(block_size)[None, None, :]).reshape(B, W * block_size)
    # int8 caches dequantize in the gather (fused); the kernel then runs on
    # the q-dtype values exactly as with a plain cache
    k = gather_pages(k_cache, lidx, slot_idx).astype(q.dtype)  # [B,T,KV,hd]
    v = gather_pages(v_cache, lidx, slot_idx).astype(q.dtype)
    return flash_prefill(q, k, v, positions[:, 0], kv_lens,
                         sliding_window=sliding_window, sinks=sinks,
                         interpret=interpret)
