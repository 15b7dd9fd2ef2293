"""Pallas TPU paged-attention decode kernel.

One query token per sequence attends over its paged KV cache (the serving
hot loop). The XLA fallback in engine/model.py materializes the gathered
K/V [B, W·bs, KV, hd] through HBM; this kernel instead streams pages
HBM→VMEM with double-buffered async DMA and folds them into an online
softmax, so K/V traffic is read exactly once and never re-materialized.

Contract matches engine/model._paged_attention for S=1:
  q            [B, H, hd]
  k/v cache    [num_slots, KV, hd]   (flat paged layout, slot = block·bs+off)
  block_tables [B, W] int32          (0 = reserved null block)
  kv_lens      [B] int32             (valid kv length per sequence)
  → out        [B, H, hd]

TPU mapping: Mosaic requires DMA slices tile-aligned in the trailing dims
(lane = 128), which a [bs, KV, hd≤64] page view violates. So the kernel
works in the flattened [slots, KV·hd] view (KV·hd is a lane multiple for
real GQA models: 8·64=512): pages DMA as [bs, KV·hd]; scores come from one
MXU matmul of a block-expanded query Q̃ [H, KV·hd] (head h carries its q
only in its own KV segment, zeros elsewhere, so contraction over KV·hd
reduces to the correct per-group dot); PV accumulates in the [H, KV·hd]
domain and the correct segment per head is gathered outside the kernel.
The redundant-segment FLOPs are noise — decode attention is DMA-bound.

Falls back to the XLA path when shapes can't align (KV·hd % 128 ≠ 0).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

_NEG = -1e30
_LANE = 128


def kernel_interpret_mode() -> bool:
    """Pallas TPU kernels compile through Mosaic on the TPU backend and run
    in interpret mode everywhere else (the CPU tests). Every kernel wrapper
    asks here, so the engine can state — and check — which one it got."""
    return jax.default_backend() != "tpu"


def _decode_kernel(block_tables_ref, kv_lens_ref, window_ref,
                   sbase_ref,  # scalar pf; sbase = scale-table slot base
                   qexp_ref,  # [1, H, KVhd] VMEM
                   sink_ref,  # [1, H, 1] VMEM (zeros when has_sink=False)
                   kcache_ref, vcache_ref,  # [slots, KVhd] HBM
                   *rest,  # [ksc_ref, vsc_ref (HBM [slots, KV] | VMEM),]
                           # out_ref, kbuf, vbuf, [ksbuf, vsbuf,] dma_sem
                   bs: int, has_sink: bool, quant: bool,
                   vmem_scales: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant and vmem_scales:
        # scales ride as ordinary VMEM operands (constant block → fetched
        # once for the whole grid): 2 DMAs/page, same as the bf16 path.
        # The r4 chip measurement showed the 4-DMA variant at 1557 tok/s vs
        # 4528 bf16 — the two tiny (bs·KV·4 B) scale copies pay full DMA
        # grant latency each, tripling effective page-fetch cost.
        ksc_ref, vsc_ref, out_ref, kbuf, vbuf, dma_sem = rest
        ksbuf = vsbuf = None
    elif quant:
        (ksc_ref, vsc_ref, out_ref, kbuf, vbuf,
         ksbuf, vsbuf, dma_sem) = rest
    else:
        out_ref, kbuf, vbuf, dma_sem = rest
        ksc_ref = vsc_ref = ksbuf = vsbuf = None

    b = pl.program_id(0)
    kv_len = kv_lens_ref[b]
    num_pages = (kv_len + bs - 1) // bs
    # sliding window (gpt-oss/mistral): pages entirely outside the window
    # are never fetched — a 128-token window reads 1-2 pages regardless of
    # context length. window<=0 means full attention.
    win = window_ref[0]
    first_key = jnp.where(win > 0, jnp.maximum(kv_len - win, 0), 0)
    start_page = first_key // bs
    H = qexp_ref.shape[1]
    KVhd = qexp_ref.shape[2]

    D = kbuf.shape[0]  # pipeline depth: D page fetches always in flight

    def start_dma(w):
        blk = block_tables_ref[b, w]
        slot = w % D
        pltpu.make_async_copy(
            kcache_ref.at[pl.ds(blk * bs, bs)], kbuf.at[slot],
            dma_sem.at[slot, 0]).start()
        pltpu.make_async_copy(
            vcache_ref.at[pl.ds(blk * bs, bs)], vbuf.at[slot],
            dma_sem.at[slot, 1]).start()
        if quant and not vmem_scales:
            # per-(slot, head) scales ride their own small DMAs; offsets
            # rebase onto the scale table (callers may pass ONE layer's
            # slice of a stacked cache — see scale_slot_base)
            soff = blk * bs - sbase_ref[0]
            pltpu.make_async_copy(
                ksc_ref.at[pl.ds(soff, bs)], ksbuf.at[slot],
                dma_sem.at[slot, 2]).start()
            pltpu.make_async_copy(
                vsc_ref.at[pl.ds(soff, bs)], vsbuf.at[slot],
                dma_sem.at[slot, 3]).start()

    def wait_dma(w):
        slot = w % D
        pltpu.make_async_copy(kbuf.at[slot], kbuf.at[slot],
                              dma_sem.at[slot, 0]).wait()
        pltpu.make_async_copy(vbuf.at[slot], vbuf.at[slot],
                              dma_sem.at[slot, 1]).wait()
        if quant and not vmem_scales:
            pltpu.make_async_copy(ksbuf.at[slot], ksbuf.at[slot],
                                  dma_sem.at[slot, 2]).wait()
            pltpu.make_async_copy(vsbuf.at[slot], vsbuf.at[slot],
                                  dma_sem.at[slot, 3]).wait()

    # D-deep rotating pipeline — scattered pages are independent, so keeping
    # D fetches in flight hides per-DMA grant latency (a 2-deep double
    # buffer serializes W·B small copies on that latency).
    prefill_n = jnp.minimum(num_pages, start_page + D)
    jax.lax.fori_loop(start_page, prefill_n,
                      lambda w, c: (start_dma(w), c)[1], 0)

    qexp = qexp_ref[0].astype(jnp.float32)  # [H, KVhd], block-expanded

    if quant:
        # static head→segment one-hot [H, KV]: head h's scale per key t is
        # seg_oh @ spage.T — one tiny MXU matmul instead of lane-expanding
        # scales to the [bs, KVhd] domain
        KV = ksc_ref.shape[0] if vmem_scales else ksbuf.shape[2]
        G = H // KV
        rows = jax.lax.broadcasted_iota(jnp.int32, (H, KV), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (H, KV), 1)
        seg_oh = (cols == rows // G).astype(jnp.float32)

    def body(w, carry):
        m, l, acc = carry  # [H,1] f32, [H,1] f32, [H,KVhd] f32
        wait_dma(w)
        kpage = kbuf[w % D].astype(jnp.float32)  # [bs, KVhd]
        vpage = vbuf[w % D].astype(jnp.float32)
        if quant and vmem_scales:
            # resident layout is TRANSPOSED [KV, padded_slots] (slots on the
            # lane dim — a [slots, KV] block would tile-pad KV→128, 16-128×
            # the useful bytes; ADVICE r4)
            blk = block_tables_ref[b, w]
            soff = blk * bs - sbase_ref[0]  # rebase onto the scale slice
            kscpage = ksc_ref[:, pl.ds(soff, bs)]  # [KV, bs] VMEM slice
            vscpage = vsc_ref[:, pl.ds(soff, bs)]
            sc_dims = (((1,), (0,)), ((), ()))  # seg_oh[H,KV] @ [KV,bs]
        elif quant:
            kscpage = ksbuf[w % D]  # [bs, KV]
            vscpage = vsbuf[w % D]
            sc_dims = (((1,), (1,)), ((), ()))

        # scores: contraction over KVhd == per-group q·k (q̃ is segment-masked)
        s = jax.lax.dot_general(
            qexp, kpage, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, bs]
        if quant:
            # dequant scores in the [H, bs] domain: each head contracts only
            # its own segment, so its raw score scales by that segment's
            # per-key k-scale
            ksc = jax.lax.dot_general(
                seg_oh, kscpage, sc_dims,
                preferred_element_type=jnp.float32)  # [H, bs]
            s = s * ksc

        key_pos = w * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where((key_pos < kv_len) & (key_pos >= first_key), s, _NEG)

        chunk_max = jnp.max(s, axis=1, keepdims=True)
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)  # [H, bs]
        new_l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv_p = p
        if quant:
            # fold per-key v-scales into p (head h's own segment scaling;
            # other segments become garbage the caller discards anyway)
            vsc = jax.lax.dot_general(
                seg_oh, vscpage, sc_dims,
                preferred_element_type=jnp.float32)  # [H, bs]
            pv_p = p * vsc
        pv = jax.lax.dot_general(
            pv_p, vpage, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, KVhd]

        # refill this slot for page w+D — issued after the loads above, so
        # the in-order instruction stream can't overwrite data still in use
        @pl.when(w + D < num_pages)
        def _():
            start_dma(w + D)

        return new_m, new_l, acc * corr + pv

    if has_sink:
        # gpt-oss attention sink: an extra softmax slot with zero value
        # contribution — seed the online softmax with it (m=sink, l=1)
        m0 = sink_ref[0].astype(jnp.float32)  # [H, 1]
        l0 = jnp.ones((H, 1), jnp.float32)
    else:
        m0 = jnp.full((H, 1), _NEG, jnp.float32)
        l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, KVhd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(start_page, num_pages, body,
                                  (m0, l0, acc0))

    out_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)


def pallas_supported(num_kv_heads: int, head_dim: int) -> bool:
    return (num_kv_heads * head_dim) % _LANE == 0


def paged_attention_decode(q, k_cache, v_cache, block_tables, kv_lens, *,
                           block_size: int, interpret: bool = False,
                           window=None, sinks=None,
                           k_scales=None, v_scales=None,
                           scale_slot_base=None):
    """Decode-step paged attention. See module docstring for the contract.

    ``window``: sliding-window size as a (possibly traced per-layer) scalar
    — 0/None = full attention; pages outside the window are never fetched.
    ``sinks``: optional per-head attention-sink logits [H] (gpt-oss),
    seeded into the online softmax with zero value contribution.
    ``k_scales``/``v_scales`` [slots, KV] f32 (int8 caches): pages are int8
    and dequantize IN the kernel — HBM page traffic halves vs bf16, the
    decode bandwidth win the KV-capacity role of the reference's G1 tier
    implies (lib/llm/src/block_manager/).
    ``scale_slot_base`` (traced scalar, default 0): slot offset of the
    scale tables relative to the page cache — callers with a LAYER-STACKED
    flat cache pass one layer's scale slice plus ``lidx·slots`` so the
    VMEM-resident scale budget is per-layer, not ×L (serving-scale caches
    would otherwise always fall back to the slow 4-DMA path).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    slots, KV, _ = k_cache.shape
    G = H // KV
    KVhd = KV * hd
    bs = block_size
    quant = k_scales is not None
    if not pallas_supported(KV, hd):
        return paged_attention_decode_xla(
            q, k_cache, v_cache, block_tables, kv_lens, block_size=bs,
            window=window, sinks=sinks, k_scales=k_scales,
            v_scales=v_scales, scale_slot_base=scale_slot_base)
    interpret = interpret or kernel_interpret_mode()
    has_sink = sinks is not None
    win_arr = jnp.asarray([0 if window is None else window],
                          jnp.int32).reshape(1)
    sbase_arr = jnp.asarray([0 if scale_slot_base is None
                             else scale_slot_base], jnp.int32).reshape(1)
    sink_in = (jnp.zeros((1, H, 1), q.dtype) if not has_sink
               else sinks.reshape(1, H, 1).astype(q.dtype))

    # block-expand q: head h's vector sits in its own KV segment, zeros else
    seg = jnp.arange(H) // G  # [H]
    onehot = jax.nn.one_hot(seg, KV, dtype=q.dtype)  # [H, KV]
    qexp = jnp.einsum("bhd,hk->bhkd", q, onehot).reshape(B, H, KVhd)
    qexp = qexp * jnp.asarray(1.0 / np.sqrt(hd), q.dtype)  # fold in the scale

    W = block_tables.shape[1]
    D = min(W, 16)  # pipeline depth (VMEM budget: 2·D·bs·KVhd·dtype bytes)
    # int8 scale placement: resident in VMEM when both arrays fit the
    # budget (one fetch for the whole grid, 2 DMAs/page like bf16) — the
    # 4-DMA variant measured 2.9x slower on-chip (tiny scale copies pay
    # full grant latency). Budget overridable for experiments.
    vmem_scales = False
    if quant:
        # honest VMEM footprint of the lane-packed TRANSPOSED [KV, slots]
        # layout: sublane dim pads KV→8, lane dim pads slots→128. (The r4
        # [slots, KV] layout tile-padded its lane dim KV→128 — 16-128× the
        # bytes the old 2·slots·KV·4 check counted, so configs passed the
        # check yet overflowed VMEM at Mosaic compile time; ADVICE r4.)
        # Sized from the SCALE table, not the page cache: layer-stacked
        # callers pass one layer's slice (scale_slot_base), so the gate
        # and the packed operand are per-layer — an L·slots cache must
        # not fail the gate at L× the real residency.
        sc_slots = k_scales.shape[0]
        padded_slots = -(-sc_slots // _LANE) * _LANE
        scale_bytes = 2 * (-(-KV // 8) * 8) * padded_slots * 4
        budget = int(os.environ.get("DYN_KV_SCALE_VMEM_BYTES", 32 << 20))
        vmem_scales = scale_bytes <= budget
    kernel = functools.partial(_decode_kernel, bs=bs, has_sink=has_sink,
                               quant=quant, vmem_scales=vmem_scales)
    in_specs = [
        pl.BlockSpec((1, H, KVhd), lambda b, *_: (b, 0, 0)),
        pl.BlockSpec((1, H, 1), lambda b, *_: (0, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
    ]
    scratch = [
        pltpu.VMEM((D, bs, KVhd), k_cache.dtype),  # D pages in flight
        pltpu.VMEM((D, bs, KVhd), v_cache.dtype),
    ]
    operands = [k_cache.reshape(slots, KVhd), v_cache.reshape(slots, KVhd)]
    if quant:
        if vmem_scales:
            # constant block index → Pallas fetches the arrays once and
            # keeps them resident across the whole (B,) grid. Transposed so
            # slots ride the (cheap) lane dim — see the budget note above.
            def lane_pack_t(s):
                s = s.astype(jnp.float32).T  # [KV, sc_slots]
                return jnp.pad(s, ((0, 0), (0, padded_slots - sc_slots)))

            in_specs += [
                pl.BlockSpec((KV, padded_slots), lambda b, *_: (0, 0)),
                pl.BlockSpec((KV, padded_slots), lambda b, *_: (0, 0))]
            operands += [lane_pack_t(k_scales), lane_pack_t(v_scales)]
        else:
            in_specs += [pl.BlockSpec(memory_space=pltpu.HBM),
                         pl.BlockSpec(memory_space=pltpu.HBM)]
            scratch += [pltpu.VMEM((D, bs, KV), jnp.float32),
                        pltpu.VMEM((D, bs, KV), jnp.float32)]
            operands += [k_scales.astype(jnp.float32),
                         v_scales.astype(jnp.float32)]
    scratch.append(
        pltpu.SemaphoreType.DMA((D, 4 if quant and not vmem_scales else 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, KVhd), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch,
    )
    out_full = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, KVhd), q.dtype),
        interpret=interpret,
    )(block_tables, kv_lens, win_arr, sbase_arr, qexp, sink_in, *operands)

    # pick each head's own KV segment back out
    out_full = out_full.reshape(B, H, KV, hd)
    return jnp.take_along_axis(
        out_full, seg[None, :, None, None], axis=2).reshape(B, H, hd)


def paged_attention_decode_xla(q, k_cache, v_cache, block_tables, kv_lens, *,
                               block_size: int, window=None, sinks=None,
                               k_scales=None, v_scales=None,
                               scale_slot_base=None):
    """Reference/fallback path (same math, gather through XLA) — honors the
    same window/sink/int8 contract as the kernel, so a shape-based fallback
    can never silently change attention semantics."""
    B, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    W = block_tables.shape[1]
    T = W * block_size

    slot_idx = (block_tables[:, :, None] * block_size
                + jnp.arange(block_size)[None, None, :]).reshape(B, T)
    k = k_cache[slot_idx]  # [B, T, KV, hd]
    v = v_cache[slot_idx]
    if k_scales is not None:  # int8 pages: dequant fused into the gather
        sidx = slot_idx - (0 if scale_slot_base is None else scale_slot_base)
        k = k.astype(jnp.float32) * k_scales[sidx][..., None]
        v = v.astype(jnp.float32) * v_scales[sidx][..., None]
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32)) / np.sqrt(hd)
    key_pos = jnp.arange(T)
    mask = key_pos[None] < kv_lens[:, None]  # [B, T]
    if window is not None:
        win = jnp.asarray(window)
        mask = mask & ((win <= 0) | (key_pos[None] >= kv_lens[:, None] - win))
    s = jnp.where(mask[:, None, None], s, _NEG)
    if sinks is not None:  # combined softmax, sink slot contributes no value
        sk = sinks.astype(jnp.float32).reshape(KV, G)[None, :, :, None]
        m = jnp.maximum(s.max(-1), sk[..., 0])[..., None]
        e = jnp.exp(s - m)
        p = e / (e.sum(-1, keepdims=True) + jnp.exp(sk - m))
    else:
        p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------- MLA decode

def _mla_decode_kernel(block_tables_ref, kv_lens_ref,
                       sbase_ref,  # scalar prefetch; scale-table slot base
                       qe_ref,  # [1, H, R] VMEM (scale folded in)
                       qr_ref,  # [1, H, PR] VMEM
                       ccache_ref, rcache_ref,  # [slots, R] / [slots, PR] HBM
                       *rest,  # [csc_ref, rsc_ref (VMEM [slots, 1]),]
                               # out_ref, cbuf, rbuf, dma_sem
                       bs: int, quant: bool = False):
    """MLA is simpler than GQA here: every head attends over the SAME single
    latent page, so no block-expansion trick is needed — scores are
    q_eff·c + q_rot·rope (both lane-aligned MXU matmuls) and the VALUE is
    the latent itself; W_UV absorption happens outside.

    int8 pages (``quant``): the per-slot scales are ONE f32 per key,
    lane-packed [rows, 128] and VMEM-resident (no scale DMAs — the GQA
    lesson); callers gate on mla_int8_kernel_supported (VMEM budget +
    bs | 128) and fall back to the XLA gather path past it. Score parts
    dequant separately (c and rope carry different scales); the value
    dequant folds into p."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        csc_ref, rsc_ref, out_ref, cbuf, rbuf, dma_sem = rest
    else:
        out_ref, cbuf, rbuf, dma_sem = rest
        csc_ref = rsc_ref = None

    b = pl.program_id(0)
    kv_len = kv_lens_ref[b]
    num_pages = (kv_len + bs - 1) // bs
    H, R = qe_ref.shape[1], qe_ref.shape[2]
    D = cbuf.shape[0]

    def start_dma(w):
        blk = block_tables_ref[b, w]
        slot = w % D
        pltpu.make_async_copy(
            ccache_ref.at[pl.ds(blk * bs, bs)], cbuf.at[slot],
            dma_sem.at[slot, 0]).start()
        pltpu.make_async_copy(
            rcache_ref.at[pl.ds(blk * bs, bs)], rbuf.at[slot],
            dma_sem.at[slot, 1]).start()

    def wait_dma(w):
        slot = w % D
        pltpu.make_async_copy(cbuf.at[slot], cbuf.at[slot],
                              dma_sem.at[slot, 0]).wait()
        pltpu.make_async_copy(rbuf.at[slot], rbuf.at[slot],
                              dma_sem.at[slot, 1]).wait()

    prefill_n = jnp.minimum(num_pages, D)
    jax.lax.fori_loop(0, prefill_n, lambda w, c: (start_dma(w), c)[1], 0)

    qe = qe_ref[0].astype(jnp.float32)  # [H, R]
    qr = qr_ref[0].astype(jnp.float32)  # [H, PR]

    def body(w, carry):
        m, l, acc = carry
        wait_dma(w)
        cpage = cbuf[w % D].astype(jnp.float32)  # [bs, R]
        rpage = rbuf[w % D].astype(jnp.float32)  # [bs, PR]
        if quant:
            blk = block_tables_ref[b, w]
            # scales are LANE-PACKED [rows, 128] (a [slots, 1] block would
            # tile-pad the lane dim 1→128, inflating VMEM 128×); a page's
            # bs scales sit inside one row because bs divides 128. The
            # offset rebases onto the (possibly layer-sliced) scale table.
            off = blk * bs - sbase_ref[0]
            csc = csc_ref[off // _LANE, pl.ds(off % _LANE, bs)].reshape(1, bs)
            rsc = rsc_ref[off // _LANE, pl.ds(off % _LANE, bs)].reshape(1, bs)

        sc = jax.lax.dot_general(
            qe, cpage, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, bs]
        sr = jax.lax.dot_general(
            qr, rpage, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quant:
            # the two score parts carry DIFFERENT quant scales — dequant
            # each before summing
            s = sc * csc + sr * rsc
        else:
            s = sc + sr

        key_pos = w * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(key_pos < kv_len, s, _NEG)  # MLA: full attention

        chunk_max = jnp.max(s, axis=1, keepdims=True)
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        new_l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        # value IS the latent; its dequant folds into p (per-key scale)
        pv = jax.lax.dot_general(
            p * csc if quant else p, cpage, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, R]

        @pl.when(w + D < num_pages)
        def _():
            start_dma(w + D)

        return new_m, new_l, acc * corr + pv

    m0 = jnp.full((H, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, R), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_pages, body, (m0, l0, acc0))
    out_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)


def mla_pallas_supported(kv_lora_rank: int, rope_cache_dim: int) -> bool:
    return kv_lora_rank % _LANE == 0 and rope_cache_dim % _LANE == 0


def mla_int8_kernel_supported(block_size: int, flat_slots: int) -> bool:
    """Whether the int8 latent kernel can take this cache: a page's scales
    must sit in one lane row (bs | 128) and both lane-packed scale arrays
    must fit the VMEM budget (callers fall back to the XLA gather path
    otherwise)."""
    if _LANE % block_size:
        return False
    padded = -(-flat_slots // _LANE) * _LANE
    budget = int(os.environ.get("DYN_KV_SCALE_VMEM_BYTES", 32 << 20))
    return 2 * padded * 4 <= budget


def mla_paged_decode(q_eff, q_rot, latent_cache, rope_cache, block_tables,
                     kv_lens, *, block_size: int, scale: float,
                     interpret: bool = False,
                     c_scales=None, r_scales=None,
                     scale_slot_base=None):
    """MLA decode over the paged latent cache.

    q_eff [B,H,R] (queries absorbed through W_UK), q_rot [B,H,PR] (post-rope
    part, zero-padded to the cache's lane-aligned PR), latent_cache
    [slots,R], rope_cache [slots,PR] → attention output IN LATENT SPACE
    [B,H,R] (caller expands through W_UV). ``scale`` is the softmax scale
    (incl. YaRN mscale² — engine/model.mla_softmax_scale), folded into the
    queries here.

    ``c_scales``/``r_scales`` [slots] f32 (int8 caches): pages are int8 and
    dequantize in the kernel; scales ride lane-packed in VMEM (no scale
    DMAs). Callers must check :func:`mla_int8_kernel_supported` first.
    ``scale_slot_base``: slot offset of the scale tables relative to the
    page cache (layer-stacked callers pass one layer's slice + its base,
    keeping VMEM residency per-layer — same contract as
    paged_attention_decode).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R = q_eff.shape
    PR = q_rot.shape[-1]
    bs = block_size
    quant = c_scales is not None
    interpret = interpret or kernel_interpret_mode()

    qe = (q_eff.astype(jnp.float32) * scale).astype(q_eff.dtype)
    qr = (q_rot.astype(jnp.float32) * scale).astype(q_rot.dtype)
    sbase_arr = jnp.asarray([0 if scale_slot_base is None
                             else scale_slot_base], jnp.int32).reshape(1)

    W = block_tables.shape[1]
    D = min(W, 8)  # VMEM: D·bs·(R+PR)·dtype bytes in flight
    slots = (c_scales.shape[0] if quant else latent_cache.shape[0])
    kernel = functools.partial(_mla_decode_kernel, bs=bs, quant=quant)
    in_specs = [
        pl.BlockSpec((1, H, R), lambda b, *_: (b, 0, 0)),
        pl.BlockSpec((1, H, PR), lambda b, *_: (b, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
    ]
    operands = [latent_cache, rope_cache]
    if quant:
        # constant block index → fetched once, resident for the whole grid.
        # LANE-PACKED [rows, 128] so VMEM holds slots×4 bytes, not ×512
        # (a [slots, 1] block would pad its lane dim 1→128); callers gate
        # on mla_int8_kernel_supported for the budget + bs|128 invariants
        padded = -(-slots // _LANE) * _LANE
        rows = padded // _LANE

        def lane_pack(s):
            s = s.astype(jnp.float32)
            return jnp.pad(s, (0, padded - slots)).reshape(rows, _LANE)

        in_specs += [pl.BlockSpec((rows, _LANE), lambda b, *_: (0, 0)),
                     pl.BlockSpec((rows, _LANE), lambda b, *_: (0, 0))]
        operands += [lane_pack(c_scales), lane_pack(r_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, R), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((D, bs, R), latent_cache.dtype),
            pltpu.VMEM((D, bs, PR), rope_cache.dtype),
            pltpu.SemaphoreType.DMA((D, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_eff.dtype),
        interpret=interpret,
    )(block_tables, kv_lens, sbase_arr, qe, qr, *operands)
