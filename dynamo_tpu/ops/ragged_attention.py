"""Pallas TPU ragged paged-attention kernel: mixed prefill+decode, one launch.

One packed token batch serves every sequence in the step — decode rows
(q_len=1) and prefill chunks (q_len>1) ride the SAME kernel with per-row
``(q_start, q_len, kv_len)`` metadata, so the engine no longer pads decode
batches and prefill chunks to separate compiled buckets (the Ragged Paged
Attention design, PAPERS.md arxiv 2604.15464; the bucket-lattice tax it
kills is quantified in docs/performance.md).

Contract (one layer; the stacked-cache wiring lives in engine/model.py):
  q            [T, H, hd]        packed queries, row-major by sequence; a
                                 row's tokens are consecutive positions
                                 ending at kv_len-1 (the engine's chunk
                                 layout), so per-token positions are pure
                                 index math: pos = kv_len - q_len + j
  k/v cache    [slots, KV, hd]   flat paged layout (slot = block·bs + off)
  block_tables [R, W] int32      per ROW (0 = reserved null block)
  rows3        [R, 3] int32      (q_start, q_len, kv_len) per row; padding
                                 rows carry q_len = 0 and are skipped
  → out        [T, H, hd]

TPU mapping (everything here is shaped by what Mosaic will compile — see
tests/test_chip_compile.py, which asks the chip's compiler without a chip):
pages are read in the cache's OWN layout. ``[slots, KV, hd]`` is viewed as
``[pages, bs·KV, hd]`` — the same bytes, so XLA hands the pool to the kernel
without a relayout copy (a flattened ``[slots, KV·hd]`` view is a different
tiling and costs a copy of the whole pool per call) — and a page is one
index on the leading, untiled dim. Pages stream HBM→VMEM once per query
tile through a D-deep rotating DMA pipeline as ``[bs·KV, hd]`` tiles; scores
come from one MXU matmul of the query tile ``[TQ·Hp, hd]`` against ALL KV
heads' keys of the page (column = slot·KV + kv head), a static mask keeps
each head's own KV group, and an online softmax folds pages as they land;
``P @ V`` over the same columns lands directly in ``[TQ·Hp, hd]``, so
neither q nor the output is ever expanded. Query and output tiles DMA at
dynamic offsets on the LEADING token dim of 3-D ``[T, Hp, hd]`` operands
(q_start is data; Mosaic takes a dynamic offset on a tiled dim only when it
can prove it tile-aligned), so T never enters VMEM whole and the compiled
signature depends ONLY on (T, R, W) — one program per token budget, not per
(chunk × batch × width) bucket. Heads pad to the sublane packing (Hp).

Sliding windows and attention sinks match the decode kernel. int8 KV pages
dequantize IN the kernel: the per-(slot, head) f32 scales of one layer ride
as constant-block VMEM operands, one ``[bs·KV]`` row per page (exactly the
score columns' order), indexed on the sublane dim and rebased per layer via
``scale_slot_base``. k-scales multiply the scores, v-scales fold into p
before the PV matmul, so int8 pages cost the same two DMAs per page as bf16
at half the bytes. The only degrades to :func:`ragged_attention_xla` are a
head dim that is not a lane multiple and scale tables past the VMEM budget
— both static shape facts the engine counts and logs
(``dynamo_ragged_fallback_total``), never a silent data-dependent branch.
``DYN_RAGGED_ORACLE=1`` routes to the XLA oracle explicitly (bench/test
A/B arms only).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops.paged_attention import (
    _LANE, _NEG, kernel_interpret_mode,
)

#: scoped-VMEM ceiling handed to Mosaic (v5e: 128 MiB physical, 16 MiB
#: default scope). Pages, tiles and f32 temporaries take ~2 MB; the rest is
#: room for the VMEM-resident int8 scale tables.
_VMEM_LIMIT_BYTES = 64 << 20


def ragged_pallas_supported(num_kv_heads: int, head_dim: int) -> bool:
    """Pages DMA as [bs·KV, hd] tiles and Mosaic takes only whole 128-lane
    rows, so the head dim itself must be a lane multiple (hd = 64 models
    leave the kernel under ``lane_align``)."""
    return head_dim % _LANE == 0


def _scale_table_shape(num_kv_heads: int, sc_slots: int, block_size: int):
    """VMEM shape of one int8 scale table: one row per page holding its
    [bs, KV] scales flattened, rows padded to the f32 sublane tile and
    lanes to 128."""
    pages = -(-sc_slots // block_size)
    return (-(-pages // 8) * 8,
            -(-(block_size * num_kv_heads) // _LANE) * _LANE)


def ragged_int8_kernel_supported(num_kv_heads: int, sc_slots: int,
                                 block_size: int = 16) -> bool:
    """True when the per-layer k/v scale tables fit the VMEM-resident
    budget: two tables, each double-buffered by the Pallas pipeline (a
    constant block index is fetched once but still gets two buffers).
    ``sc_slots`` is the PER-LAYER slot count (the layer-stacked caller
    passes one layer's slice + scale_slot_base)."""
    pages, lanes = _scale_table_shape(num_kv_heads, sc_slots, block_size)
    scale_bytes = 2 * 2 * pages * lanes * 4
    return scale_bytes <= int(os.environ.get("DYN_KV_SCALE_VMEM_BYTES",
                                             40 << 20))


def _ragged_kernel(rows3_ref, block_tables_ref, win_ref,  # scalar prefetch
                   sbase_ref,  # scalar pf; sbase = scale-table page base
                   sink_ref,   # [1, Hp, 1] VMEM (zeros when has_sink=False)
                   q_ref,      # [Tpad, Hp, hd] HBM (softmax scale folded in)
                   kcache_ref, vcache_ref,  # [pages, bs·KV, hd] HBM
                   *rest,  # [ksc_ref, vsc_ref ([sc_pages, lanes] VMEM),]
                           # out_ref, qbuf, obuf, kbuf, vbuf, qo_sem, dma_sem
                   bs: int, tq: int, KV: int, G: int, has_sink: bool,
                   quant: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        (ksc_ref, vsc_ref, out_ref, qbuf, obuf, kbuf, vbuf,
         qo_sem, dma_sem) = rest
    else:
        out_ref, qbuf, obuf, kbuf, vbuf, qo_sem, dma_sem = rest
        ksc_ref = vsc_ref = None

    r = pl.program_id(0)
    q_start = rows3_ref[r, 0]
    q_len = rows3_ref[r, 1]
    kv_len = rows3_ref[r, 2]
    win = win_ref[0]
    _, Hp, hd = qbuf.shape
    D = kbuf.shape[0]
    N = bs * KV  # keys of one page, all KV heads: column c = slot·KV + kv

    def start_page_dma(w):
        blk = block_tables_ref[r, w]
        slot = w % D
        pltpu.make_async_copy(kcache_ref.at[blk], kbuf.at[slot],
                              dma_sem.at[slot, 0]).start()
        pltpu.make_async_copy(vcache_ref.at[blk], vbuf.at[slot],
                              dma_sem.at[slot, 1]).start()

    def wait_page_dma(w):
        slot = w % D
        pltpu.make_async_copy(kbuf.at[slot], kbuf.at[slot],
                              dma_sem.at[slot, 0]).wait()
        pltpu.make_async_copy(vbuf.at[slot], vbuf.at[slot],
                              dma_sem.at[slot, 1]).wait()

    n_tiles = (q_len + tq - 1) // tq

    # score layout [TQ·Hp, bs·KV]: row = token·Hp + head, column = page
    # slot·KV + kv head. A head only reads the columns of its own KV group.
    rows = jax.lax.broadcasted_iota(jnp.int32, (tq * Hp, N), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tq * Hp, N), 1)
    tok_in_tile = rows // Hp
    key_in_page = cols // KV
    own_group = (cols % KV) == (rows % Hp) // G

    def tile_body(t, _carry):
        tok0 = q_start + t * tq
        # query tile in: tokens ride the LEADING (untiled) dim, so the
        # data-dependent row offset needs no alignment proof; the packed
        # array is padded by TQ rows, so the fixed-size copy cannot overrun
        pltpu.make_async_copy(q_ref.at[pl.ds(tok0, tq)], qbuf,
                              qo_sem.at[0]).start()
        pltpu.make_async_copy(qbuf, qbuf, qo_sem.at[0]).wait()

        # positions of this tile: pos0 .. pos0+tq-1 (chunk tokens occupy
        # the tail of the kv range — the engine's packing contract)
        pos0 = kv_len - q_len + t * tq
        hi_pos = jnp.minimum(pos0 + tq - 1, kv_len - 1)
        num_pages = jnp.minimum((hi_pos + bs) // bs, (kv_len + bs - 1) // bs)
        # sliding window: the EARLIEST key any tile position can see is
        # pos0 - win + 1; pages wholly before it are never fetched
        first_key = jnp.where(win > 0, jnp.maximum(pos0 - win + 1, 0), 0)
        start_page = first_key // bs

        prefill_n = jnp.minimum(num_pages, start_page + D)
        jax.lax.fori_loop(start_page, prefill_n,
                          lambda w, c: (start_page_dma(w), c)[1], 0)

        qt = qbuf[...].astype(jnp.float32).reshape(tq * Hp, hd)
        q_pos = pos0 + tok_in_tile

        def page_body(w, carry):
            m, l, acc = carry  # [TQ·Hp,1] f32 ×2, [TQ·Hp,hd] f32
            wait_page_dma(w)
            kpage = kbuf[w % D].astype(jnp.float32)  # [bs·KV, hd]
            vpage = vbuf[w % D].astype(jnp.float32)

            # every head against every KV head's keys in one MXU matmul;
            # the own_group mask keeps each head's own columns
            s = jax.lax.dot_general(
                qt, kpage, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [TQ·Hp, bs·KV]
            if quant:
                # int8 pages: one [1, bs·KV] scale row per page, already in
                # column order (the [slots, KV] tables flatten to it)
                page = block_tables_ref[r, w] - sbase_ref[0]
                s = s * ksc_ref[pl.ds(page, 1), :][:, :N]

            key_pos = w * bs + key_in_page
            mask = own_group & (key_pos <= q_pos) & (key_pos < kv_len)
            mask = mask & ((win <= 0) | (key_pos > q_pos - win))
            s = jnp.where(mask, s, _NEG)

            chunk_max = jnp.max(s, axis=1, keepdims=True)
            new_m = jnp.maximum(m, chunk_max)
            corr = jnp.exp(m - new_m)
            # masked columns must contribute exactly 0 — a fully-masked
            # page leaves new_m at _NEG, where exp(s - new_m) would be 1
            p = jnp.where(mask, jnp.exp(s - new_m), 0.0)
            new_l = l * corr + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * vsc_ref[pl.ds(page, 1), :][:, :N]
            pv = jax.lax.dot_general(
                p, vpage, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [TQ·Hp, hd]

            @pl.when(w + D < num_pages)
            def _():
                start_page_dma(w + D)

            return new_m, new_l, acc * corr + pv

        if has_sink:
            # sink slot: seeds the online softmax, contributes no value
            sk = sink_ref[0].astype(jnp.float32)  # [Hp, 1]
            m0 = jnp.broadcast_to(sk[None], (tq, Hp, 1)).reshape(tq * Hp, 1)
            l0 = jnp.ones((tq * Hp, 1), jnp.float32)
        else:
            m0 = jnp.full((tq * Hp, 1), _NEG, jnp.float32)
            l0 = jnp.zeros((tq * Hp, 1), jnp.float32)
        acc0 = jnp.zeros((tq * Hp, hd), jnp.float32)
        m, l, acc = jax.lax.fori_loop(start_page, num_pages, page_body,
                                      (m0, l0, acc0))

        obuf[...] = (acc / jnp.maximum(l, 1e-30)).reshape(
            tq, Hp, hd).astype(obuf.dtype)
        # tile out: overruns past q_len land in the NEXT row's region,
        # which that row's own (later, sequential) grid step overwrites;
        # the last row's overrun lands in the TQ-row output padding
        pltpu.make_async_copy(obuf, out_ref.at[pl.ds(tok0, tq)],
                              qo_sem.at[1]).start()
        pltpu.make_async_copy(obuf, obuf, qo_sem.at[1]).wait()
        return 0

    @pl.when(q_len > 0)
    def _():
        jax.lax.fori_loop(0, n_tiles, tile_body, 0)


def ragged_paged_attention(q, k_cache, v_cache, block_tables, rows3, *,
                           block_size: int, interpret: bool = False,
                           window=None, sinks=None, tq: int = 8,
                           k_scales=None, v_scales=None,
                           scale_slot_base=None):
    """Ragged paged attention over a packed token batch. See module
    docstring for the contract.

    ``k_scales``/``v_scales`` [sc_slots, KV] f32 (int8 caches): pages are
    int8 and dequantize IN the kernel — scales go VMEM-resident, one
    [bs·KV] row per page, fetched once for the whole grid.
    ``scale_slot_base`` (traced scalar, default 0): slot offset of the
    scale tables relative to the page cache — layer-stacked callers pass
    one layer's scale slice plus ``lidx·slots`` so the VMEM budget is
    per-layer, not ×L.

    Routes to :func:`ragged_attention_xla` only for a head dim off the
    lane multiple, scale tables past the VMEM budget, or the explicit
    ``DYN_RAGGED_ORACLE=1`` bench/test oracle switch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, hd = q.shape
    slots, KV, _ = k_cache.shape
    G = H // KV
    bs = block_size
    quant = k_scales is not None
    sc_slots = k_scales.shape[0] if quant else 0
    if (not ragged_pallas_supported(KV, hd)
            or (quant and not ragged_int8_kernel_supported(
                KV, sc_slots, bs))
            or os.environ.get("DYN_RAGGED_ORACLE") == "1"):
        return ragged_attention_xla(
            q, k_cache, v_cache, block_tables, rows3, block_size=bs,
            window=window, sinks=sinks, k_scales=k_scales,
            v_scales=v_scales, scale_slot_base=scale_slot_base)
    interpret = interpret or kernel_interpret_mode()
    R, W = block_tables.shape
    has_sink = sinks is not None
    win_arr = jnp.asarray([0 if window is None else window],
                          jnp.int32).reshape(1)
    sbase_arr = (jnp.asarray([0 if scale_slot_base is None
                              else scale_slot_base], jnp.int32) // bs
                 ).reshape(1)

    # heads pad to the sublane packing of q's dtype (8 rows of 32 bits) so
    # the in-kernel [TQ, Hp, hd] <-> [TQ·Hp, hd] reshapes are layout-
    # trivial; padded heads match no KV group and come out zero
    sub = 8 * max(1, 4 // q.dtype.itemsize)
    Hp = -(-H // sub) * sub
    sink_in = jnp.pad(
        jnp.zeros((H,), q.dtype) if not has_sink else sinks.astype(q.dtype),
        (0, Hp - H)).reshape(1, Hp, 1)
    # fold the softmax scale; pad by one tile so fixed-size tile DMAs never
    # overrun. Tokens stay on the leading dim of a 3-D operand: q_start is
    # data, and Mosaic takes a dynamic DMA offset on a tiled dim only when
    # it can prove it tile-aligned.
    qs = q * jnp.asarray(1.0 / np.sqrt(hd), q.dtype)
    qs = jnp.pad(qs, ((0, tq), (0, Hp - H), (0, 0)))

    D = min(W, 8)  # page-pipeline depth (VMEM: 2·D·bs·KV·hd·dtype bytes)
    kernel = functools.partial(_ragged_kernel, bs=bs, tq=tq, KV=KV, G=G,
                               has_sink=has_sink, quant=quant)
    in_specs = [
        pl.BlockSpec((1, Hp, 1), lambda r, *_: (0, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.HBM),  # q
        pl.BlockSpec(memory_space=pltpu.HBM),  # k pages
        pl.BlockSpec(memory_space=pltpu.HBM),  # v pages
    ]
    # page view [pages, bs·KV, hd]: the same bytes as [slots, KV, hd] (a
    # page's slots are consecutive), so XLA passes the cache through
    # without a relayout copy, and a page is one leading-dim index
    operands = [sink_in, qs, k_cache.reshape(slots // bs, bs * KV, hd),
                v_cache.reshape(slots // bs, bs * KV, hd)]
    if quant:
        # constant block index → Pallas fetches the scale tables once and
        # keeps them resident across the whole (R,) grid; page p's row is
        # the [bs, KV] scales of its slots flattened in column order
        sc_pages, lanes = _scale_table_shape(KV, sc_slots, bs)

        def page_rows(s):
            s = s.astype(jnp.float32).reshape(sc_slots // bs, bs * KV)
            return jnp.pad(s, ((0, sc_pages - sc_slots // bs),
                               (0, lanes - bs * KV)))

        in_specs += [pl.BlockSpec((sc_pages, lanes), lambda r, *_: (0, 0)),
                     pl.BlockSpec((sc_pages, lanes), lambda r, *_: (0, 0))]
        operands += [page_rows(k_scales), page_rows(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=[
            pltpu.VMEM((tq, Hp, hd), q.dtype),            # qbuf
            pltpu.VMEM((tq, Hp, hd), q.dtype),            # obuf
            pltpu.VMEM((D, bs * KV, hd), k_cache.dtype),  # kbuf
            pltpu.VMEM((D, bs * KV, hd), v_cache.dtype),  # vbuf
            pltpu.SemaphoreType.DMA((2,)),                # q-in / out tiles
            pltpu.SemaphoreType.DMA((D, 2)),              # page pipeline
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + tq, Hp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ragged_paged_attention",
    )(rows3.astype(jnp.int32), block_tables.astype(jnp.int32), win_arr,
      sbase_arr, *operands)
    return out[:T, :H]


def ragged_attention_xla(q, k_cache, v_cache, block_tables, rows3, *,
                         block_size: int, window=None, sinks=None,
                         k_scales=None, v_scales=None,
                         scale_slot_base=None):
    """Reference/oracle path: per-token dense gather through XLA, same
    masking semantics as the kernel — the oracle the kernel tests pin, and
    the path non-lane-aligned shapes take. int8 caches dequantize in the
    gather with the same ``k_scales``/``v_scales``/``scale_slot_base``
    contract as the kernel."""
    T, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    R, W = block_tables.shape
    bs = block_size
    Tk = W * bs

    q_start = rows3[:, 0]
    q_len = rows3[:, 1]
    kv_len = rows3[:, 2]
    # token → row membership from the contiguous packing. Padding rows
    # (q_len == 0) carry zero q_start/q_len, which would break
    # searchsorted's sorted-input precondition — push their end markers
    # past every real token so the search only ever lands real rows (or
    # the first padding row, for padding tokens; its kv_len 0 masks all).
    ends = jnp.where(q_len > 0, q_start + q_len, jnp.int32(1 << 30))
    tok = jnp.arange(T)
    row_ids = jnp.clip(
        jnp.searchsorted(ends, tok, side="right"), 0, R - 1)
    positions = kv_len[row_ids] - (q_start + q_len)[row_ids] + tok

    slot_idx = (block_tables[:, :, None] * bs
                + jnp.arange(bs)[None, None, :]).reshape(R, Tk)
    k = k_cache[slot_idx].astype(jnp.float32)  # [R, Tk, KV, hd]
    v = v_cache[slot_idx].astype(jnp.float32)
    if k_scales is not None:
        # int8 pages: dequant in the gather, rebasing slot ids onto the
        # caller's (possibly per-layer) scale slice
        sidx = slot_idx - (0 if scale_slot_base is None else scale_slot_base)
        k = k * k_scales[sidx].astype(jnp.float32)[..., None]
        v = v * v_scales[sidx].astype(jnp.float32)[..., None]
    k = k[row_ids]  # [T, Tk, KV, hd]
    v = v[row_ids]

    qg = q.reshape(T, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("tkgd,tskd->tkgs", qg, k) / np.sqrt(hd)
    key_pos = jnp.arange(Tk)
    mask = (key_pos[None, :] <= positions[:, None]) & (
        key_pos[None, :] < kv_len[row_ids][:, None])
    if window is not None:
        win = jnp.asarray(window)
        mask = mask & ((win <= 0)
                       | (key_pos[None, :] > positions[:, None] - win))
    s = jnp.where(mask[:, None, None, :], s, _NEG)
    if sinks is not None:
        sk = sinks.astype(jnp.float32).reshape(KV, G)[None, :, :, None]
        m = jnp.maximum(s.max(-1), sk[..., 0])[..., None]
        e = jnp.exp(s - m)
        p = e / (e.sum(-1, keepdims=True) + jnp.exp(sk - m))
    else:
        p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("tkgs,tskd->tkgd", p, v)
    return o.reshape(T, H, hd).astype(q.dtype)
