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
  k cache      [slots, KV·NB, 128]  flat paged layout (slot = block·bs +
                                 off). A head of 128 dims is one row
                                 (NB = 1); a wider one is stored as NB
                                 whole 128-lane rows, the lanes past hd
                                 zero (MiMo-V2's 192-wide head: NB = 2),
                                 a narrower one as one row, zero past hd
                                 (LFM2's 64-wide head, where the model
                                 says ``kv_lane_pad``) — Mosaic strides
                                 sublanes of 128-lane buffers only. q is
                                 zero-padded to NB·128 here; the softmax
                                 scale is hd's
  v cache      [slots, KV, 128]  V rows have their own width (a narrow V
                                 head padded alike: the output's lanes
                                 past it are zero, and the caller cuts
                                 them)
  block_tables [R, W] int32      per ROW (0 = reserved null block)
  rows3        [R, 3] int32      (q_start, q_len, kv_len) per row; padding
                                 rows carry q_len = 0 and are skipped
  → out        [T, H, vd]

TPU mapping (everything here is shaped by what Mosaic will compile — see
tests/test_chip_compile.py, which asks the chip's compiler without a chip):
pages are read in the cache's OWN layout. ``[slots, KV, hd]`` is viewed as
``[pages, bs·KV, hd]`` — the same bytes, so XLA hands the pool to the kernel
without a relayout copy (a flattened ``[slots, KV·hd]`` view is a different
tiling and costs a copy of the whole pool per call) — and a page is one
index on the leading, untiled dim.

One grid step is one row, and a row's work is done once:

- **A query tile chosen from the row.** A row of at most ``NARROW_TILE``
  (8) tokens — decode, speculative verify — is one 8-token tile; a longer
  row takes 128-token tiles, the last one moved back so it ENDS at the
  row's end (a row shorter than a tile overruns into the next rows' region
  of the output, which their own, later grid steps overwrite). The choice
  is made in the kernel from ``rows3``, so the compiled signature depends
  ONLY on (T, R, W) — one program per token budget. A 1,024-token chunk
  streams its prefix 8 times, not 128.
- **Keys stream in 512-key blocks**, double-buffered: a block's pages DMA
  HBM→VMEM as ``[bs·KV, hd]`` tiles into one ``[512·KV, hd]`` buffer while
  the block before it is scored. Only a row's own pages are fetched; the
  rest of a buffer is masked (and zeroed once, so it is finite).
- **Each head against its own KV head only.** Rows of a page interleave
  the KV heads (row = slot·KV + kv), so KV head k's keys are a
  sublane-STRIDED read of the block buffer; Mosaic strides 32-bit sublanes
  only, so bf16 / int8 rows are read as 32-bit words and shifted apart
  (``_load_rows``). The query tile arrives ``[TQ·Hp, hd]`` (row = token·Hp
  + head, times NB lane rows; Hp = heads padded to the sublane packing,
  which makes a tile's data-dependent DMA offset provably aligned) and is
  regrouped once per tile, by the same strided read, into ``[KV, G·TQ,
  kd]`` (kd = NB·128): per KV head one ``[G·TQ, kd] x [kd, 512]`` score
  matmul and one ``[G·TQ, 512] x [512, vd]`` P·V, no group mask and no column another head owns. G is whatever
  ``H // KV`` is (4 Mistral, 7 Qwen2, 1 MHA, 8 and 16 MiMo-V2's window
  and full layers); it pads only until the small tile's rows fill a
  packed sublane tile. A wide tile holds at most ``_WIDE_ROWS`` score
  rows, so its tokens are 128 up to G = 8 and 64 at G = 16: the MXU's rows
  are as full, and the f32 score temporaries stay a size the compiler was
  shown to place.
- **MXU inputs in the stored dtype, f32 accumulation.** bf16 pages meet a
  bf16 q as they are, int8 pages enter as q's dtype (exact for int8), f32
  pages (the CPU tests) stay f32. The online-softmax state (m, l, acc)
  is f32 in VMEM scratch, per KV head, the row statistics lane-replicated.

Sliding windows and attention sinks match the decode kernel. int8 KV pages
dequantize IN the kernel: the per-(slot, head) f32 scales of one layer ride
as constant-block VMEM operands ``[rows, KV, 128]`` (a row = 128 // bs
consecutive pages' scales per KV head along the lanes), rebased per layer
via ``scale_slot_base``; as a block's pages are fetched, each page's scales
are rotated to the lanes of its keys' score columns. k-scales multiply the
scores, v-scales fold into p before the PV matmul, so int8 pages cost the
same two DMAs per page as bf16 at half the bytes. The only degrades to
:func:`ragged_attention_xla` are page rows that are not one 128-lane row
(a narrow head stored as it is — a model of 64-wide heads that does not
set ``ModelConfig.kv_lane_pad``; a V head wider than 128; a wider K head
is stored as lane rows, ``ModelConfig.k_cache_dim``) and scale tables past
the VMEM budget — both static shape facts the engine counts and logs
(``dynamo_ragged_fallback_total``, reasons ``lane_align`` and
``scale_budget``), never a silent data-dependent branch. ``DYN_RAGGED_ORACLE=1`` routes to the XLA oracle
explicitly (bench/test A/B arms only).

Trace + lowering of a step program is paid on every start (sixteen
programs in the warm-up), so the kernel's trace is held small: loops over
tiles, blocks and KV heads are ``fori_loop``s (strided reads take a traced
start), index arithmetic is one equation an op (``_div`` / ``_rem``), and
the launch is jitted so programs that share a (T, R, W) trace it once
(tests/test_ragged.py bounds the equation count).
"""

from __future__ import annotations

import functools
import math
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
#: query tile of a short row (decode, speculative verify); a row with more
#: tokens than this takes the wide tile (the engine counts those rows)
NARROW_TILE = 8
#: the most query tokens of one tile of a row longer than the small tile
_WIDE_TILE = 128
#: the most score rows (G · query tokens) of one KV head's wide tile
_WIDE_ROWS = 1024
#: keys of one streamed, double-buffered block
_KEY_BLOCK = 512


def ragged_pallas_supported(num_kv_heads: int, k_dim: int,
                            v_dim: int) -> bool:
    """Pages DMA as [rows, 128] tiles and a head's rows are a sublane-
    strided read, which Mosaic takes of 128-lane buffers only: the STORED
    row of K and of V must each be one lane row (``k_dim`` is the width of
    a stored row: 128 for a wide head kept as lane rows, and for a narrow
    one padded to a row under ``ModelConfig.kv_lane_pad``). Narrow heads
    stored as they are leave the kernel under ``lane_align``."""
    return k_dim == _LANE and v_dim == _LANE


def _wide_tile(G: int) -> int:
    """Query tokens of a wide tile for a KV head shared by ``G`` heads."""
    fit = 1 << ((_WIDE_ROWS // G).bit_length() - 1)  # a power of two
    return max(NARROW_TILE, min(_WIDE_TILE, fit))


def _scale_table_shape(num_kv_heads: int, sc_slots: int, block_size: int):
    """VMEM shape of one int8 scale table: [rows, KV (padded to the f32
    sublane tile), 128], a row holding the scales of ``128 // bs``
    consecutive pages, per KV head, along its lanes."""
    pages = -(-sc_slots // block_size)
    return (-(-pages // max(1, _LANE // block_size)),
            -(-num_kv_heads // 8) * 8, _LANE)


def ragged_int8_kernel_supported(num_kv_heads: int, sc_slots: int,
                                 block_size: int = 16) -> bool:
    """True when the per-layer k/v scale tables fit the VMEM-resident
    budget: two tables, each double-buffered by the Pallas pipeline (a
    constant block index is fetched once but still gets two buffers), and a
    page's scales fill a whole fraction of a lane row. ``sc_slots`` is the
    PER-LAYER slot count (the layer-stacked caller passes one layer's slice
    + scale_slot_base)."""
    if _LANE % block_size:
        return False
    scale_bytes = 2 * 2 * 4 * math.prod(
        _scale_table_shape(num_kv_heads, sc_slots, block_size))
    return scale_bytes <= int(os.environ.get("DYN_KV_SCALE_VMEM_BYTES",
                                             40 << 20))


def _div(x, n: int):
    """``x // n`` and ``x % n`` of a traced NON-NEGATIVE int in one equation
    each (the Python operators trace a dozen for the sign they guard)."""
    return jax.lax.div(x, jnp.asarray(n, x.dtype))


def _rem(x, n: int):
    return jax.lax.rem(x, jnp.asarray(n, x.dtype))


def _lane_row(row, nb: int, c: int):
    """Row of lane row ``c`` of the ``nb`` that a (traced) ``row`` of wide
    heads takes; one lane row a head traces nothing."""
    return row if nb == 1 else row * nb + c


def _load_rows(ref, start, count: int, stride: int):
    """Rows ``start, start+stride, ...`` (``count`` of them) of a 2-D VMEM
    ref — the sublane-strided read that takes ONE head's rows out of a
    buffer whose rows interleave heads. Mosaic strides 32-bit sublanes only,
    so a packed dtype (bf16: 2 rows a word, int8: 4) is read as uint32 words
    and the wanted row shifted out of each; those come back float32, exact
    (a bf16 is the top half of an f32). ``stride`` is a multiple of the
    packing; ``start`` may be traced."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pack = 4 // ref.dtype.itemsize
    if pack == 1:
        return ref[pl.ds(start, count, stride=stride), :]
    words = ref.bitcast(jnp.uint32)[
        pl.ds(_div(start, pack), count, stride=stride // pack), :]
    sub = _rem(start, pack).astype(jnp.uint32)
    if pack == 2:
        return pltpu.bitcast((words >> (16 * sub)) << 16, jnp.float32)
    return (pltpu.bitcast(words << (24 - 8 * sub), jnp.int32)
            >> 24).astype(jnp.float32)


def _ragged_kernel(rows3_ref, block_tables_ref, win_ref,  # scalar prefetch
                   sbase_ref,  # scalar pf; sbase = scale-table page base
                   sink_ref,   # [KV, Gp, 128] f32 VMEM, lane-replicated
                   q_ref,      # [Tpad·Hp·NB, 128] HBM (softmax scale folded)
                   kcache_ref, vcache_ref,  # [pages, bs·KV·NB | bs·KV, 128]
                   *rest,  # [ksc_ref, vsc_ref ([rows, KVp, 128] VMEM),]
                           # out_ref, scratch...
                   bs: int, tiles: tuple, KV: int, G: int, Gp: int, Hp: int,
                   NB: int, PB: int, has_sink: bool, quant: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        ksc_ref, vsc_ref, out_ref, *scratch = rest
        *scratch, ksb, vsb = scratch
    else:
        out_ref, *scratch = rest
    (qbuf, qg, m_s, l_s, acc_s, o32, obuf, kbuf, vbuf, qo_sem,
     dma_sem) = scratch

    r = pl.program_id(0)
    q_start = rows3_ref[r, 0]
    q_len = rows3_ref[r, 1]
    kv_len = rows3_ref[r, 2]
    win = win_ref[0]
    hd = vbuf.shape[2]   # width of a V row, so of the output
    mm = qg.dtype        # MXU input dtype (module docstring)
    N = bs * KV          # rows of one V page: row = slot·KV + kv head
    BK = PB * bs         # keys of one streamed block
    # a KV head's keys are every KV-th row; where KV is not a multiple of
    # the page dtype's packing they come as ``pieces`` interleaved reads,
    # and the block's keys are scored in that (piece, page, slot) order
    pack = 4 // kbuf.dtype.itemsize
    pieces = pack // math.gcd(KV, pack)
    ppr = max(1, _LANE // bs)  # pages per lane row of a scale table

    @pl.when(r == 0)
    def _():
        # pages past a row's end are never fetched; what the buffers hold
        # there is masked, but must be finite (0 · NaN = NaN in P·V)
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        if quant:
            ksb[...] = jnp.zeros(ksb.shape, ksb.dtype)
            vsb[...] = jnp.zeros(vsb.shape, vsb.dtype)

    def page_copies(w, b):
        blk = block_tables_ref[r, w]
        slot = _rem(b, 2)
        dst = pl.ds(pl.multiple_of((w - b * PB) * N, N), N)
        kdst = dst if NB == 1 else pl.ds(
            pl.multiple_of((w - b * PB) * N * NB, N * NB), N * NB)
        return (pltpu.make_async_copy(kcache_ref.at[blk], kbuf.at[slot, kdst],
                                      dma_sem.at[slot, 0]),
                pltpu.make_async_copy(vcache_ref.at[blk], vbuf.at[slot, dst],
                                      dma_sem.at[slot, 1]))

    def place_scales(w, b):
        # this page's scales go where its keys' score columns will be: one
        # lane rotation of the table row that holds them, per piece
        page = block_tables_ref[r, w] - sbase_ref[0]
        width = bs // pieces
        lane = jax.lax.broadcasted_iota(jnp.int32, ksb.shape[2:], 1)
        for j in range(pieces):
            dest = j * (BK // pieces) + (w - b * PB) * width
            src = _rem(page, ppr) * bs + j * width
            at = _rem(dest, _LANE)
            here = (lane >= at) & (lane < at + width)
            for table, buf in ((ksc_ref, ksb), (vsc_ref, vsb)):
                moved = pltpu.roll(table[_div(page, ppr)],
                                   _rem(at - src + _LANE, _LANE), 1)
                row = (_rem(b, 2), _div(dest, _LANE))
                buf[row] = jnp.where(here, moved, buf[row])

    def scale_row(buf, b, k):
        rows = [buf[_rem(b, 2), i, pl.ds(k, 1), :]
                for i in range(buf.shape[1])]
        return (rows[0] if len(rows) == 1
                else jnp.concatenate(rows, axis=1))[:, :BK]

    def head_rows(buf, b, k, nb=1):
        """[BK, nb·128]: KV head ``k``'s rows of the block in ``buf``, its
        ``nb`` lane rows side by side."""
        got = [[_load_rows(buf.at[_rem(b, 2)], _lane_row(j * KV + k, nb, c),
                           BK // pieces, KV * nb * pieces)
                for c in range(nb)] for j in range(pieces)]
        got = [g[0] if nb == 1 else jnp.concatenate(g, axis=1) for g in got]
        return (got[0] if pieces == 1
                else jnp.concatenate(got, axis=0)).astype(mm)

    def lanes(x, n):  # a lane-replicated [M, 128] statistic, n lanes wide
        return x if n == _LANE else jnp.tile(x, (1, n // _LANE))

    def run_tiles(TQ: int):
        M = Gp * TQ          # score rows of one KV head: row = g·TQ + token
        span = TQ * Hp       # rows of the tile in out: token·Hp + head
        tok_of_row = _rem(jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0), TQ)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, BK), 1)
        if pieces == 1:
            key_of_col = col
        else:
            per, width = BK // pieces, bs // pieces
            key_of_col = (_div(_rem(col, per), width) * bs
                          + _rem(col, width) * pieces + _div(col, per))

        def tile_body(t, carry):
            # the last tile is moved back to END at the row's end, so a row
            # of at least TQ tokens never computes past itself
            off = jnp.minimum(t * TQ, jnp.maximum(q_len - TQ, 0))
            at = pl.ds(pl.multiple_of((q_start + off) * Hp, Hp), span)
            # q: NB lane rows a (token, head)
            q_at = at if NB == 1 else pl.ds(pl.multiple_of(
                (q_start + off) * Hp * NB, Hp * NB), span * NB)
            fetch = pltpu.make_async_copy(
                q_ref.at[q_at], qbuf.at[pl.ds(0, span * NB)], qo_sem.at[0])
            fetch.start()

            # positions of this tile: pos0 .. pos0+TQ-1 (chunk tokens occupy
            # the tail of the kv range — the engine's packing contract)
            pos0 = kv_len - q_len + off
            hi_pos = jnp.minimum(pos0 + TQ - 1, kv_len - 1)
            num_pages = _div(hi_pos, bs) + 1
            # sliding window: the EARLIEST key any tile position can see is
            # pos0 - win + 1; pages wholly before it are never fetched
            first_key = jnp.where(win > 0, jnp.maximum(pos0 - win + 1, 0), 0)
            start_page = _div(first_key, bs)
            b0, nb = _div(start_page, PB), _div(num_pages + PB - 1, PB)

            def for_pages(b, fn):
                jax.lax.fori_loop(jnp.maximum(b * PB, start_page),
                                  jnp.minimum((b + 1) * PB, num_pages),
                                  lambda w, c: (fn(w), c)[1], 0)

            def start_block(b):
                def one(w):
                    for cp in page_copies(w, b):
                        cp.start()
                    if quant:
                        place_scales(w, b)
                for_pages(b, one)

            start_block(b0)
            fetch.wait()

            def regroup(k, c):
                # the G heads of KV head k, each a strided read of the tile
                heads = [[_load_rows(qbuf, _lane_row(k * G + g, NB, c), TQ,
                                     Hp * NB)
                          for c in range(NB)] for g in range(G)]
                heads = [h[0] if NB == 1 else jnp.concatenate(h, axis=1)
                         for h in heads]
                heads += [jnp.zeros_like(heads[0])] * (Gp - G)
                qg[k, pl.ds(0, M)] = jnp.concatenate(heads).astype(mm)
                if has_sink:
                    # sink slot: seeds the online softmax, adds no value
                    sk = sink_ref[k]
                    m_s[k, pl.ds(0, M)] = jnp.concatenate(
                        [jnp.broadcast_to(sk[g:g + 1], (TQ, _LANE))
                         for g in range(Gp)])
                    l_s[k, pl.ds(0, M)] = jnp.ones((M, _LANE), jnp.float32)
                else:
                    m_s[k, pl.ds(0, M)] = jnp.full((M, _LANE), _NEG,
                                                   jnp.float32)
                    l_s[k, pl.ds(0, M)] = jnp.zeros((M, _LANE), jnp.float32)
                acc_s[k, pl.ds(0, M)] = jnp.zeros((M, hd), jnp.float32)
                return c

            jax.lax.fori_loop(0, KV, regroup, 0)
            q_pos = pos0 + tok_of_row

            def block_body(b, c):
                for_pages(b, lambda w: [cp.wait() for cp in page_copies(w, b)])

                @pl.when(b + 1 < nb)
                def _():
                    start_block(b + 1)

                key_pos = b * BK + key_of_col
                mask = (key_pos <= q_pos) & (key_pos < kv_len) & (
                    (win <= 0) | (key_pos > q_pos - win))

                def head_body(k, c2):
                    rows = (k, pl.ds(0, M))
                    s = jax.lax.dot_general(
                        qg[rows], head_rows(kbuf, b, k, NB),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [M, BK]
                    if quant:
                        s = s * scale_row(ksb, b, k)
                    s = jnp.where(mask, s, _NEG)
                    m_old = m_s[rows]
                    m_new = jnp.maximum(m_old,
                                        jnp.max(s, axis=1, keepdims=True))
                    corr = jnp.exp(m_old - m_new)
                    # a row with no key yet holds m = _NEG and p = 1 here;
                    # its first real block's corr = 0 wipes that
                    p = jnp.exp(s - m_new[:, :1])
                    l_s[rows] = l_s[rows] * corr + jnp.sum(
                        p, axis=1, keepdims=True)
                    m_s[rows] = m_new
                    if quant:
                        p = p * scale_row(vsb, b, k)
                    pv = jax.lax.dot_general(
                        p.astype(mm), head_rows(vbuf, b, k),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [M, hd]
                    acc_s[rows] = acc_s[rows] * lanes(corr, hd) + pv
                    return c2

                jax.lax.fori_loop(0, KV, head_body, 0)
                return c

            jax.lax.fori_loop(b0, nb, block_body, 0)

            def scatter(k, c):
                rows = (k, pl.ds(0, M))
                o = acc_s[rows] / lanes(jnp.maximum(l_s[rows], 1e-30), hd)
                for g in range(G):
                    o32[pl.ds(k * G + g, TQ, stride=Hp), :] = (
                        o[g * TQ:(g + 1) * TQ])
                return c

            jax.lax.fori_loop(0, KV, scatter, 0)
            obuf[pl.ds(0, span)] = o32[pl.ds(0, span)].astype(obuf.dtype)
            # tile out: a row shorter than its tile overruns into the NEXT
            # rows' region, which their own (later, sequential) grid steps
            # overwrite; the last row's overrun lands in the output padding
            put = pltpu.make_async_copy(obuf.at[pl.ds(0, span)],
                                        out_ref.at[at], qo_sem.at[1])
            put.start()
            put.wait()
            return carry

        jax.lax.fori_loop(0, _div(q_len + TQ - 1, TQ), tile_body, 0)

    # the tile is chosen from the row: a decode / verify row keeps the small
    # tile, a prompt chunk fills the MXU's rows and streams its prefix once
    # per 128 tokens
    narrow, *wide = tiles
    if wide:
        pl.when(q_len > narrow)(lambda: run_tiles(wide[0]))
    pl.when((q_len > 0) & (q_len <= narrow))(lambda: run_tiles(narrow))


def ragged_paged_attention(q, k_cache, v_cache, block_tables, rows3, *,
                           block_size: int, interpret: bool = False,
                           window=None, sinks=None, tq: int = NARROW_TILE,
                           k_scales=None, v_scales=None,
                           scale_slot_base=None):
    """Ragged paged attention over a packed token batch. See module
    docstring for the contract.

    ``tq`` is the query tile of a short row (``q_len <= tq``: decode,
    speculative verify); longer rows take :func:`_wide_tile` tokens a tile.

    ``k_scales``/``v_scales`` [sc_slots, KV] f32 (int8 caches): pages are
    int8 and dequantize IN the kernel — scales go VMEM-resident, fetched
    once for the whole grid.
    ``scale_slot_base`` (traced scalar, default 0): slot offset of the
    scale tables relative to the page cache — layer-stacked callers pass
    one layer's scale slice plus ``lidx·slots`` so the VMEM budget is
    per-layer, not ×L.

    Routes to :func:`ragged_attention_xla` only for page rows off the
    lane multiple, scale tables past the VMEM budget, or the explicit
    ``DYN_RAGGED_ORACLE=1`` bench/test oracle switch."""
    KV = v_cache.shape[1]
    bs = block_size
    quant = k_scales is not None
    if (not ragged_pallas_supported(KV, k_cache.shape[2], v_cache.shape[2])
            or (quant and not ragged_int8_kernel_supported(
                KV, k_scales.shape[0], bs))
            or os.environ.get("DYN_RAGGED_ORACLE") == "1"):
        return ragged_attention_xla(
            q, k_cache, v_cache, block_tables, rows3, block_size=bs,
            window=window, sinks=sinks, k_scales=k_scales,
            v_scales=v_scales, scale_slot_base=scale_slot_base)
    return _ragged_call(
        q, k_cache, v_cache, block_tables, rows3,
        jnp.asarray(0 if window is None else window, jnp.int32),
        jnp.asarray(0 if scale_slot_base is None else scale_slot_base,
                    jnp.int32),
        sinks, k_scales, v_scales, bs=bs, tq=tq,
        interpret=interpret or kernel_interpret_mode())


@functools.partial(jax.jit, static_argnames=("bs", "tq", "interpret"))
def _ragged_call(q, k_cache, v_cache, block_tables, rows3, window,
                 scale_slot_base, sinks, k_scales, v_scales, *, bs: int,
                 tq: int, interpret: bool):
    """The kernel's launch. Jitted, so the step programs that share a
    (T, R, W) — the mixed and the decode-only variant of one token bucket —
    trace the kernel once between them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, hd = q.shape
    slots, KV, vd = v_cache.shape
    NB = k_cache.shape[1] // KV  # lane rows of one K head
    kd = NB * _LANE
    G = H // KV
    R, W = block_tables.shape
    has_sink = sinks is not None
    quant = k_scales is not None
    # MXU inputs in the stored dtype: bf16 pages meet a bf16 q as they are,
    # int8 pages enter as q's dtype (exact), f32 pages (CPU tests) stay f32
    mm = (jnp.promote_types(q.dtype, k_cache.dtype)
          if jnp.issubdtype(k_cache.dtype, jnp.floating) else q.dtype)
    # no row of a batch of <= tq tokens is longer than the small tile
    tiles = (tq, _wide_tile(G)) if T > tq else (tq,)
    TQ = tiles[-1]
    # heads pad to the sublane packing of q's dtype (8 rows of 32 bits) so
    # a tile's rows start on a packed row; a KV head's group pads until the
    # small tile's score rows do
    sub = 8 * max(1, 4 // q.dtype.itemsize)
    Hp = -(-H // sub) * sub
    Gp = G
    while Gp * tq % (8 * max(1, 4 // jnp.dtype(mm).itemsize)):
        Gp += 1
    PB = max(1, min(_KEY_BLOCK // bs, W))  # pages of one streamed block

    # fold the softmax scale; pad by one tile so fixed-size tile DMAs never
    # overrun. Rows are (token, head): a tile starts on a multiple of Hp,
    # which is all Mosaic needs to take a data-dependent DMA offset.
    qs = q * jnp.asarray(1.0 / np.sqrt(hd), q.dtype)
    qs = jnp.pad(qs, ((0, TQ), (0, Hp - H), (0, kd - hd))).reshape(-1, _LANE)
    sink_in = jnp.zeros((KV, Gp), jnp.float32)
    if has_sink:
        sink_in = jnp.pad(sinks.astype(jnp.float32).reshape(KV, G),
                          ((0, 0), (0, Gp - G)))
    sink_in = jnp.broadcast_to(sink_in[..., None], (KV, Gp, _LANE))

    kernel = functools.partial(
        _ragged_kernel, bs=bs, tiles=tiles, KV=KV, G=G, Gp=Gp, Hp=Hp, NB=NB,
        PB=PB, has_sink=has_sink, quant=quant)
    in_specs = [
        pl.BlockSpec((KV, Gp, _LANE), lambda r, *_: (0, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.HBM),  # q
        pl.BlockSpec(memory_space=pltpu.HBM),  # k pages
        pl.BlockSpec(memory_space=pltpu.HBM),  # v pages
    ]
    # page view [pages, bs·rows, 128]: the same bytes as [slots, rows, 128]
    # (a page's slots are consecutive), so XLA passes the cache through
    # without a relayout copy, and a page is one leading-dim index
    operands = [sink_in, qs,
                k_cache.reshape(slots // bs, bs * KV * NB, _LANE),
                v_cache.reshape(slots // bs, bs * KV, vd)]
    scratch = [
        pltpu.VMEM((TQ * Hp * NB, _LANE), q.dtype),   # qbuf: the query tile
        pltpu.VMEM((KV, Gp * TQ, kd), mm),            # qg: it, by KV head
        pltpu.VMEM((KV, Gp * TQ, _LANE), jnp.float32),  # m (lane-replicated)
        pltpu.VMEM((KV, Gp * TQ, _LANE), jnp.float32),  # l
        pltpu.VMEM((KV, Gp * TQ, vd), jnp.float32),   # acc
        pltpu.VMEM((TQ * Hp, vd), jnp.float32),       # o32: the output tile
        pltpu.VMEM((TQ * Hp, vd), q.dtype),           # obuf: it, as stored
        pltpu.VMEM((2, PB * bs * KV * NB, _LANE), k_cache.dtype),  # kbuf
        pltpu.VMEM((2, PB * bs * KV, vd), v_cache.dtype),  # vbuf
        pltpu.SemaphoreType.DMA((2,)),                # q-in / out tiles
        pltpu.SemaphoreType.DMA((2, 2)),              # block pipeline
    ]
    if quant:
        # constant block index → Pallas fetches the scale tables once and
        # keeps them resident across the whole (R,) grid. A table row holds
        # 128 // bs consecutive pages' scales per KV head along its lanes,
        # a page's in the order its keys are scored (piece, slot)
        sc_slots = k_scales.shape[0]
        rows, KVp, _ = shape = _scale_table_shape(KV, sc_slots, bs)
        pack = 4 // k_cache.dtype.itemsize
        pieces = pack // math.gcd(KV, pack)

        def table(s):
            s = s.astype(jnp.float32).reshape(sc_slots // bs, bs, KV)
            s = jnp.pad(s, ((0, rows * (_LANE // bs) - sc_slots // bs),
                            (0, 0), (0, KVp - KV)))
            s = s.reshape(rows, _LANE // bs, bs // pieces, pieces, KVp)
            return s.transpose(0, 4, 1, 3, 2).reshape(shape)

        in_specs += [pl.BlockSpec(shape, lambda r, *_: (0, 0, 0))] * 2
        operands += [table(k_scales), table(v_scales)]
        scratch += [pltpu.VMEM((2, -(-PB * bs // _LANE), KVp, _LANE),
                               jnp.float32)] * 2      # ksb, vsb: 2 blocks'
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(((T + TQ) * Hp, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ragged_paged_attention",
    )(rows3.astype(jnp.int32), block_tables.astype(jnp.int32),
      window.reshape(1), _div(scale_slot_base, bs).reshape(1), *operands)
    return out.reshape(T + TQ, Hp, vd)[:T, :H]


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
    KV = v_cache.shape[1]
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
    # a wide K head is stored as lane rows, zero lanes past hd
    k = k_cache[slot_idx].reshape(R, Tk, KV, -1)[..., :hd].astype(
        jnp.float32)                                     # [R, Tk, KV, hd]
    v = v_cache[slot_idx].astype(jnp.float32)            # [R, Tk, KV, vd]
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
    return o.reshape(T, H, v.shape[-1]).astype(q.dtype)
