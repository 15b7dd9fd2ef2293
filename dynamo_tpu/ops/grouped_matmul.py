"""Pallas TPU grouped matrix product for an expert layer: rows sorted by
expert, one weight matrix per expert, work that follows the rows present.

Contract:
  x          [M, k]       rows grouped by expert, every group padded to a
                          whole number of ``ROW_TILE``-row tiles (padding
                          rows are zero), so a row tile belongs to ONE expert
  w          [L, E, k, n] the held experts' weights, ALL layers of a stack
  layer      [] int32     whose layer's experts: the kernel picks its blocks
                          out of the stack itself — a layer sliced out by
                          the scan would be copied whole (E·k·n) before
                          every launch, touched experts or not
  tile_group [M // ROW_TILE] int32   expert of each row tile
  num_tiles  [] int32     row tiles in use (the groups' tiles, packed first)
  → out      [M, n]       rows of tiles past ``num_tiles`` are NOT written:
                          whoever reads the result back reads only rows of
                          pairs (ops/moe_combine.py never fetches another).
                          ``rows_apart`` (the down launch): ``[M, n // 128,
                          128]``, the same rows, each its own run of tiles

One grid bound is ``num_tiles`` itself, a traced value: a step that routed
40 pairs to 12 experts launches 12 row tiles and reads 12 experts' weights,
not the static buffer's worst case and not the experts nobody chose. That
worst case (every token routing all its choices here) only sizes the buffer,
so the layer stays dropless.

What crosses HBM in one launch: each touched expert's matrix ONCE, however
many row tiles its rows fill, and each row tile of the result once. The
blocks and the grid's order follow the launch's static shape (``_blocks``).
Where the buffer has two row tiles an expert or more (a prompt's chunk), the
contraction is not split: a weight block is ``[k, tn]``, ``tn`` all of ``n``
where ``[k, n]`` fits ``_W_BLOCK_BYTES`` (Granite-4.0-H's 4096 x 768: 6.3
MB), else the widest multiple of 128 that divides ``n`` and fits
(MiMo-V2.5's 4096 x 2048: ``tn`` 1024, 8.4 MB). The grid is ``(n // tn,
num_tiles, 1)``: the row tiles are the fastest of the axes the block's index
depends on, and the dispatch lays an expert's tiles next to each other
(``tile_group`` does not decrease), so from one row tile to the next of the
same expert the index stays and the pipeline keeps the block it has in VMEM;
a row tile of ``x`` is read ``n // tn`` times. What the pipeline does not
hide: it looks one grid step ahead, so the next expert's block has the
present expert's last tile to travel behind, and where the block's copy is
longer than a tile's product (Granite's: 7.7 us against 4.1) the launch
waits out the rest at every change of expert. Where the buffer is smaller
(a decode step: an expert has one tile, there is nothing to keep, and the
launch is bound by the copies) the blocks are ``_TILE`` square, 2 MB at
bf16 (a dim that ``_TILE`` does not divide takes the largest multiple of
128 under it that does: LFM2-24B-A2B's 1,536-wide experts 768, so 2048 x
1536 is cut 1024 x 768 and 1536 x 2048 768 x 1024; a smaller dim whole:
Granite's 768), summed in a float32 scratch, and the grid is ``(num_tiles,
n // tn, k // tk)``, a tile's blocks together: the first block is there
sooner, the last product is shorter than with a whole matrix, a row tile of ``x`` stays
while its column blocks pass, and the launch fits the compiler's default
VMEM limit, so a decode step's buffers keep their room there (on the chip
a whole-matrix block read within 2% a launch either way, 0.8% more a step).

Shaped after the megablox ``gmm`` (jax.experimental.pallas.ops.tpu), less
what tile-aligned groups make unnecessary: no tile straddles two groups,
so there is no store mask and no group-offset table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.paged_attention import kernel_interpret_mode

#: rows of one tile: the MXU's own height on a v5e
ROW_TILE = 128
_LANES = 128
#: the largest weight block [k, tn]; the pipeline holds two of them
_W_BLOCK_BYTES = 8 * 2 ** 20
#: contraction and output tile of a launch that keeps no block: 2 MB at bf16
_TILE = 1024
#: VMEM a launch may use beyond its own blocks (the compiler's scratch)
_VMEM_ROOM_BYTES = 2 * 2 ** 20
#: the compiler's own limit on a v5e, which a launch that fits it keeps
_VMEM_DEFAULT_BYTES = 16 * 2 ** 20


def _kernel(tile_group_ref, layer_ref, x_ref, w_ref, out_ref, *acc):
    from jax.experimental import pallas as pl

    del tile_group_ref, layer_ref  # read by the index maps only
    part = jnp.dot(x_ref[...], w_ref[...],
                   preferred_element_type=jnp.float32)
    if not acc:  # the contraction in one block; rows apart: [rows, C, 128]
        out_ref[...] = part.astype(out_ref.dtype).reshape(out_ref.shape)
        return
    (acc_ref,), kk = acc, pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    acc_ref[...] += part

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype).reshape(
            out_ref.shape)


def _tile(dim: int, want: int) -> int:
    """The block of a ``dim`` that is to be cut into blocks of ``want``:
    ``want`` where it divides ``dim``, all of a smaller ``dim``, else the
    largest multiple of 128 under ``want`` that divides it (1,536: 768)."""
    if dim % want == 0:
        return want
    if dim < want:
        return dim  # a block equal to the whole dim needs no alignment
    fits = [t for t in range(128, want, 128) if dim % t == 0]
    if not fits:
        raise ValueError(f"dim {dim} has no tile that is a multiple of 128, "
                         f"divides it and is at most {want}")
    return fits[-1]


def _n_tile(k: int, n: int, itemsize: int) -> int:
    """Output columns of a weight block that holds the whole contraction."""
    if k * n * itemsize <= _W_BLOCK_BYTES:
        return n  # a block equal to the whole dim needs no alignment
    fits = [tn for tn in range(128, n, 128)
            if n % tn == 0 and k * tn * itemsize <= _W_BLOCK_BYTES]
    if not fits:
        raise ValueError(f"no block of a [{k}, {n}] matrix that is a "
                         f"multiple of 128 columns, divides {n} and fits "
                         f"{_W_BLOCK_BYTES} bytes")
    return fits[-1]


def _blocks(tiles: int, experts: int, k: int, n: int,
            itemsize: int) -> tuple[int, int, bool]:
    """``(tk, tn, keep)`` of a launch whose buffer has ``tiles`` row tiles
    for ``experts`` experts; ``keep``: an expert's block stays over its row
    tiles. See the module docstring."""
    if tiles < 2 * experts:
        return _tile(k, _TILE), _tile(n, _TILE), False
    return k, _n_tile(k, n, itemsize), True


@functools.partial(jax.jit, static_argnames=("tk", "tn", "keep", "apart",
                                             "tag", "interpret"))
def _call(x, w, tile_group, num_tiles, layer, *, tk: int, tn: int,
          keep: bool, apart: bool, tag: str, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, k = x.shape
    n = w.shape[3]
    # two weight blocks, two row tiles in and out, the float32 product. No
    # more than it needs: what a launch reserves beyond the compiler's
    # default, XLA cannot use to keep a decode step's buffers in VMEM
    vmem = (2 * (tk * tn * w.dtype.itemsize
                 + ROW_TILE * (tk + tn) * x.dtype.itemsize)
            + ROW_TILE * tn * 4 + _VMEM_ROOM_BYTES)

    def at(index):  # of (row tile, column block, contraction block)
        if keep:    # the row tiles under each block of columns
            return lambda j, i, kk, tg, ly: index(i, j, kk, tg, ly)
        return index

    # rows apart: a row of the result is [n // 128, 128], its own tiles (a
    # width that is no multiple of 128 lanes, a test's: [1, n])
    lanes = _LANES if tn % _LANES == 0 else tn
    cols = (lambda rows, c: (rows, c // lanes, lanes)) if apart else (
        lambda rows, c: (rows, c))

    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(cols(M, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=((n // tn, num_tiles, 1) if keep else
                  (num_tiles, n // tn, k // tk)),
            in_specs=[
                pl.BlockSpec((ROW_TILE, tk),
                             at(lambda i, j, kk, tg, ly: (i, kk))),
                pl.BlockSpec((None, None, tk, tn), at(
                    lambda i, j, kk, tg, ly: (ly[0], tg[i], kk, j))),
            ],
            out_specs=pl.BlockSpec(
                cols(ROW_TILE, tn),
                at(lambda i, j, kk, tg, ly: (i, j, 0) if apart else (i, j))),
            scratch_shapes=([] if keep else
                            [pltpu.VMEM((ROW_TILE, tn), jnp.float32)]),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=(
                vmem if vmem > _VMEM_DEFAULT_BYTES else None)),
        interpret=interpret,
        name="moe_grouped_matmul" + tag,
    )(tile_group, layer.reshape(1), x, w)


def grouped_matmul(x, w, tile_group, num_tiles, layer=0, tag: str = "",
                   rows_apart: bool = False):
    """See the module docstring for the contract; ``w`` [E, k, n] is a
    stack of one layer. ``tag`` joins the op's name in the device trace
    (``moe_grouped_matmul<tag>``): which launch of a step this is, for a
    reader that holds its time against its own work. ``rows_apart``: the
    result is ``[M, n // 128, 128]`` (``[M, 1, n]`` where n is no multiple
    of 128), the same rows, each its own run of tiles in memory, so that
    ops/moe_combine.py can fetch one row with one copy."""
    assert x.shape[0] % ROW_TILE == 0, x.shape
    tk, tn, keep = _blocks(x.shape[0] // ROW_TILE, w.shape[-3], x.shape[1],
                           w.shape[-1], w.dtype.itemsize)
    return _call(x, w if w.ndim == 4 else w[None],
                 tile_group.astype(jnp.int32),
                 jnp.asarray(num_tiles, jnp.int32),
                 jnp.asarray(layer, jnp.int32),
                 tk=tk, tn=tn, keep=keep, apart=rows_apart, tag=tag,
                 interpret=kernel_interpret_mode())
