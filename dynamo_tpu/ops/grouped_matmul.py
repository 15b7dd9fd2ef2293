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
  → out      [M, n]       rows of tiles past ``num_tiles`` are NOT written

The grid's leading bound is ``num_tiles`` itself, a traced value: a step
that routed 40 pairs to 12 experts launches 12 row tiles and reads 12
experts' weights, not the static buffer's worst case and not the experts
nobody chose. That worst case (every token routing all its choices here)
only sizes the buffer, so the layer stays dropless. An expert whose rows
fill more than one tile has its weights read once per tile.

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
#: contraction and output tile: 2 MB weight blocks at bf16, double-buffered
_K_TILE = 1024
_N_TILE = 1024


def _kernel(tile_group_ref, layer_ref, x_ref, w_ref, out_ref, acc_ref):
    from jax.experimental import pallas as pl

    del tile_group_ref, layer_ref  # read by the index maps only
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tile(dim: int, want: int) -> int:
    if dim % want == 0:
        return want
    if dim < want:
        return dim  # a block equal to the whole dim needs no alignment
    raise ValueError(f"dim {dim} is not a multiple of its tile {want}")


@functools.partial(jax.jit, static_argnames=("tag", "interpret"))
def _call(x, w, tile_group, num_tiles, layer, *, tag: str, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, k = x.shape
    n = w.shape[3]
    tk, tn = _tile(k, _K_TILE), _tile(n, _N_TILE)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((M, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(num_tiles, n // tn, k // tk),
            in_specs=[
                pl.BlockSpec((ROW_TILE, tk),
                             lambda i, j, kk, tg, ly: (i, kk)),
                pl.BlockSpec((None, None, tk, tn),
                             lambda i, j, kk, tg, ly: (ly[0], tg[i], kk, j)),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, tn),
                                   lambda i, j, kk, tg, ly: (i, j)),
            scratch_shapes=[pltpu.VMEM((ROW_TILE, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_grouped_matmul" + tag,
    )(tile_group, layer.reshape(1), x, w)


def grouped_matmul(x, w, tile_group, num_tiles, layer=0, tag: str = ""):
    """See the module docstring for the contract; ``w`` [E, k, n] is a
    stack of one layer. ``tag`` joins the op's name in the device trace
    (``moe_grouped_matmul<tag>``): which launch of a step this is, for a
    reader that holds its time against its own work."""
    assert x.shape[0] % ROW_TILE == 0, x.shape
    return _call(x, w if w.ndim == 4 else w[None],
                 tile_group.astype(jnp.int32),
                 jnp.asarray(num_tiles, jnp.int32),
                 jnp.asarray(layer, jnp.int32), tag=tag,
                 interpret=kernel_interpret_mode())
