"""Pallas TPU combine of the held-experts layer: the gate-weighted sum over
a token's K choices, reading only the rows the routing wrote.

Contract:
  yb     [M, C, 128]  the down launch's result, one buffer row of D = C·128
                      elements a pair (a test's narrow D: [M, 1, D]), ROWS
                      APART (a row is its own run of tiles, so one copy
                      fetches it; ``grouped_matmul``'s ``rows_apart``). Rows
                      of tiles that were not launched hold whatever was
                      there, and so do the padding rows of an expert's last
                      tile for all this kernel knows
  row    [N·K] int32  pair -> buffer row; M = "not here" (the pair's expert
                      is absent, or the token is a step's padding)
  gates  [N, K] f32   the pair's gate
  → y    [N, D]       y[t] = cast(Σ_k f32(yb[row[t, k]]) · gates[t, k]) over
                      the pairs with row < M, in k order, float32 throughout
                      and rounded once. A token with no pair here gets a row
                      of zeros, written.
    rows [] int32     the copies the launch starts: the length of the lists
                      it walks, so the rows of ``yb`` it fetched

The invariant: a pair with ``row == M`` is SKIPPED — it is not in the list
the kernel walks. It is not read and multiplied by zero (0 × NaN is NaN) and
its index is not clamped. The kernel starts no copy from ``yb`` at an index
no pair names, so nothing that an unlaunched tile or a padding row holds can
reach ``y`` (tests/test_moe_combine.py poisons every such row).

How: the grid walks tiles of ``_TOKENS`` tokens. Before the launch the
places of a tile's held pairs are listed, in pair order (the j-th is the
number of places whose running count of held pairs is j or less: one
comparison a place and a slot, no scatter, no sort), with the count a tile
and a token; the lists are scalar-prefetched FLAT (a 2-D scalar operand is
padded on its minor axis: ``s32[2048, 10]`` would fill the scalar memory;
a step of more than ``_LAUNCH_PAIRS`` pairs takes a launch a slice of tokens).
While a tile is summed, the copies of the NEXT tile's held pairs are in
flight, the j-th to slot j of a VMEM ring (two halves of ``_TOKENS · K + K``
slots of 8 KB at D 4096, bf16: 5.4 MB at K 10, inside the compiler's default
VMEM limit, which the launch does not raise): a loop over the pairs the tile
holds, with no test in it, ``_STARTS`` copies a turn. A row is ``[C, 128]``,
whole vector registers, so a pair costs a few vector operations; a token's
pairs are consecutive slots, and of the K slots from its first those past
its count are selected away, not multiplied. What crosses HBM: the rows that
were written for tokens (Granite-4.0-H's 2,048-token step: 10,200 of 20,480
pairs, 84 MB) in, ``y`` once out — where XLA's gather, re-layout and sum
moved the N·K worst case five times (840 MB).

What a start pays: a step program traces and lowers the kernel before it can
ask the compile cache (≈ 0.1 s a program, PERF.md section 6), so the kernel
is written in few operations — the K slots are one loop body unrolled, the
two tiles a first step fetches are two turns of one loop, the waits are one
loop — and ``_mlp_moe_held`` names it after the step program alone, so that
a program's layer groups share one.

Every step program's read-back is this kernel (engine/model.py
``_mlp_moe_held``): a decode step's is one tile of tokens and a few dozen
copies. Two other ways to write the sum were timed on the chip and not kept
(PERF.md section 6, PR 46): a loop over exactly a token's held pairs (no
select; faster where few pairs are held, slower where most are) and lists of
rows and gates compacted before the launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.paged_attention import kernel_interpret_mode

#: tokens of one grid step: the ring holds two such tiles' pairs
_TOKENS = 32
#: pairs of one launch: its three lists of a pair each (480 KB) leave half
#: of the 1 MB of scalar memory free; a longer step takes several launches
_LAUNCH_PAIRS = 40960
#: copies started a turn of the loop that starts them: their scalar work
#: (two loads and an address each, one after the other) overlaps
_STARTS = 2  # a power of two


def _kernel(place_ref, row_ref, gate_ref, held_ref, tok_ref, yb_ref, out_ref,
            ring, sem, *, K: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, tiles = pl.program_id(0), pl.num_programs(0)
    pairs = _TOKENS * K
    half = i & 1
    # (lax's own operations and shifts, not ``//``, ``%`` and jnp's wrapped
    # functions: each of those is a nested function to trace and lower, and
    # every step program pays for the kernel's before it can ask the
    # compile cache)
    lax = jax.lax

    def fetch(tile, c):
        """Start the copy of every held pair of ``tile``: the places its
        list's first ``held_ref[tile]`` entries name, and no other."""
        first, into, n = tile * pairs, tile & 1, held_ref[tile]
        turns = lax.shift_right_logical(n, _STARTS.bit_length() - 1)

        def start(j):
            row = row_ref[first + place_ref[first + j]]
            pltpu.make_async_copy(yb_ref.at[row], ring.at[into, j],
                                  sem.at[into]).start()

        def some(q, c):
            for u in range(_STARTS):
                start(q * _STARTS + u)
            return c

        def one(j, c):
            start(j)
            return c

        lax.fori_loop(0, turns, some, 0)
        return lax.fori_loop(turns * _STARTS, n, one, c)

    # a slot past a token's count is read and selected away: let no launch
    # find in the ring what no copy of its own put there
    @pl.when(i == 0)
    def _():
        ring[...] = jnp.zeros(ring.shape, ring.dtype)

    # the next tile's copies (before the first tile: its own too)
    lax.fori_loop(lax.select(i == 0, 0 * i, i + 1), lax.min(i + 2, tiles),
                  fetch, 0)

    # the tile's copies have arrived: one wait a copy started, each for a
    # copy's own size (a descriptor that names no row of yb: only its size
    # is read); one wait for many copies' bytes read the same on the chip
    # and 67 us a layer less (chip run, PR 44), the documented use is this
    def arrived(_, c):
        at = ring.at[half, 0]
        pltpu.make_async_copy(at, at, sem.at[half]).wait()
        return c

    lax.fori_loop(0, held_ref[i], arrived, 0)

    first = i * pairs
    zero = jnp.zeros(ring.shape[2:], jnp.float32)

    def token(t, base):
        """``base``: the slot of the token's first held pair."""
        count = tok_ref[i * _TOKENS + t]

        def slot(k, acc):
            gate = gate_ref[first + place_ref[first + base + k]]
            return acc + lax.select(
                lax.broadcast(k < count, zero.shape),
                ring[half, base + k].astype(jnp.float32) * gate, zero)

        # K slots from the token's first, unrolled: traced once
        acc = lax.fori_loop(0, K, slot, zero, unroll=True)
        out_ref[t] = acc.astype(out_ref.dtype)
        return base + count

    lax.fori_loop(0, _TOKENS, token, 0)


def _held_places(row, K: int, M: int):
    """``(place, held, tok)``: a tile's places (0 .. ``_TOKENS · K``) that
    hold a pair here, in pair order, flat (behind a tile's count: the number
    of its places, which is no place of it); the count a tile; a token."""
    pairs = _TOKENS * K
    held = row.reshape(-1, pairs) < M
    before = jnp.cumsum(held, 1, dtype=jnp.int32)
    place = (before[:, :, None] <= jnp.arange(pairs, dtype=jnp.int32)).sum(
        1, dtype=jnp.int32)
    return (place.reshape(-1), before[:, -1],
            held.reshape(-1, K).sum(1, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("K", "tag", "interpret"))
def _call(yb, row, gates, *, K: int, tag: str, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, C, lanes = yb.shape
    N = row.shape[0] // K
    place, held, tok = _held_places(row, K, M)
    y = pl.pallas_call(
        functools.partial(_kernel, K=K),
        out_shape=jax.ShapeDtypeStruct((N, C, lanes), yb.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // _TOKENS,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((_TOKENS, C, lanes),
                                   lambda i, *_: (i, 0, 0)),
            # a token's K slots may lie behind the tile's last pair
            scratch_shapes=[
                pltpu.VMEM((2, _TOKENS * K + K, C, lanes), yb.dtype),
                pltpu.SemaphoreType.DMA((2,))],
        ),
        # a tile's copies are started by the step before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_combine" + tag,
    )(jnp.pad(place, (0, K)), row, jnp.pad(gates, (0, 1)), held, tok, yb)
    return y.reshape(N, C * lanes), held.sum()


def moe_combine(yb, row, gates, tag: str = ""):
    """See the module docstring for the contract: ``(y, rows)``; ``gates``
    [N, K] says K. ``tag`` joins the op's name in the device trace
    (``moe_combine<tag>``)."""
    N, K = gates.shape
    M = yb.shape[0]
    assert row.shape == (N * K,) and yb.ndim == 3, (row.shape, yb.shape)
    pad = -N % _TOKENS  # whole tiles of tokens: the rest holds no pair
    row = jnp.pad(row.astype(jnp.int32), (0, pad * K), constant_values=M)
    gates = jnp.pad(gates.astype(jnp.float32).reshape(N * K), (0, pad * K))
    pairs = _LAUNCH_PAIRS // (_TOKENS * K) * (_TOKENS * K)
    y, rows = zip(*(_call(yb, row[p:p + pairs], gates[p:p + pairs], K=K,
                          tag=tag, interpret=kernel_interpret_mode())
                    for p in range(0, (N + pad) * K, pairs)))
    return (y[0] if len(y) == 1 else jnp.concatenate(y))[:N], sum(rows)
