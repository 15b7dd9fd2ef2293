"""The Mamba-2 mixer's state math inside one ragged step: the causal
convolution and the selective state-space recurrence of every row of the
step, each row starting from its sequence's state slot and leaving the
state after its last token there.

    xBC_t = silu(b + sum_j w[j] * xBC_{t-3+j})        zeros before a sequence
    S_t   = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t
    y_t   = S_t C_t + D * x_t

The convolution is ops/shortconv.py's ``causal_conv``, which the
short-convolution mixer shares.

A step's rows are of two sorts, and each sort is ONE Pallas launch a layer.
A row of ONE token (a decode row, or a chunk of one) is a read-modify-write
of its slot by :func:`mamba2_decode_update`. A row of more (a prefill chunk;
a step holds at most ``RAGGED_MAX_CHUNKS``) runs the chunked form of the
recurrence (state-space duality) over the step's FLAT token axis in
:func:`mamba2_chunk_scan`, which walks the axis in blocks of ``SSD_BLOCK``
tokens, a group of heads at a time, with the chunk rows' running states in
VMEM from the first block to the last: inside a block a head's output is a
masked matrix product in which a token sees only earlier tokens of its own
row (the decay across a row boundary is zero); between blocks each chunk row
carries its own state, read and moved only in the blocks the row has tokens
in, and tokens of other rows leave it as it is — so a prompt fed in three
budgets leaves the state one pass would. No per-block state and no
per-head decay matrix is ever written to HBM: the kernel reads x, B, C and
four ``[T, H]`` float32 arrays of within-row cumulative sums, the rows'
states once, and writes y and the states once. Everything a token is a row
of ``[T, H·P]``, a head's P channels side by side on the lanes as in the
state's lane rows below, so neither the scan nor what surrounds it
transposes anything. Padding and foreign tokens are SELECTED away
(``jnp.where``), never multiplied by zero: on the chip a row no kernel
wrote may hold NaN; the scan writes every row of y (zeros outside the chunk
rows). A token bucket under one block is padded to one.

Whether a row starts a sequence is read off its first token's position
(0): such a row starts from zeros whatever its slot holds, so a slot needs
no zeroing when it changes hands and a finished sequence's state cannot
leak. Padding rows (``q_len`` 0) write to the dump slot, the last one.

State layout: ``conv`` ``[L, slots + 1, (d_conv - 1) · C]`` in the model's
dtype (the taps flattened into the minor axis: with an axis of 3 second to
last the compiler lays the array out in another order than its fusions
read, and copies the whole array into and out of every step), ``ssm`` ``[L, slots + 1, H // pack, N, pack * P]`` in float32 —
``pack`` heads side by side on the minor axis so that a row of the state is
a whole 128-lane row (P = 64 alone would leave half of every lane row
empty, in memory too), the layout both kernels compute in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import RAGGED_MAX_CHUNKS
from dynamo_tpu.ops.paged_attention import kernel_interpret_mode
from dynamo_tpu.ops.shortconv import causal_conv, step_rows

#: tokens of one block of the chunked recurrence (the MXU's height; the
#: published ``mamba_chunk_size`` 256 is a tiling and changes no equation)
SSD_BLOCK = 128


#: head groups (lane rows of ``pack`` heads) of one block of the update
#: kernel: 16 x 128 x 128 float32 = 1 MB, in and out, double-buffered
_UPDATE_GROUPS = 16


def _update_kernel(slot_ref, keep_ref, layer_ref, a_ref, dx_ref, b_ref,
                   c_ref, s_ref, s_out, y_out):
    from jax.experimental import pallas as pl

    del slot_ref, layer_ref  # read by the index maps only
    keep = keep_ref[pl.program_id(0)] > 0
    b, c = b_ref[...], c_ref[...]                        # [N, 1]
    for g in range(s_ref.shape[0]):   # one lane row of heads at a time
        s = jnp.where(keep, s_ref[g].astype(jnp.float32), 0.0)   # [N, W]
        s = a_ref[g:g + 1, :] * s + b * dx_ref[g:g + 1, :]
        s_out[g] = s.astype(s_out.dtype)
        y_out[g:g + 1, :] = jnp.sum(s * c, axis=0, keepdims=True)


def mamba2_decode_update(ssm_state, lidx, slots, keep, n_rows, a, dx, Bm, Cm,
                         *, interpret: bool, tag: str = ""):
    """The recurrence's single-token update as ONE Pallas launch (op
    ``mamba2_decode_update<tag>`` in the device trace; forward's tag says
    the run of layers and the step program, so that an op's time can be
    held against the work of exactly the steps and layers that ran it), in
    place on the state stack: for i < ``n_rows``, slot ``slots[i]`` of
    layer ``lidx``

        S <- a_i * (S if keep_i else 0) + dx_i (x) B_i,    y_i = S C_i

    ``ssm_state`` [L, slots, G, N, W] (packed: W = pack·P lanes; float32,
    or what a control keeps it in: the arithmetic is float32 either way);
    a, dx [R, G, W] (a head's decay over its P lanes); Bm, Cm [R, N].
    The grid's leading bound is ``n_rows``, a traced value: a step of 9
    rows reads and writes 9 slots, not the row bucket's 64, and nothing but
    those (the whole stack is aliased to the output). Returns (state,
    y [R, G, W]; rows past ``n_rows`` are not written).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, G, W = a.shape
    N = Bm.shape[1]
    gb = _UPDATE_GROUPS if G % _UPDATE_GROUPS == 0 else G
    row = pl.BlockSpec((None, gb, W), lambda i, j, sl, kp, ly: (i, j, 0))
    col = pl.BlockSpec((None, N, 1), lambda i, j, sl, kp, ly: (i, 0, 0))
    st = pl.BlockSpec((None, None, gb, N, W),
                      lambda i, j, sl, kp, ly: (ly[0], sl[i], j, 0, 0))
    state, y = pl.pallas_call(
        _update_kernel,
        out_shape=(jax.ShapeDtypeStruct(ssm_state.shape, ssm_state.dtype),
                   jax.ShapeDtypeStruct((R, G, W), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_rows, G // gb),
            in_specs=[row, row, col, col, st],
            out_specs=(st, row),
        ),
        input_output_aliases={7: 0},   # 3 scalar operands, a, dx, B, C
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="mamba2_decode_update" + tag,
    )(slots.astype(jnp.int32), keep.astype(jnp.int32),
      jnp.asarray(lidx, jnp.int32).reshape(1), a, dx,
      Bm.astype(jnp.float32)[..., None], Cm.astype(jnp.float32)[..., None],
      ssm_state)
    return state, y


#: lane rows of ``pack`` heads a grid step of the scan: the K chunk rows'
#: states of a group, 4 x 8 x 128 x 128 float32 = 2 MB, stay in VMEM from
#: the step's first block to its last (in and out, double-buffered, 8 MB of
#: the compiler's default 16 MiB)
_SCAN_GROUPS = 8


def _scan_kernel(present_ref, x_ref, b_ref, bt_ref, c_ref, st_ref, dec_ref,
                 s0_ref, y_ref, s_ref, dxw_ref, eo_ref, *, P, pack, hb, cd):
    from jax.experimental import pallas as pl

    blk = pl.program_id(1)
    Q, K = x_ref.shape[0], s_ref.shape[0]
    W = pack * P
    gb = x_ref.shape[1] // W

    @pl.when(blk == 0)
    def _():
        s_ref[...] = s0_ref[...]

    st = st_ref[...]                                     # [R, Q] by token
    # the same numbers a token a sublane: what scales a row of x or of y
    stT = jnp.concatenate(
        [st, jnp.zeros((Q - st.shape[0], Q), st.dtype)], axis=0).T
    ridr, ridc = st[4 * hb:4 * hb + 1, :], stT[:, 4 * hb:4 * hb + 1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    same = (ridc == ridr) & (ridc >= 0) & (ii >= jj)
    Cm = c_ref[...]
    cb = jax.lax.dot_general(Cm, b_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 1)
    inside = ridc >= 0
    # a padding token's x may be NaN: selected away, never multiplied by 0
    head = [inside & (lane >= p * P) & (lane < (p + 1) * P)
            for p in range(pack)]

    def by_lane(r0, g):
        """Rows ``r0 + head`` of the group's lane row g, a head's number
        over its P lanes: [Q, W]."""
        out = stT[:, r0 + g * pack + pack - 1:r0 + (g + 1) * pack]
        for p in reversed(range(pack - 1)):
            h = r0 + g * pack + p
            out = jnp.where(lane < (p + 1) * P, stT[:, h:h + 1], out)
        return out

    for g in range(gb):
        cols = slice(g * W, (g + 1) * W)
        xg = x_ref[:, cols]
        ms, xs = [], []
        for p in range(pack):
            h = g * pack + p
            # decay x dt of token j as token i sees it: exp(cum_i - cum_j)
            # dt_j, one exponential; outside the row and above the
            # diagonal exp(-1e30) = 0
            arg = stT[:, h:h + 1] - st[hb + h:hb + h + 1, :]
            ms.append((jnp.exp(jnp.where(same, arg, -1e30)) * cb).astype(cd))
            xs.append(jnp.where(head[p], xg, 0))
        y_ref[:, cols] = jnp.dot(jnp.concatenate(ms, axis=1),
                                 jnp.concatenate(xs, axis=0),
                                 preferred_element_type=jnp.float32)
        eo_ref[:, cols] = by_lane(2 * hb, g)
        dxw_ref[:, cols] = (jnp.where(inside, xg, 0).astype(jnp.float32)
                            * by_lane(3 * hb, g)).astype(cd)

    def row(k, carry):   # one text for the K rows: a quarter to lower
        @pl.when(present_ref[blk, k] > 0)
        def _():
            kf = k.astype(jnp.float32)
            Ck = jnp.where(ridc == kf, Cm, 0)
            Bk = jnp.where(ridr == kf, bt_ref[...], 0)
            dec = dec_ref[...]       # row k of it, without a dynamic load
            dec = jnp.sum(jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, dec.shape, 0) == k, dec, 0.0), axis=0,
                keepdims=True)
            for g in range(gb):
                cols = slice(g * W, (g + 1) * W)
                S = s_ref[k, g]
                y_ref[:, cols] += eo_ref[:, cols] * jnp.dot(
                    Ck, S.astype(cd), preferred_element_type=jnp.float32)
                s_ref[k, g] = dec[:, cols] * S + jnp.dot(
                    Bk, dxw_ref[:, cols], preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, K, row, 0)


def mamba2_chunk_scan(x, delta, la, Bm, Cm, onek, S0, *, P: int, pack: int,
                      interpret: bool, tag: str = ""):
    """The chunk rows of a step over its flat token axis, as ONE Pallas
    launch (op ``mamba2_chunk_scan<tag>`` in the device trace) that walks
    the axis block by block with the rows' running states in VMEM.

    x [T, H·P] in the dtype the matrix products take their inputs in,
    delta [T, H] = dt and la [T, H] = dt·A in float32, Bm, Cm [T, N],
    ``onek`` [T, K] bool: token t belongs to chunk row k (no k: a token of
    a one-token row or padding, which neither reads nor moves any state
    here and whose x, dt, B and C may hold anything, NaN included), S0
    [K, G, N, W] float32 the rows' states before the step, packed. Returns
    y [T, H·P] float32 (zeros outside the chunk rows: every row is
    written) and the rows' states after the step.

    Inside a block of ``SSD_BLOCK`` tokens a head's output is the masked
    product (C Bᵀ ∘ decay ∘ dt) x over the tokens of the same row; between
    blocks a row's state is read (``C S``, decayed from the block's start)
    and moved (``exp(Σ dt·A) S + Bᵀ (dt x decayed to the block's end)``)
    only in the blocks the row has tokens in (``present``, prefetched).
    The wrapper makes, in float32 and on [T, H] arrays, the within-row
    cumulative sums the decays are exponentials of; the kernel makes every
    [Q, Q] and [Q, W] product from them.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T0, di = x.shape
    H, N, K = delta.shape[1], Bm.shape[1], onek.shape[1]
    G, W, Q, cd = H // pack, pack * P, SSD_BLOCK, x.dtype
    pad = -T0 % Q
    if pad:   # a bucket under one block: tokens outside every row
        x, delta, la, Bm, Cm, onek = (
            jnp.pad(a, ((0, pad), (0, 0))) for a in (x, delta, la, Bm, Cm,
                                                     onek))
    T = T0 + pad
    nC = T // Q
    gb = _SCAN_GROUPS if G % _SCAN_GROUPS == 0 else G
    hb, HG = gb * pack, G // gb
    R = -(-(4 * hb + 1) // 8) * 8
    assert R <= Q, (hb, Q)

    inside = onek.any(-1)
    rid = jnp.where(inside, jnp.argmax(onek, axis=-1), -1)
    # selected, never multiplied by 0: a padding token's row may hold NaN
    la = jnp.where(inside[:, None], la, 0.0).reshape(nC, Q, H)
    delta = jnp.where(inside[:, None], delta, 1.0)
    Bm = jnp.where(inside[:, None], Bm, 0).astype(cd)
    Cm = jnp.where(inside[:, None], Cm, 0).astype(cd)
    ridb = rid.reshape(nC, Q)
    own = (ridb[:, :, None] == ridb[:, None, :]) & (ridb[:, :, None] >= 0)
    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    hi = jax.lax.Precision.HIGHEST
    # sums of dt·A over a row's tokens in the block: up to and with token i
    # (0 >= cum), after token j to the row's last in the block, all of them
    cum = jnp.einsum("cij,cjh->cih", (own & tri).astype(jnp.float32), la,
                     precision=hi).reshape(T, H)
    to_end = jnp.einsum("cij,cjh->cih", (own & ~tri).astype(jnp.float32), la,
                        precision=hi).reshape(T, H)
    tot = jnp.einsum("cqk,cqh->ckh",
                     onek.reshape(nC, Q, K).astype(jnp.float32), la,
                     precision=hi)

    def rows(a):   # [T, H] -> [HG, hb, T]
        return a.T.reshape(HG, hb, T)

    # what the kernel reads a (token, head), by token along the lanes: hb
    # rows each of cum (token i of the decay), cum - log dt (token j: its
    # dt rides the exponent), exp(cum) (a row's state as token i sees it),
    # dt exp(to_end) (token j's share of the state the block leaves); then
    # the token's chunk row, -1 outside every row
    st = jnp.concatenate(
        [rows(cum), rows(cum - jnp.log(delta)), rows(jnp.exp(cum)),
         rows(delta * jnp.exp(to_end)),
         jnp.broadcast_to(rid.astype(jnp.float32), (HG, 1, T)),
         jnp.zeros((HG, R - 4 * hb - 1, T), jnp.float32)], axis=1)
    dec = jnp.repeat(jnp.exp(tot), P, axis=-1)           # [nC, K, H·P]
    present = onek.reshape(nC, Q, K).any(1).astype(jnp.int32)

    tok = pl.BlockSpec((Q, gb * W), lambda j, c, pr: (c, j))
    bc = pl.BlockSpec((Q, N), lambda j, c, pr: (c, 0))
    state = pl.BlockSpec((K, gb, N, W), lambda j, c, pr: (0, j, 0, 0))
    y, S = pl.pallas_call(
        functools.partial(_scan_kernel, P=P, pack=pack, hb=hb, cd=cd),
        out_shape=(jax.ShapeDtypeStruct((T, di), jnp.float32),
                   jax.ShapeDtypeStruct(S0.shape, jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(HG, nC),
            in_specs=[tok, bc,
                      pl.BlockSpec((N, Q), lambda j, c, pr: (0, c)), bc,
                      pl.BlockSpec((None, R, Q), lambda j, c, pr: (j, 0, c)),
                      pl.BlockSpec((None, K, gb * W),
                                   lambda j, c, pr: (c, 0, j)),
                      state],
            out_specs=(tok, state),
            scratch_shapes=[pltpu.VMEM((Q, gb * W), cd),
                            pltpu.VMEM((Q, gb * W), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="mamba2_chunk_scan" + tag,
    )(present, x, Bm, Bm.T, Cm, st, dec, S0)
    return y[:T0], S


def mamba2_ragged(xbc, dt, lp, conv_state, ssm_state, lidx, rows, positions,
                  *, cfg, chunks: bool, tag: str = ""):
    """Convolution and recurrence of one Mamba-2 layer for every row of a
    ragged step.

    xbc [T, C] the in-projection's x|B|C part, dt [T, H] its dt part (raw),
    ``lp`` the layer's ``conv_w`` [W, C], ``conv_b``, ``dt_bias``,
    ``A_log``, ``D``; ``rows`` [R, 4] int32 (q_start, q_len, kv_len, state
    slot), ``positions`` [T]; ``lidx`` the layer's index in the state
    arrays. ``chunks=False`` is the decode-only program (every row holds
    one token). Returns (y [T, H·P] float32, D·x added, before the gate,
    conv_state, ssm_state).
    """
    T, C = xbc.shape
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    di, pack, cd = H * P, cfg.mamba_head_pack, xbc.dtype
    R = rows.shape[0]
    g = step_rows(rows, positions, T)
    _, q_len, slot, valid, first, keep, tok_row, _, tok_valid = g
    dump = ssm_state.shape[1] - 1
    # conv_from_slots, with the activation before the tails are put back:
    # the order the accepted step programs were traced in
    slot_r = jnp.where(valid, slot, dump)
    tail = jnp.where(keep[:, None, None],
                     conv_state[lidx, slot_r].reshape(R, -1, C), 0)
    pre, new_tail = causal_conv(xbc, lp["conv_w"], lp["conv_b"], tail, g)
    xc = jax.nn.silu(pre)
    conv_state = conv_state.at[lidx, slot_r].set(new_tail.reshape(R, -1))

    # everything a token a row of [T, H·P]: a head's P channels lie side by
    # side on the lanes, as the state's lane rows do (no [T, H, P] array,
    # which the compiler lays head-minor and transposes to and fro)
    x = xc[:, :di]
    Bm, Cm = xc[:, di:di + N], xc[:, di + N:]
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + lp["dt_bias"].astype(jnp.float32)[None, :])
    la = delta * -jnp.exp(lp["A_log"].astype(jnp.float32))[None, :]

    # rows of one token: a batched update of their slots
    one = valid & (q_len == 1)
    order = jnp.argsort(~one, stable=True)   # the kernel walks them only
    tok = first[order]
    G, Wl = H // pack, pack * P

    def lanes(a):   # a head's number over its P lanes
        return jnp.repeat(a, P, axis=1).reshape(R, G, Wl)

    ssm_state, y_k = mamba2_decode_update(
        ssm_state, lidx, jnp.where(one, slot, dump)[order], keep[order],
        one.sum().astype(jnp.int32), lanes(jnp.exp(la[tok])),
        x[tok].reshape(R, G, Wl) * lanes(delta[tok]), Bm[tok], Cm[tok],
        interpret=kernel_interpret_mode(), tag=tag)
    y_a = jnp.zeros((R, di), jnp.float32).at[order].set(y_k.reshape(R, di))
    if chunks:
        K = RAGGED_MAX_CHUNKS
        crow = jnp.nonzero(valid & (q_len > 1), size=K, fill_value=R)[0]
        cvalid = crow < R
        crow = jnp.clip(crow, 0, R - 1)
        slot_c = jnp.where(cvalid, slot[crow], dump)
        S0 = jnp.where((cvalid & keep[crow])[:, None, None, None],
                       ssm_state[lidx, slot_c].astype(jnp.float32), 0.0)
        onek = ((tok_row[:, None] == crow[None, :]) & cvalid[None, :]
                & tok_valid[:, None])
        y, S_fin = mamba2_chunk_scan(
            x.astype(cd), delta, la, Bm, Cm, onek, S0, P=P, pack=pack,
            interpret=kernel_interpret_mode(), tag=tag)
        ssm_state = ssm_state.at[lidx, slot_c].set(
            S_fin.astype(ssm_state.dtype))
    else:
        y = jnp.zeros((T, di), jnp.float32)
    y = y.at[jnp.where(one, first, T)].set(y_a, mode="drop")
    y = y + jnp.repeat(lp["D"].astype(jnp.float32), P)[None, :] * x
    return y, conv_state, ssm_state
