"""The Mamba-2 mixer's state math inside one ragged step: the causal
convolution and the selective state-space recurrence of every row of the
step, each row starting from its sequence's state slot and leaving the
state after its last token there.

    xBC_t = silu(b + sum_j w[j] * xBC_{t-3+j})        zeros before a sequence
    S_t   = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t
    y_t   = S_t C_t + D * x_t

The convolution is ops/shortconv.py's ``causal_conv``, which the
short-convolution mixer shares.

A step's rows are of two sorts. A row of ONE token (a decode row, or a
chunk of one) is a read-modify-write of its slot by the kernel
:func:`mamba2_decode_update`. A row of more (a prefill chunk;
a step holds at most ``RAGGED_MAX_CHUNKS``) runs the chunked form of the
recurrence (state-space duality) over the step's FLAT token axis,
:func:`_ssd_flat`: inside a block of ``SSD_BLOCK`` tokens the output is a
masked matrix product in which a token sees only earlier tokens of its own
row (the decay across a row boundary is zero), and between blocks each
chunk row carries its own state, which tokens of other rows leave as it is
— so a prompt fed in three budgets leaves the state one pass would.

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
empty, in memory too). :func:`pack_state` / :func:`unpack_state` turn it
from and to ``[..., H, P, N]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import RAGGED_MAX_CHUNKS
from dynamo_tpu.ops.paged_attention import kernel_interpret_mode
from dynamo_tpu.ops.shortconv import causal_conv, step_rows

#: tokens of one block of the chunked recurrence (the MXU's height; the
#: published ``mamba_chunk_size`` 256 is a tiling and changes no equation)
SSD_BLOCK = 128


def pack_state(s, pack: int):
    """``[..., H, P, N]`` → ``[..., H // pack, N, pack * P]``."""
    *lead, H, P, N = s.shape
    s = s.reshape(*lead, H // pack, pack, P, N)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, H // pack, N, pack * P)


def unpack_state(a, pack: int):
    """``[..., H // pack, N, pack * P]`` → ``[..., H, P, N]``."""
    *lead, G, N, W = a.shape
    a = a.reshape(*lead, G, N, pack, W // pack)
    return jnp.moveaxis(a, -3, -1).reshape(*lead, G * pack, W // pack, N)


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


def _ssd_flat(x, la, dx, Bm, Cm, onek, S0, cd):
    """The chunk rows of a step, over its flat token axis.

    x, dx [T, H, P] (dx = dt·x), la [T, H] = dt·A, Bm, Cm [T, N], ``onek``
    [T, K] bool: token t belongs to chunk row k (no k: a token of a
    one-token row or padding, which neither reads nor moves any state
    here), S0 [K, H, P, N] the rows' states before the step. Returns
    y [T, H, P] float32 (zeros outside the chunk rows) and the rows' states
    after the step. ``cd``: dtype the matrix products take their inputs in.
    """
    T, H, P = x.shape
    Q = min(T, SSD_BLOCK)
    nC = T // Q
    assert nC * Q == T, (T, Q)

    def r(a):
        return a.reshape(nC, Q, *a.shape[1:])

    inside = onek.any(-1)
    la = jnp.where(inside[:, None], la, 0.0)
    dx = jnp.where(inside[:, None, None], dx, 0.0)
    # selected, not multiplied by 0 below: a padding token's row may hold
    # NaN (a kernel further down wrote no such row), and 0 x NaN is NaN in
    # the state of every chunk row of the step
    Bm = jnp.where(inside[:, None], Bm, 0)
    Cm = jnp.where(inside[:, None], Cm, 0)
    rid = jnp.where(inside, jnp.argmax(onek, axis=-1), -1)
    ok, rla, rdx, rB, rC, rrid = r(onek), r(la), r(dx), r(Bm), r(Cm), r(rid)
    cum = jnp.cumsum(rla, axis=1)                        # [nC, Q, H]
    # inside a block: token i sees token j <= i of its own row, decayed
    same = ((rrid[:, :, None] == rrid[:, None, :]) & (rrid[:, :, None] >= 0)
            & (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[None])
    decay = jnp.exp(jnp.minimum(cum[:, :, None, :] - cum[:, None, :, :], 0.0))
    cb = jnp.einsum("cin,cjn->cij", rC.astype(cd), rB.astype(cd),
                    preferred_element_type=jnp.float32)
    m = jnp.where(same[..., None], decay * cb[..., None], 0.0)
    y = jnp.einsum("cijh,cjhp->cihp", m.astype(cd), rdx.astype(cd),
                   preferred_element_type=jnp.float32)
    # between blocks: every chunk row carries its own state; the tokens of
    # other rows leave it as it is (their decay is 1, they add nothing)
    okf = ok.astype(jnp.float32)
    cum_k = jnp.cumsum(rla[:, :, None, :] * okf[..., None], axis=1)
    tot_k = cum_k[:, -1]                                 # [nC, K, H]
    cum_own = (cum_k * okf[..., None]).sum(2)            # [nC, Q, H]
    to_end = ((tot_k[:, None] - cum_k) * okf[..., None]).sum(2)
    dxw = rdx * jnp.exp(to_end)[..., None]
    Bk = rB.astype(jnp.float32)[:, :, None, :] * okf[..., None]
    Sc = jnp.einsum("cqhp,cqkn->ckhpn", dxw.astype(cd), Bk.astype(cd),
                    preferred_element_type=jnp.float32)

    def step(carry, inp):
        sc, tot = inp
        return jnp.exp(tot)[..., None, None] * carry + sc, carry

    S_fin, S_in = jax.lax.scan(step, S0, (Sc, tot_k))
    Ck = rC.astype(jnp.float32)[:, :, None, :] * okf[..., None]
    y_in = jnp.einsum("cqkn,ckhpn->cqhp", Ck.astype(cd), S_in.astype(cd),
                      preferred_element_type=jnp.float32)
    y = y + jnp.exp(cum_own)[..., None] * y_in
    return y.reshape(T, H, P), S_fin


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

    x = xc[:, :di].reshape(T, H, P)
    Bm, Cm = xc[:, di:di + N], xc[:, di + N:]
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + lp["dt_bias"].astype(jnp.float32)[None, :])
    la = delta * -jnp.exp(lp["A_log"].astype(jnp.float32))[None, :]
    dx = x * delta[..., None]

    # rows of one token: a batched update of their slots
    one = valid & (q_len == 1)
    order = jnp.argsort(~one, stable=True)   # the kernel walks them only
    tok = first[order]
    G, Wl = H // pack, pack * P
    ssm_state, y_k = mamba2_decode_update(
        ssm_state, lidx, jnp.where(one, slot, dump)[order], keep[order],
        one.sum().astype(jnp.int32),
        jnp.repeat(jnp.exp(la[tok]), P, axis=1).reshape(R, G, Wl),
        dx[tok].reshape(R, G, Wl), Bm[tok], Cm[tok],
        interpret=kernel_interpret_mode(), tag=tag)
    y_a = jnp.zeros((R, H, P), jnp.float32).at[order].set(
        y_k.reshape(R, H, P))
    if chunks:
        K = RAGGED_MAX_CHUNKS
        crow = jnp.nonzero(valid & (q_len > 1), size=K, fill_value=R)[0]
        cvalid = crow < R
        crow = jnp.clip(crow, 0, R - 1)
        slot_c = jnp.where(cvalid, slot[crow], dump)
        S0 = jnp.where((cvalid & keep[crow])[:, None, None, None],
                       unpack_state(ssm_state[lidx, slot_c], pack
                                    ).astype(jnp.float32), 0.0)
        onek = ((tok_row[:, None] == crow[None, :]) & cvalid[None, :]
                & tok_valid[:, None])
        y, S_fin = _ssd_flat(x, la, dx, Bm, Cm, onek, S0, cd)
        ssm_state = ssm_state.at[lidx, slot_c].set(
            pack_state(S_fin, pack).astype(ssm_state.dtype))
    else:
        y = jnp.zeros((T, H, P), jnp.float32)
    y = y.at[jnp.where(one, first, T)].set(y_a, mode="drop")
    y = y + lp["D"].astype(jnp.float32)[None, :, None] * x
    return y.reshape(T, di), conv_state, ssm_state
