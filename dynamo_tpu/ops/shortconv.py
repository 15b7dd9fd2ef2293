"""The causal depthwise convolution over a ragged step's flat token axis,
which both recurrent mixers run from a state slot's tail, and LFM2's gated
short-convolution mixer, whose WHOLE state that tail is.

    [B | C | x] = in_proj(u)            thirds of 3·D, in that order
    z_t = B_t * x_t
    c_t = sum_j w[j] * z_{t-(W-1)+j}    depthwise, causal, zeros before a
                                        sequence; no bias, no activation
    y_t = C_t * c_t                     then out_proj

A step's rows each continue one sequence: a row's first ``W - 1`` tokens
read the sequence's last inputs out of its state slot (``conv`` ``[L,
slots + 1, (W - 1) · D]`` in the model's dtype, the taps flattened into the
minor axis as ops/mamba2.py keeps its own), every later token reads the
row's own earlier tokens, and the row leaves its last ``W - 1`` inputs in
the slot. Whether a row starts a sequence is read off its first token's
position (0): such a row starts from zeros whatever its slot holds, so a
slot needs no zeroing when it changes hands. Padding rows (``q_len`` 0)
write to the dump slot, the last one. A decode row is a row of one token:
the same three multiply-adds a channel, no program of its own.

No kernel: the convolution is ``W`` multiply-adds a channel a token and the
state of 256 slots is 17 MB (LFM2-24B-A2B's eight layers of a stage).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class StepRows(NamedTuple):
    """What the rows operand (``[R, 4]``: q_start, q_len, kv_len, state
    slot) and the tokens' positions say about a step's flat token axis."""

    q_start: jax.Array
    q_len: jax.Array
    slot: jax.Array
    valid: jax.Array      # [R] the row holds tokens
    first: jax.Array      # [R] index of the row's first token
    keep: jax.Array       # [R] the row continues a sequence (position > 0)
    tok_row: jax.Array    # [T] the row a token lies in
    in_row: jax.Array     # [T] its offset in that row
    tok_valid: jax.Array  # [T] a real token (not padding past the rows)


def step_rows(rows, positions, T: int) -> StepRows:
    R = rows.shape[0]
    q_start, q_len, slot = rows[:, 0], rows[:, 1], rows[:, 3]
    valid = q_len > 0
    first = jnp.clip(q_start, 0, T - 1)
    keep = valid & (positions[first] != 0)   # continues a sequence
    # token -> row: the rows lie one after another in row order
    t = jnp.arange(T)
    tok_row = jnp.clip(((t[:, None] >= q_start[None, :])
                        & valid[None, :]).sum(1) - 1, 0, R - 1)
    in_row = t - q_start[tok_row]
    tok_valid = in_row < q_len[tok_row]
    return StepRows(q_start, q_len, slot, valid, first, keep, tok_row,
                    in_row, tok_valid)


def _shift(x, s: int):
    """``out[t] = x[t - s]``, zeros for ``t < s``."""
    return x if s == 0 else jnp.pad(x, ((s, 0), (0, 0)))[:x.shape[0]]


def causal_conv(x, w, b, tail, g: StepRows):
    """The causal depthwise convolution over the flat token axis of the
    rows ``g``: ``w`` [W, C] taps over a row's own tokens, ``tail`` [R,
    W-1, C] (each row's last inputs before this step, zeros where the row
    starts a sequence) for its first ``W - 1``. ``b`` [C] or None. Returns
    the sums [T, C] in float32, before any activation, and the rows' new
    tails [R, W-1, C]."""
    T, _ = x.shape
    W = w.shape[0]
    q_start, q_len, valid, in_row = g.q_start, g.q_len, g.valid, g.in_row
    x32, w32, t32 = (a.astype(jnp.float32) for a in (x, w, tail))
    pre = sum(
        w32[j][None, :] * jnp.where((in_row >= W - 1 - j)[:, None],
                                    _shift(x32, W - 1 - j), 0.0)
        for j in range(W))
    if b is not None:
        pre = b.astype(jnp.float32)[None, :] + pre
    for k in range(W - 1):
        # the row's token at offset k reads tail entries k .. W-2
        add = sum(w32[j][None, :] * t32[:, k + j] for j in range(W - 1 - k))
        at = jnp.where(valid & (k < q_len), q_start + k, T)
        pre = pre.at[at].add(add, mode="drop")
    new = []
    for i in range(W - 1):
        p = q_len - (W - 1) + i          # offset in the row, < 0: old tail
        old = jnp.take_along_axis(
            tail, jnp.clip(W - 1 + p, 0, W - 2)[:, None, None], axis=1)[:, 0]
        new.append(jnp.where((p >= 0)[:, None],
                             x[jnp.clip(q_start + p, 0, T - 1)], old))
    return pre, jnp.stack(new, axis=1)


def conv_from_slots(x, w, b, conv_state, lidx, g: StepRows):
    """:func:`causal_conv` of a step's rows ``g``, each from its state
    slot's tail in ``conv_state`` [L, slots + 1, (W-1)·C] at layer ``lidx``
    (zeros where it starts a sequence; padding rows take the dump slot,
    the last), its new tail left there. Returns (the sums [T, C] float32,
    conv_state)."""
    R, C = g.q_len.shape[0], x.shape[1]
    slot_r = jnp.where(g.valid, g.slot, conv_state.shape[1] - 1)
    tail = jnp.where(g.keep[:, None, None],
                     conv_state[lidx, slot_r].reshape(R, -1, C), 0)
    pre, new_tail = causal_conv(x, w, b, tail, g)
    return pre, conv_state.at[lidx, slot_r].set(new_tail.reshape(R, -1))


def shortconv_ragged(bcx, conv_w, conv_state, lidx, rows, positions):
    """The gated short convolution of one layer for every row of a ragged
    step (prompt chunks and decode rows alike).

    bcx [T, 3·D] the in-projection's B | C | x, ``conv_w`` [W, D] the
    layer's taps, ``conv_state`` the tails of every layer (module
    docstring), ``lidx`` the layer's index in it, ``rows`` [R, 4] int32
    (q_start, q_len, kv_len, state slot), ``positions`` [T]. Returns
    (C * conv(B * x) [T, D] in bcx's dtype, conv_state).
    """
    D = conv_w.shape[1]
    g = step_rows(rows, positions, bcx.shape[0])
    c, conv_state = conv_from_slots(bcx[:, :D] * bcx[:, 2 * D:], conv_w,
                                    None, conv_state, lidx, g)
    y = bcx[:, D:2 * D].astype(jnp.float32) * c
    return y.astype(bcx.dtype), conv_state
