"""The chunked Mamba-2 scan (ops/mamba2.py: ``mamba2_chunk_scan``, the Pallas
kernel in interpret mode) through ``mamba2_ragged``, against the recurrence
written out token by token in float32 here: y of every valid token and the
state each row leaves in its slot."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.cache import allocate_state
from dynamo_tpu.models import granite4_tiny
from dynamo_tpu.ops.mamba2 import SSD_BLOCK, mamba2_ragged

SLOTS, R = 6, 8
TOL = 2e-4


def _cfg(published: bool):
    cfg = granite4_tiny()
    if published:   # P = 64, N = 128: two heads a lane row, H cut to 8
        cfg = dataclasses.replace(cfg, mamba_n_heads=8, mamba_d_head=64,
                                  mamba_d_state=128)
    return cfg


def _layer(cfg, key):
    H = cfg.mamba_n_heads
    C = cfg.mamba_d_inner + 2 * cfg.mamba_d_state
    k = jax.random.split(key, 5)
    return {"conv_w": 0.5 * jax.random.normal(k[0], (cfg.mamba_d_conv, C)),
            "conv_b": 0.1 * jax.random.normal(k[1], (C,)),
            "dt_bias": jax.random.normal(k[2], (H,)) - 2.0,
            "A_log": jnp.log(jnp.linspace(1.0, 8.0, H)),
            "D": jax.random.normal(k[4], (H,))}


def _unpacked(ssm, cfg):
    """[slots, G, N, pack·P] -> [slots, H, P, N], written out here."""
    _, s, G, N, W = ssm.shape   # layer 0 of the stack
    pack = W // cfg.mamba_d_head
    a = np.asarray(ssm[0], np.float32).reshape(s, G, N, pack, W // pack)
    return a.transpose(0, 1, 3, 4, 2).reshape(s, G * pack, W // pack, N)


def _plain(cfg, lp, xbc, dt, conv, ssm, rows):
    """Token by token, row by row, float32: ``rows`` are (q_start, q_len,
    position of the first token, slot). Returns y [T, H·P] (zeros outside
    the rows) and the conv and ssm state arrays (ssm unpacked)."""
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    di, Wc = H * P, cfg.mamba_d_conv
    f = lambda a: np.asarray(a, np.float32)
    w, b, xbc, dt = f(lp["conv_w"]), f(lp["conv_b"]), f(xbc), f(dt)
    A, D, dtb = -np.exp(f(lp["A_log"])), f(lp["D"]), f(lp["dt_bias"])
    conv = f(conv[0]).reshape(conv.shape[1], Wc - 1, -1).copy()
    S_all = _unpacked(ssm, cfg).copy()
    y = np.zeros((xbc.shape[0], di), np.float32)
    for q0, n, pos0, slot in rows:
        fresh = pos0 == 0
        seq = np.concatenate([np.zeros_like(conv[slot]) if fresh
                              else conv[slot], xbc[q0:q0 + n]])
        S = np.zeros_like(S_all[slot]) if fresh else S_all[slot].copy()
        for t in range(n):
            pre = b + sum(w[j] * seq[t + j] for j in range(Wc))
            u = pre / (1.0 + np.exp(-pre))
            x, Bt, Ct = u[:di].reshape(H, P), u[di:di + N], u[di + N:]
            d = np.logaddexp(0.0, dt[q0 + t] + dtb).astype(np.float32)
            S = (np.exp(d * A)[:, None, None] * S
                 + (d[:, None] * x)[:, :, None] * Bt[None, None, :])
            y[q0 + t] = (S @ Ct + D[:, None] * x).reshape(di)
        conv[slot], S_all[slot] = seq[-(Wc - 1):], S
    return y, conv, S_all


def _run(cfg, lp, xbc, dt, conv, ssm, rows, T):
    """The same step through ``mamba2_ragged``."""
    r4 = np.zeros((R, 4), np.int32)
    r4[:, 3] = SLOTS
    pos = np.zeros(T, np.int32)
    for i, (q0, n, pos0, slot) in enumerate(rows):
        r4[i] = (q0, n, pos0 + n, slot)
        pos[q0:q0 + n] = pos0 + np.arange(n)
    y, conv, ssm = mamba2_ragged(
        jnp.asarray(xbc), jnp.asarray(dt), lp, jnp.asarray(conv),
        jnp.asarray(ssm), 0, jnp.asarray(r4), jnp.asarray(pos), cfg=cfg,
        chunks=True)
    return np.asarray(y), np.asarray(conv), np.asarray(ssm)


def _close(got, want, what):
    err = float(np.abs(got - want).max())
    assert err <= TOL * max(1.0, float(np.abs(want).max())), (what, err)


#: name -> (token bucket, rows (q_start, q_len, first position, slot),
#:          what the slots hold before the step, published head shape)
CASES = {
    "one_full_row": (256, [(0, 256, 0, 0)], "zeros", False),
    "a_row_from_position_0_over_a_slot_of_garbage":
        (256, [(0, 200, 0, 2)], "nan", False),
    "a_row_continuing_from_its_slot": (256, [(0, 256, 37, 1)], "random",
                                       False),
    "three_chunk_rows_and_one_token_rows_between_them_in_one_block":
        (256, [(0, 40, 0, 0), (40, 1, 9, 1), (41, 50, 5, 2), (91, 1, 3, 3),
               (92, 1, 77, 4), (93, 30, 0, 5)], "random", False),
    "a_row_boundary_in_the_middle_of_a_block":
        (256, [(0, 100, 0, 0), (100, 156, 11, 3)], "random", False),
    "a_bucket_under_one_block": (64, [(0, 40, 6, 1), (40, 1, 2, 0)],
                                 "random", False),
    "the_published_head_shape":
        (256, [(0, 70, 3, 0), (70, 1, 9, 1), (71, 150, 0, 2)], "random",
         True),
}


def _inputs(cfg, T, fill, key=1):
    C = cfg.mamba_d_inner + 2 * cfg.mamba_d_state
    ks = jax.random.split(jax.random.key(key), 4)
    xbc = jax.random.normal(ks[0], (T, C))
    dt = jax.random.normal(ks[1], (T, cfg.mamba_n_heads))
    conv, ssm = allocate_state(cfg, SLOTS)
    if fill != "zeros":
        conv = jax.random.normal(ks[2], conv.shape, conv.dtype)
        ssm = jax.random.normal(ks[3], ssm.shape, ssm.dtype)
    return xbc, dt, conv, ssm


def _scan_case(name):
    T, rows, fill, published = CASES[name]
    cfg = _cfg(published)
    lp = _layer(cfg, jax.random.key(0))
    xbc, dt, conv, ssm = _inputs(cfg, T, fill)
    want_y, want_conv, want_S = _plain(cfg, lp, xbc, dt, conv, ssm, rows)
    if fill == "nan":   # what the finished sequence left is never read
        conv, ssm = jnp.full_like(conv, jnp.nan), jnp.full_like(ssm, jnp.nan)
    y, conv2, ssm2 = _run(cfg, lp, xbc, dt, conv, ssm, rows, T)
    used = sorted(r[3] for r in rows)
    for q0, n, _, _ in rows:
        _close(y[q0:q0 + n], want_y[q0:q0 + n], (name, "y", q0))
    _close(_unpacked(ssm2, cfg)[used], want_S[used], (name, "ssm"))
    _close(conv2[0].reshape(want_conv.shape)[used], want_conv[used],
           (name, "conv"))
    assert np.isfinite(y).all()   # every row of y is written


def _padding_rows_nan():
    """A padded bucket whose padding rows are NaN on the way in: state and
    valid y bit for bit the zero-filled run's, and the recurrence's."""
    cfg, T, n = _cfg(False), 256, 150
    lp = _layer(cfg, jax.random.key(0))
    xbc, dt, conv, ssm = _inputs(cfg, T, "random")
    rows = [(0, n, 4, 2)]
    pad = (np.arange(T) >= n)[:, None]
    runs = [_run(cfg, lp, jnp.where(pad, fill, xbc), jnp.where(pad, fill, dt),
                 conv, ssm, rows, T) for fill in (jnp.nan, 0.0)]
    (y_n, conv_n, ssm_n), (y_z, conv_z, ssm_z) = runs
    assert np.isfinite(ssm_n).all() and np.isfinite(y_n[:n]).all()
    assert (ssm_n == ssm_z).all() and (conv_n == conv_z).all()
    assert (y_n[:n] == y_z[:n]).all()
    want_y, _, want_S = _plain(cfg, lp, xbc, dt, conv, ssm, rows)
    _close(y_n[:n], want_y[:n], "y")
    _close(_unpacked(ssm_n, cfg)[2], want_S[2], "ssm")


def _three_budgets():
    """A prompt fed in three budgets (each a padded bucket of one block)
    against one pass: the same y, and the state one pass leaves."""
    cfg, n = _cfg(False), 256
    lp = _layer(cfg, jax.random.key(0))
    xbc, dt, conv, ssm = _inputs(cfg, n, "random", key=2)
    want_y, _, want_S = _plain(cfg, lp, xbc, dt, conv, ssm, [(0, n, 0, 3)])
    y1, _, ssm1 = _run(cfg, lp, xbc, dt, conv, ssm, [(0, n, 0, 3)], n)
    ys, at = [], 0
    for m in (100, 90, 66):
        pad = jnp.zeros((SSD_BLOCK - m, xbc.shape[1]))
        y, conv, ssm = _run(
            cfg, lp, jnp.concatenate([xbc[at:at + m], pad]),
            jnp.concatenate([dt[at:at + m], pad[:, :dt.shape[1]]]),
            conv, ssm, [(0, m, at, 3)], SSD_BLOCK)
        ys.append(y[:m])
        at += m
    _close(np.concatenate(ys), want_y, "y, three budgets")
    _close(y1, want_y, "y, one pass")
    _close(_unpacked(ssm, cfg)[3], want_S[3], "ssm, three budgets")
    _close(_unpacked(ssm1, cfg)[3], want_S[3], "ssm, one pass")


@pytest.mark.parametrize("case", [
    *CASES, "a_padded_bucket_whose_padding_rows_are_nan",
    "a_prompt_in_three_budgets_against_one_pass"])
def test_the_chunk_scan_is_the_token_by_token_recurrence(case):
    """``mamba2_chunk_scan`` inside ``mamba2_ragged`` (granite4_tiny's
    widths, and once the published head shape) against the plain float32
    recurrence of this file: y of every token of every row, the state and
    the convolution tail each row leaves in its slot."""
    if case in CASES:
        _scan_case(case)
    elif case.startswith("a_padded"):
        _padding_rows_nan()
    else:
        _three_budgets()
