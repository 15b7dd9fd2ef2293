"""ops/moe_combine.py (interpret mode) against the plain read-back of the
held-experts layer, and what the rows nobody wrote may hold.

The kernel's contract is in its module docstring. The hazard it is built
around: the down launch leaves the rows of tiles it did not launch
unwritten, a tile's padding rows are nobody's, and 0 x NaN is NaN — so a
pair that is not here is SKIPPED, and these tests poison every row no pair
names.
"""

import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.models import mimo_tiny
from dynamo_tpu.ops import grouped_matmul as gm
from dynamo_tpu.ops.grouped_matmul import ROW_TILE
from dynamo_tpu.ops.moe_combine import moe_combine

#: experts routed over for Eh = 12 held, by the share of pairs held
SHARES = {"none": None, "5pct": 240, "half": 24, "all": 12}
EH = 12
#: tokens: no multiple of the kernel's tile; the last 9 are a step's padding
N, PADDING = 75, 9


def plain(yb, row, gates):
    """The plain lines the kernel replaced: gather every pair's row, the
    absent pairs' replaced by zeros, [N, K, D] float32 gate-weighted sum,
    rounded once."""
    M_, D = yb.shape
    n, K = gates.shape
    y = jnp.where((row < M_)[:, None], yb[jnp.minimum(row, M_ - 1)], 0)
    return (y.reshape(n, K, D).astype(jnp.float32)
            * gates[..., None]).sum(1).astype(yb.dtype)


def layout(K, share, seed=0):
    """(row [N·K], rows of the buffer, named [rows] bool) as ``_mlp_moe_held``
    lays the held pairs out: by expert, an expert's rows padded to whole
    tiles, spare tiles behind; tokens 0 and 1 hold no pair, the last
    ``PADDING`` tokens are padding."""
    rng = np.random.default_rng(seed)
    E = SHARES[share]
    if E is None:  # every choice is an absent expert
        topi = np.full((N, K), EH, np.int64)
    else:
        topi = np.stack([rng.permutation(E)[:K] for _ in range(N)])
    topi[:2] = EH
    topi[N - PADDING:] = EH
    tiles_max = -(-N * K // ROW_TILE) + EH
    rows = tiles_max * ROW_TILE
    e = np.where(topi < EH, topi, EH).reshape(-1)
    counts = np.bincount(e, minlength=EH + 1)[:EH]
    row0 = np.concatenate([[0], np.cumsum(-(-counts // ROW_TILE))])[:-1] * (
        ROW_TILE)
    row, seen = np.full(N * K, rows, np.int32), np.zeros(EH, np.int64)
    for p, ex in enumerate(e):
        if ex < EH:
            row[p] = row0[ex] + seen[ex]
            seen[ex] += 1
    named = np.zeros(rows, bool)
    named[row[row < rows]] = True
    return row, rows, named


def operands(K, share, dtype, D=256, seed=0):
    row, rows, named = layout(K, share, seed)
    ks = jax.random.split(jax.random.key(seed), 2)
    yb = jax.random.normal(ks[0], (rows, D), jnp.float32).astype(dtype)
    gates = jax.random.uniform(ks[1], (N, K), jnp.float32)
    return yb, jnp.asarray(row), gates, named


def apart(yb):
    """The down launch's ``rows_apart`` layout of a plain [rows, D]."""
    rows, D = yb.shape
    return yb.reshape(rows, -1, 128) if D % 128 == 0 else yb[:, None]


@pytest.mark.parametrize("share", list(SHARES))
@pytest.mark.parametrize("K,D", [(4, 256), (8, 256), (10, 256), (10, 192),
                                 (4, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_gives_the_plain_sum(dtype, K, D, share):
    yb, row, gates, named = operands(K, share, dtype, D)
    held = float((np.asarray(row) < yb.shape[0]).mean())
    assert {"none": held == 0, "5pct": 0.005 < held < 0.08,
            "half": 0.3 < held < 0.5, "all": held > 0.8}[share], held
    y, rows = moe_combine(apart(yb), row, gates)
    assert int(rows) == int(named.sum())       # the rows it fetched: counted
    want = plain(yb, row, gates)
    assert y.shape == want.shape and y.dtype == want.dtype
    y, want = (np.asarray(a, np.float32) for a in (y, want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)
    else:  # one step of bf16 at the sum's size
        assert (np.abs(y - want) <= 2.0 ** -7 * np.maximum(
            np.abs(want), 2.0 ** -6)).all()
    # a token with no pair here, and every padding token: zeros, written
    assert not y[:2].any() and not y[N - PADDING:].any()


def test_a_step_longer_than_a_launch_takes_several(monkeypatch):
    """The lists of a launch must fit the scalar memory, so a step of more
    pairs than ``_LAUNCH_PAIRS`` is summed in several launches over the same
    buffer: the same ``y``, bit for bit, and the same count."""
    from dynamo_tpu.ops import moe_combine as mc

    yb, row, gates, named = operands(10, "half", jnp.bfloat16)
    whole, rows = moe_combine(apart(yb), row, gates)
    monkeypatch.setattr(mc, "_LAUNCH_PAIRS", 32 * 10)  # a tile a launch
    calls = []
    monkeypatch.setattr(mc, "_call", lambda *a, _call=mc._call, **kw: (
        calls.append(a[1].shape), _call(*a, **kw))[1])
    y, rows_sliced = moe_combine(apart(yb), row, gates)
    assert calls == [(320,)] * 3                  # 75 tokens: three tiles
    assert np.asarray(y == whole).all()
    assert int(rows) == int(rows_sliced) == int(named.sum())
    assert np.asarray(whole == plain(yb, row, gates)).mean() > 0.99


@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("share", ["none", "5pct", "half"])
@pytest.mark.parametrize("K", [4, 8, 10])
def test_rows_no_pair_names_cannot_reach_y(K, share, poison):
    """Every row of ``yb`` that no pair names — the spare tiles AND the
    padding rows inside each expert's last tile — poisoned, and the gates
    of the padding tokens with them (a router that read a NaN row): ``y`` is
    finite and bit for bit what it was."""
    yb, row, gates, named = operands(K, share, jnp.bfloat16)
    assert not named[-ROW_TILE:].any()            # spare tiles
    if share != "none":                           # padding rows within tiles
        assert (~named[:np.flatnonzero(named).max()]).any()
    clean, _ = moe_combine(apart(yb), row, gates)
    bad = jnp.where(jnp.asarray(named)[:, None], yb, poison).astype(yb.dtype)
    bad_gates = gates.at[N - PADDING:].set(poison)
    y, _ = moe_combine(apart(bad), row, bad_gates)
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert np.asarray(y == clean).all()
    assert not np.asarray(y[N - PADDING:]).any()


def _layer(tokens):
    cfg = dataclasses.replace(mimo_tiny(), hidden_size=128)
    lp = jax.tree.map(lambda a: a[0], M.init_params(
        cfg, jax.random.key(0))["stacks"][1])
    x = jax.random.normal(jax.random.key(5), (tokens, cfg.hidden_size))
    return cfg, lp, x, jnp.arange(tokens) < tokens - 5


@pytest.mark.parametrize("tokens", [24, 264], ids=["small", "large"])
def test_the_layer_never_reads_a_row_its_launches_did_not_write(tokens):
    """``_mlp_moe_held`` whole, at a buffer under and over two tiles an
    expert (the launches' two kinds of blocks; ONE read-back): the rows past
    ``num_tiles x 128`` of every launch's result made NaN, the layer's ``y``
    is what it was, and its counters say what the read-back fetched."""
    cfg, lp, x, valid = _layer(tokens)
    Eh, K = cfg.num_experts_held, cfg.num_experts_per_tok
    assert gm._blocks(-(-tokens * K // ROW_TILE) + Eh, Eh, 128, 128, 4)[2] \
        == (tokens == 264)
    clean, stats, _ = M._mlp_moe_held(x, lp, cfg, valid)
    launched, launch = [], gm.grouped_matmul

    def poisoned(a, w, tile_group, num_tiles, *args, **kw):
        out = launch(a, w, tile_group, num_tiles, *args, **kw)
        launched.append(kw.get("rows_apart", False))
        unwritten = jnp.arange(out.shape[0]) >= num_tiles * ROW_TILE
        return jnp.where(unwritten.reshape(-1, *[1] * (out.ndim - 1)),
                         jnp.nan, out)

    with mock.patch("dynamo_tpu.ops.grouped_matmul.grouped_matmul", poisoned):
        y, _, _ = M._mlp_moe_held(x, lp, cfg, valid)
    assert launched == [False, False, True]
    assert int(stats[3]) < -(-tokens * K // ROW_TILE) + Eh  # a tile not run
    assert np.isfinite(np.asarray(y)).all()
    assert np.asarray(y == clean).all()
    assert not np.asarray(y[tokens - 5:]).any()
    # fetched: the held pairs' rows, of the padded tokens' every pair
    assert int(stats[4]) == int(stats[1]) == int(
        stats[M.MOE_STATS_HEAD:].sum()) < int(stats[5]) == tokens * K


@pytest.mark.parametrize("tokens", [24, 264], ids=["small", "large"])
def test_padding_tokens_that_come_in_as_nan_go_out_as_zeros(tokens):
    """A step's padding rows hold whatever a kernel further down left there:
    NaN on the way in, they are routed nowhere, counted nowhere, and come
    out as zeros, written; the valid tokens' rows are what they were."""
    cfg, lp, x, valid = _layer(tokens)
    clean, stats, ids = M._mlp_moe_held(x, lp, cfg, valid)
    y, stats_n, ids_n = M._mlp_moe_held(
        jnp.where(valid[:, None], x, jnp.nan), lp, cfg, valid)
    assert np.asarray(y == clean).all() and np.asarray(stats == stats_n).all()
    assert not np.asarray(y[tokens - 5:]).any()
    assert np.asarray(ids[:tokens - 5] == ids_n[:tokens - 5]).all()


@pytest.mark.parametrize("tokens", [24, 264], ids=["small", "large"])
def test_the_layer_gives_the_plain_lines_sum(tokens):
    """The layer's ``y`` against the lines its read-back replaced, run here
    on the down launch's own result: equal to a float32 sum's order."""
    from dynamo_tpu.ops import moe_combine as mc

    cfg, lp, x, valid = _layer(tokens)
    seen, combine = {}, mc.moe_combine

    def spy(yb, row, gates, tag=""):
        seen.update(yb=yb, row=row, gates=gates)
        return combine(yb, row, gates, tag)

    # (the layer imports the kernel when it is called)
    with mock.patch("dynamo_tpu.ops.moe_combine.moe_combine", spy):
        y, _, _ = M._mlp_moe_held(x, lp, cfg, valid)
    yb = seen["yb"].reshape(seen["yb"].shape[0], -1)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(plain(yb, seen["row"], seen["gates"])),
        rtol=1e-5, atol=1e-6)
