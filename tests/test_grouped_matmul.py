"""ops/grouped_matmul.py against a plain per-expert einsum (interpret mode;
what the chip's compiler says of it is tests/test_chip_compile.py's)."""

from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import grouped_matmul as gm

T = gm.ROW_TILE
#: row tiles of each expert, laid next to each other as the dispatch lays
#: them: one over 3 tiles beside one with a single tile and two with none
TILES = (0, 3, 1, 0, 2)


def _case(k, n, dtype, layers=3, spare=2, seed=0):
    rng = np.random.default_rng(seed)
    E = len(TILES)
    tile_group = np.repeat(np.arange(E), TILES)
    used = len(tile_group)
    # the buffer is sized for a worst case: tiles past the ones in use
    # carry an expert's number all the same, and rows nobody wrote
    tile_group = np.concatenate([tile_group, np.full(spare, E - 1)])
    x = rng.standard_normal(((used + spare) * T, k)).astype(np.float32)
    w = rng.standard_normal((layers, E, k, n)).astype(np.float32) / k ** 0.5
    return (jnp.asarray(x, dtype), jnp.asarray(w, dtype),
            jnp.asarray(tile_group, jnp.int32), used)


def _plain(x, w, layer, tile_group, used):
    xt = np.asarray(x, np.float32).reshape(len(tile_group), T, -1)[:used]
    wt = np.asarray(w, np.float32)[layer][np.asarray(tile_group)[:used]]
    return np.einsum("trk,tkn->trn", xt, wt).reshape(used * T, -1)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block", ["n_smaller", "n_equal", "n_multiple",
                                   "k_multiple"])
@pytest.mark.parametrize("spare", [2, 4], ids=["small_buffer", "large_buffer"])
def test_matches_a_per_expert_einsum(spare, block, dtype, tol):
    """Widths smaller than, equal to and a multiple of one weight block, at
    a layer > 0 of a stack, in a buffer of fewer than two tiles an expert
    (square blocks, the contraction split and summed in the scratch, a
    tile's blocks together) and of two (the contraction whole, an expert's
    block kept over its tiles, the tiles under each block of columns); the
    rows of tiles past ``num_tiles`` stay unwritten (interpret mode leaves
    them as it made them: not the product)."""
    k, n = {"n_smaller": (64, 96), "n_equal": (128, 256),
            "n_multiple": (128, 768), "k_multiple": (256, 256)}[block]
    size = jnp.dtype(dtype).itemsize
    x, w, tile_group, used = _case(k, n, dtype, spare=spare)
    # a budget of one [128, 256] block: n_smaller and n_equal fit whole,
    # n_multiple takes three blocks of 256 columns, k_multiple (the
    # contraction whole) two of 128; square blocks of 128
    with mock.patch.object(gm, "_W_BLOCK_BYTES", 128 * 256 * size), \
            mock.patch.object(gm, "_TILE", 128):
        want = {"n_smaller": (64, 96, True), "n_equal": (128, 256, True),
                "n_multiple": (128, 256, True),
                "k_multiple": (256, 128, True)}[block]
        if spare == 2:
            want = min(k, 128), min(n, 128), False
        assert gm._blocks(len(tile_group), len(TILES), k, n, size) == want
        out = gm.grouped_matmul(x, w, tile_group, used, layer=2)
    got = np.asarray(out, np.float32)
    want = _plain(x, w, 2, tile_group, used)
    np.testing.assert_allclose(got[:used * T], want, rtol=tol, atol=tol)
    # the spare tiles' rows are not the product of what they hold
    rest = _plain(x, w, 2, tile_group, len(tile_group))[used * T:]
    assert not np.allclose(got[used * T:], rest, rtol=tol, atol=tol)


def test_a_stack_of_one_layer_and_no_tiles_in_use():
    x, w, tile_group, used = _case(64, 128, jnp.float32, layers=1)
    out = gm.grouped_matmul(x, w[0], tile_group, used)
    np.testing.assert_allclose(
        np.asarray(out)[:used * T], _plain(x, w, 0, tile_group, used),
        rtol=1e-5, atol=1e-5)
    gm.grouped_matmul(x, w[0], tile_group, 0)  # an empty grid: no launch


def test_a_matrix_no_block_of_which_fits_is_refused():
    with mock.patch.object(gm, "_W_BLOCK_BYTES", 1024):
        with pytest.raises(ValueError, match="multiple of 128 columns"):
            gm._blocks(8, 4, 512, 256, 2)
    with pytest.raises(ValueError, match="no tile that is a multiple of 128"):
        gm._blocks(7, 4, 1100, 256, 2)
    # Granite-4.0-H's (36 held, 10 a token) and MiMo-V2.5's (16 held, 8 a
    # token) matrices at bf16, in the buffers of a 2,048-token step and of
    # a 64-row decode step
    for E, K, shapes, whole in (
            (36, 10, ((4096, 768), (768, 4096)), ((4096, 768), (768, 4096))),
            (16, 8, ((4096, 2048), (2048, 4096)),
             ((4096, 1024), (2048, 2048)))):
        for (k, n), want in zip(shapes, whole):
            assert gm._blocks(2048 * K // T + E, E, k, n, 2) == (*want, True)
            assert gm._blocks(64 * K // T + E, E, k, n, 2) == (
                min(k, 1024), min(n, 1024), False)


def test_blocks_of_a_1536_wide_expert():
    """LFM2-24B-A2B's experts (64 held, 4 a token; gate and up 2048 x 1536,
    down 1536 x 2048): the launch that keeps no block — every program
    under 2,048 tokens, 96 tiles < 128 at 1,024 — cuts 1,536 into 768, the
    largest multiple of 128 under 1,024 that divides it; the 2,048-token
    program keeps whole matrices (6.3 MB)."""
    E, K = 64, 4
    for tokens in (8, 64, 1024):
        tiles = -(-tokens * K // T) + E
        assert gm._blocks(tiles, E, 2048, 1536, 2) == (1024, 768, False)
        assert gm._blocks(tiles, E, 1536, 2048, 2) == (768, 1024, False)
    tiles = 2048 * K // T + E
    assert gm._blocks(tiles, E, 2048, 1536, 2) == (2048, 1536, True)
    assert gm._blocks(tiles, E, 1536, 2048, 2) == (1536, 2048, True)
    assert gm._tile(1536, 1024) == 768 and gm._tile(768, 1024) == 768
    assert gm._tile(4096, 1024) == 1024 and gm._tile(2048, 1024) == 1024


@pytest.mark.parametrize("k,n", [(256, 1536), (1536, 256)],
                         ids=["n1536", "k1536"])
def test_a_1536_wide_dim_in_the_launch_that_keeps_no_block(k, n):
    """n = 1,536 (gate, up) and k = 1,536 (down) at the real ``_TILE``: two
    blocks of 768 columns, and a contraction summed over two."""
    x, w, tile_group, used = _case(k, n, jnp.float32, layers=2)
    tk, tn, keep = gm._blocks(len(tile_group), len(TILES), k, n, 4)
    assert (tk, tn, keep) == (min(k, 768), min(n, 768), False)
    out = gm.grouped_matmul(x, w, tile_group, used, layer=1)
    np.testing.assert_allclose(
        np.asarray(out)[:used * T], _plain(x, w, 1, tile_group, used),
        rtol=1e-5, atol=1e-5)
