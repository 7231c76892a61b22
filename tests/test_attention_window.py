"""``ops/attention.py`` under a sliding window (ISSUE 41): both arms (the
fused kernel in Pallas's interpreter) against a dense masked softmax in
float32, forward and the three gradients; a window that hides nothing is
the causal call to the bit; the tiles the kernel's passes visit, walked
through their own grids and index maps."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.ops import attention
from imaginaire_tpu.ops.pallas import causal_attention_kernel as kernel

LENGTH, DIM = 512, 128
# query and key tiles that differ, and differ between the passes; four
# tiles of 128 a side, so that a short window leaves tiles wholly below
# its band
TILES = attention.Tiles(fwd=(256, 128), dkv=(128, 256), dq=(128, 128))
NAMES = ("out", "dq", "dk", "dv")


def _inputs(q_heads, kv_heads, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = [(1, LENGTH, q_heads, DIM), (1, LENGTH, kv_heads, DIM),
              (1, LENGTH, kv_heads, DIM), (1, LENGTH, q_heads * DIM)]
    return [jax.random.normal(k, shape, jnp.float32)
            for k, shape in zip(keys, shapes)]


def _dense(q, k, v, window):
    """Softmax over the keys ``j`` with ``0 <= i - j < window`` of the
    whole (L, L) scores, at the highest precision."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / math.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(q.shape[1])[None, :]
    seen = (i >= j) if window is None else (i >= j) & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")
    return out.reshape(*q.shape[:2], -1)


def _with_gradients(fn, q, k, v, ct):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out, *vjp(ct))


ARMS = {
    "fused": lambda window: lambda q, k, v: attention.fused_causal_attention(
        q, k, v, TILES, True, window),
    # four query blocks, which no window of the cases is a multiple of
    "blocks": lambda window: lambda q, k, v: attention.causal_attention(
        q, k, v, 128, window),
}


# under a tile, a tile, between two tiles' sizes and off any multiple,
# over the larger tile, and a window of one key: the query's own
@pytest.mark.parametrize("window,q_heads,kv_heads", [
    (1, 2, 2), (40, 2, 2), (128, 8, 1), (200, 2, 2), (300, 2, 1)])
@pytest.mark.parametrize("arm", sorted(ARMS))
def test_the_window_follows_the_dense_masked_softmax(arm, window, q_heads,
                                                     kv_heads):
    q, k, v, ct = _inputs(q_heads, kv_heads, seed=window)
    ours = _with_gradients(ARMS[arm](window), q, k, v, ct)
    exact = _with_gradients(lambda q, k, v: _dense(q, k, v, window),
                            q, k, v, ct)
    for name, a, b in zip(NAMES, ours, exact):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("window", [None, LENGTH, LENGTH + 44])
@pytest.mark.parametrize("arm", sorted(ARMS))
def test_a_window_that_hides_nothing_is_the_causal_call(arm, window):
    """To the bit, forward and backward: no window, one as long as the
    sequence and one longer are one program."""
    q, k, v, ct = _inputs(2, 1, seed=9)
    causal = {"fused": lambda q, k, v: attention.fused_causal_attention(
        q, k, v, TILES, True),
        "blocks": lambda q, k, v: attention.causal_attention(q, k, v, 128)}
    ours = _with_gradients(ARMS[arm](window), q, k, v, ct)
    plain = _with_gradients(causal[arm], q, k, v, ct)
    for a, b in zip(ours, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert attention.effective_window(window, LENGTH) is None
    assert attention.effective_window(LENGTH - 1, LENGTH) == LENGTH - 1


def test_attention_hands_the_window_to_the_plain_arm_here():
    q, k, v, _ = _inputs(2, 1, seed=3)
    np.testing.assert_array_equal(
        np.asarray(attention.attention(q, k, v, 96, 40)),
        np.asarray(attention.causal_attention(q, k, v, 96, 40)))
    assert float(jnp.abs(attention.attention(q, k, v, 96, 40)
                         - attention.attention(q, k, v, 96)).max()) > 1e-3


# ---------------------------------------------------- the tiles visited


def _in_band(i, j, bq, bkv, window):
    """Whether tile (i, j) holds a pair (row, col) with ``0 <= row - col <
    window``."""
    nearest = max(i * bq - ((j + 1) * bkv - 1), 0)
    farthest = (i + 1) * bq - 1 - j * bkv
    return farthest >= 0 and nearest < (window or math.inf)


def _walk_query_sweep(length, bq, bkv, window):
    """{(query tile, key tile)} that the forward and dQ passes' grid
    fetches for one head, through ``_query_sweep``'s own index map."""
    q = jax.ShapeDtypeStruct((1, length, 128), jnp.bfloat16)
    grid, _, kv_spec, _ = kernel._query_sweep(q, 1, 1, bq, bkv, window)
    return grid, {(i, int(kv_spec.index_map(0, 0, i, j)[1]))
                  for i in range(grid[2]) for j in range(grid[3])}


def _walk_key_sweep(length, bq, bkv, window):
    q = jax.ShapeDtypeStruct((1, length, 128), jnp.bfloat16)
    grid, steps, q_spec, _, row_spec = kernel._key_sweep(q, 1, 1, bq, bkv,
                                                         window)
    fetched = set()
    for j in range(grid[2]):
        for i in range(steps):
            tile = int(q_spec.index_map(0, 0, j, 0, i)[1])
            assert int(row_spec.index_map(0, 0, j, 0, i)[3]) == tile
            fetched.add((tile, j))
    return grid, fetched


@pytest.mark.parametrize("length,window,tile,visited,below", [
    (16384, 2048, 1024, 45, 136),      # the cell's: 1 + 2 + 14 x 3
    (16384, 2048, 512, 150, 528),      # 1 + 2 + 3 + 4 + 28 x 5
    (8192, 2048, 1024, 21, 36),
    (16384, None, 1024, 136, 136)])
def test_each_pass_visits_the_bands_tiles_and_no_other(length, window, tile,
                                                       visited, below):
    """At 16,384 positions, a window of 2,048 and 1,024 x 1,024 tiles each
    pass computes 45 tiles a head of the 136 on or below the diagonal, and
    its grid fetches those and no tile wholly outside the band: the grid's
    innermost axis is 3 steps long, not 16."""
    tiles = attention.Tiles(*((tile, tile),) * 3)
    assert attention.visited_tiles(length, window, tiles) == dict.fromkeys(
        ("fwd", "dq", "dkv"), (visited, below))
    band = {(i, j) for i in range(length // tile)
            for j in range(length // tile)
            if _in_band(i, j, tile, tile, window)}
    assert len(band) == visited
    for walk, listed in ((_walk_query_sweep, kernel.query_sweep_tiles),
                         (_walk_key_sweep, kernel.key_sweep_tiles)):
        grid, fetched = walk(length, tile, tile, window)
        assert fetched == band == set(listed(length, tile, tile, window))
        # the innermost axis: as long as the band's widest sweep
        assert grid[-1] == max(sum(1 for tile_ in band if tile_[0] == i)
                               for i in range(length // tile))
    assert attention.visited_tiles(length, length, tiles)["fwd"] == (
        below, below)


def test_uneven_tiles_visit_their_own_band():
    """Query tiles of 256 on key tiles of 128 and the other way about: the
    walked grid fetches exactly the tiles that hold a pair of the band."""
    length, window = 2048, 300
    for bq, bkv in ((256, 128), (128, 256)):
        band = {(i, j) for i in range(length // bq)
                for j in range(length // bkv)
                if _in_band(i, j, bq, bkv, window)}
        assert _walk_query_sweep(length, bq, bkv, window)[1] == band
        assert _walk_key_sweep(length, bq, bkv, window)[1] == band
