"""``ops/attention.py`` under a sliding window (ISSUE 41): both arms (the
fused kernel in Pallas's interpreter) against a dense masked softmax in
float32, forward and the three gradients; a window that hides nothing is
the causal call to the bit; the tiles the kernel's passes visit, walked
through their own grids and index maps; and where the one backward
sweep (ISSUE 42) writes a key tile's gradients out."""

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
TILES = attention.Tiles(fwd=(256, 128), bwd=(128, 256))
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
# over the larger tile, and a window of one key: the query's own; the
# last is the window cell's heads, 8 query heads a key-value head
@pytest.mark.parametrize("window,q_heads,kv_heads", [
    (1, 2, 2), (40, 2, 2), (128, 8, 1), (200, 2, 2), (300, 2, 1),
    (200, 32, 4)])
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
        np.asarray(attention.attention(q, k, v, 40)),
        np.asarray(attention.causal_attention(
            q, k, v, attention.QUERY_BLOCK, 40)))
    assert float(jnp.abs(attention.attention(q, k, v, 40)
                         - attention.attention(q, k, v)).max()) > 1e-3


# ---------------------------------------------------- the tiles visited


def _in_band(i, j, bq, bkv, window):
    """Whether tile (i, j) holds a pair (row, col) with ``0 <= row - col <
    window``."""
    nearest = max(i * bq - ((j + 1) * bkv - 1), 0)
    farthest = (i + 1) * bq - 1 - j * bkv
    return farthest >= 0 and nearest < (window or math.inf)


def _walk_query_sweep(length, bq, bkv, window):
    """{(query tile, key tile)} that the forward pass's grid fetches for
    one head, through ``_query_sweep``'s own index map."""
    q = jax.ShapeDtypeStruct((1, length, 128), jnp.bfloat16)
    grid, _, kv_spec = kernel._query_sweep(q, 1, 1, bq, bkv, window)
    return grid, {(i, int(kv_spec.index_map(0, 0, i, j)[1]))
                  for i in range(grid[2]) for j in range(grid[3])}


def _walk_backward_sweep(length, bq, bkv, window, group=1):
    """The same of the backward pass's grid, and [(query head of the
    group, query tile, key tile fetched, key tile of ``dk``, ``dv``
    named)] step by step for one key-value head."""
    q = jax.ShapeDtypeStruct((1, length, 128 * group), jnp.bfloat16)
    grid, q_spec, kv_spec, row_spec, out_spec = kernel._backward_sweep(
        q, group, 1, bq, bkv, window)
    assert grid[:3] == (1, 1, group)
    steps = []
    for r in range(group):
        for i in range(grid[3]):
            for j in range(grid[4]):
                at = (0, 0, r, i, j)
                assert (q_spec.index_map(*at)[1:] == (i, r)
                        and row_spec.index_map(*at) == (0, r, 0, i))
                steps.append((r, i, int(kv_spec.index_map(*at)[1]),
                              int(out_spec.index_map(*at)[1])))
    return grid, {(i, j) for _, i, j, _ in steps}, steps


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
    tiles = attention.Tiles(*((tile, tile),) * 2)
    assert attention.visited_tiles(length, window, tiles) == dict.fromkeys(
        ("fwd", "dq", "dkv"), (visited, below))
    band = {(i, j) for i in range(length // tile)
            for j in range(length // tile)
            if _in_band(i, j, tile, tile, window)}
    assert len(band) == visited
    for walk in (_walk_query_sweep, _walk_backward_sweep):
        grid, fetched = walk(length, tile, tile, window)[:2]
        assert fetched == band == set(kernel.query_sweep_tiles(
            length, tile, tile, window))
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
        assert _walk_backward_sweep(length, bq, bkv, window)[1] == band


@pytest.mark.parametrize("length,window,bq,bkv", [
    (16384, 2048, 1024, 1024), (8192, None, 1024, 1024),
    (2048, 300, 256, 128), (2048, None, 128, 256)])
def test_a_backward_sweep_ends_on_its_last_tile(length, window, bq, bkv):
    """The steps a short sweep does not need come FIRST and name its
    first tile: a new query tile's blocks are all asked for during the
    sweep before's last tile, and every sweep's last step is the tile on
    the diagonal (where the kernel writes ``dq``)."""
    grid, _, steps = _walk_backward_sweep(length, bq, bkv, window)
    tiles = kernel.query_sweep_tiles(length, bq, bkv, window)
    for i in range(grid[3]):
        fetched = [j for _, i_, j, _ in steps if i_ == i]
        needed = [j for i_, j in tiles if i_ == i]
        idle = grid[4] - len(needed)
        assert fetched == [needed[0]] * idle + needed
        assert fetched[-1] == kernel._last_kv(i, bq, bkv)


@pytest.mark.parametrize("length,window,bq,bkv,group", [
    (16384, 2048, 1024, 1024, 8),      # the window cell's
    (16384, None, 1024, 1024, 8),
    (8192, None, 1024, 1024, 1),       # one query head a key-value head
    (2048, 300, 256, 128, 2), (2048, 300, 128, 256, 2),
    (2048, None, 256, 128, 2), (2048, None, 128, 256, 2),
    (512, 1, 128, 128, 4)])
def test_a_key_tiles_gradients_leave_once_and_whole(length, window, bq, bkv,
                                                    group):
    """ISSUE 42: ``dk``, ``dv`` of a key-value head stand in VMEM while
    its query heads' sweeps add to them. Walked through the backward
    pass's own index maps and ``_whole_kv``: the output block named
    changes only to a tile whose last contribution is that very step's
    (the kernel writes it then, by the same predicate), never comes back
    to a tile it left, and every tile is named at its last contribution;
    so each tile reaches HBM once, after every sum into it."""
    q_tiles, kv_tiles = length // bq, length // bkv
    grid, _, steps = _walk_backward_sweep(length, bq, bkv, window, group)
    last_read = {}          # key tile -> its last (head, query tile)
    for r, i, j, _ in steps:
        last_read[j] = (r, i)
    assert sorted(last_read) == list(range(kv_tiles))
    written, named_before = set(), 0
    for r, i, j, named in steps:
        writes = (r == group - 1 and j < kernel._whole_kv(
            i, bq, bkv, window, q_tiles, kv_tiles))
        assert writes == ((r, i) == last_read[j])
        if named != named_before:
            # a block is left only once written, and none is come back to
            assert named_before in written and named not in written
            assert writes and named == j
        if writes:
            assert named == j
            written.add(j)
        named_before = named
    assert written == set(range(kv_tiles))
