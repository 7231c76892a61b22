"""``ops/attention.py`` at the early-router share's attention shape (ISSUE
45): seven query heads a key-value head (28 on 4) under a window of 4,096
at 16,384 positions. The fused kernel in Pallas's interpreter against the
dense masked softmax with groups of seven, at a small length and at the
cell's geometry scaled by eight (16 tiles a side, a window of 4 tiles);
the tiles each pass visits at the cell's own shape, 70 of 136, walked
through the kernel's grids and index maps; and where the backward sweep
writes a key tile's gradients out under a group of seven. The bodies are
``test_attention_window.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_attention_window as windowed

from imaginaire_tpu.ops import attention
from imaginaire_tpu.ops.pallas import causal_attention_kernel as kernel


@pytest.mark.parametrize("window,q_heads,kv_heads", [
    (200, 14, 2), (300, 7, 1), (None, 7, 1)])
@pytest.mark.parametrize("arm", sorted(windowed.ARMS))
def test_a_group_of_seven_follows_the_dense_masked_softmax(arm, window,
                                                           q_heads, kv_heads):
    windowed.test_the_window_follows_the_dense_masked_softmax(
        arm, window or windowed.LENGTH, q_heads, kv_heads)


def test_the_cells_geometry_scaled_by_eight_follows_the_dense_softmax():
    """2,048 positions in tiles of 128 under a window of 512: 16 tiles a
    side and a band 4 tiles wide, as 16,384 in tiles of 1,024 under
    4,096; seven query heads on one key-value head. Forward and the
    three gradients, and the tiles visited are the cell's count."""
    length, window, tile = 2048, 512, 128
    tiles = attention.Tiles(fwd=(tile, tile), bwd=(tile, tile))
    assert attention.visited_tiles(length, window, tiles)["fwd"] == (70, 136)
    keys = jax.random.split(jax.random.PRNGKey(45), 4)
    q, k, v, ct = (jax.random.normal(key, shape, jnp.float32)
                   for key, shape in zip(keys, [
                       (1, length, 7, 128), (1, length, 1, 128),
                       (1, length, 1, 128), (1, length, 7 * 128)]))
    ours = windowed._with_gradients(
        lambda q, k, v: attention.fused_causal_attention(
            q, k, v, tiles, True, window), q, k, v, ct)
    exact = windowed._with_gradients(
        lambda q, k, v: windowed._dense(q, k, v, window), q, k, v, ct)
    for name, a, b in zip(windowed.NAMES, ours, exact):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   rtol=3e-5, err_msg=name)


@pytest.mark.parametrize("length,window,tile,visited,below", [
    (16384, 4096, 1024, 70, 136),      # the cell's: 1 + 2 + 3 + 4 + 12 x 5
    (8192, 4096, 1024, 30, 36)])
def test_each_pass_visits_the_bands_tiles_and_no_other(length, window, tile,
                                                       visited, below):
    windowed.test_each_pass_visits_the_bands_tiles_and_no_other(
        length, window, tile, visited, below)
    # the innermost axis of both grids: the band's widest sweep, 5 tiles
    q = jax.ShapeDtypeStruct((1, length, 7 * 128), jnp.bfloat16)
    assert kernel._query_sweep(q, 7, 1, tile, tile, window)[0] == (
        1, 7, length // tile, 5)
    assert kernel._backward_sweep(q, 7, 1, tile, tile, window)[0] == (
        1, 1, 7, length // tile, 5)


@pytest.mark.parametrize("length,window,bq,bkv,group", [
    (16384, 4096, 1024, 1024, 7),      # the cell's
    (16384, None, 1024, 1024, 7),
    (2048, 300, 256, 128, 7)])
def test_a_key_tiles_gradients_leave_once_and_whole(length, window, bq, bkv,
                                                    group):
    windowed.test_a_key_tiles_gradients_leave_once_and_whole(
        length, window, bq, bkv, group)


def test_the_arm_is_the_fused_one_at_the_cells_shape(monkeypatch):
    """`arm_of` reads a head size and a length: 128 and 16,384 take the
    kernel on a TPU whatever the group; here, on the CPU, the plain arm."""
    assert attention.arm_of(128, 16384) == "blocks"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.arm_of(128, 16384) == "fused"
    assert attention.accumulator_bytes(16384, 128) == 16 * 2 ** 20
