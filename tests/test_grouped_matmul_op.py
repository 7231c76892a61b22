"""``ops/grouped_matmul.py``: the grouped-product kernels (in Pallas's
interpreter, on the CPU) against ``lax.ragged_dot`` and its ``jax.vjp``,
forward and both gradients; what the rows past the last group hold; the
rule that picks the arm; the tiles it picks from the widths; the
``moe_impl`` meta."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from hybrid_lm_util import ROOT, pallas_calls

from imaginaire_tpu.ops import grouped_matmul
from imaginaire_tpu.ops.pallas import grouped_matmul_kernel as kernel

ROWS = 256
NAMES = ("out", "dlhs", "drhs")
# name -> (contracted, width, group sizes, (rows, width) tile); the
# contraction stands whole, so the gradient to the rows has its width
# and is cut into the same tile
CASES = {
    # a width of a lane tile and a half; an empty group, boundaries inside
    # a row tile, and groups that end before the rows do
    "ragged_width": (256, 192, (40, 0, 100, 70), (64, 128)),
    # a contraction of two lane tiles and a half (the rows' gradient's
    # ragged width), every row filled, boundaries on the tiles' edges
    "ragged_contraction": (320, 256, (64, 64, 64, 64), (64, 128)),
    # both sizes ragged, every row filled, boundaries inside the tiles,
    # an empty group in the middle
    "both_ragged": (320, 192, (30, 90, 0, 136), (64, 128)),
    # three empty groups and a short last one: one visit in all
    "one_late_group": (256, 192, (0, 0, 0, 30), (128, 128)),
}
SLOW_CASES = {
    "wide_tiles": (640, 448, (100, 1, 0, 80, 60), (128, 256)),
    "many_groups": (384, 320, (3, 0, 50, 64, 1, 17, 0, 90), (64, 128)),
}


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _operands(contracted, width, rows=ROWS, groups=4, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(keys[0], (rows, contracted), jnp.float32)
    rhs = jax.random.normal(keys[1], (groups, contracted, width),
                            jnp.float32) * contracted ** -0.5
    dout = jax.random.normal(keys[2], (rows, width), jnp.float32)
    return [x.astype(jnp.bfloat16) for x in (lhs, rhs, dout)]


def _tiles(tile):
    return grouped_matmul.Tiles(fwd=tile, dlhs=tile)


@functools.lru_cache(maxsize=None)
def _both_ways(name):
    """(kernel, ``lax.ragged_dot``, float32 ``lax.ragged_dot``) output
    and gradients of a case, and its filled rows. The cotangent is zero
    past the last group, as ``held_experts_part``'s masks make it."""
    contracted, width, sizes, tile = {**CASES, **SLOW_CASES}[name]
    lhs, rhs, dout = _operands(contracted, width, groups=len(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    filled = int(sizes.sum())
    assert filled <= ROWS
    dout = dout.at[filled:].set(0)

    def gradients(fn, *operands):
        out, vjp = jax.vjp(fn, *operands)
        return (out, *vjp(dout.astype(out.dtype)))

    got = gradients(lambda a, b: grouped_matmul.kernel_grouped_matmul(
        a, b, sizes, _tiles(tile), True), lhs, rhs)
    want = gradients(lambda a, b: lax.ragged_dot(a, b, sizes), lhs, rhs)
    exact = gradients(lambda a, b: lax.ragged_dot(a, b, sizes),
                      lhs.astype(jnp.float32), rhs.astype(jnp.float32))
    return got, want, exact, filled


def _check(name, which):
    got, want, exact, filled = _both_ways(name)
    got, want, exact = got[which], want[which], exact[which]
    assert got.shape == want.shape
    assert got.dtype == want.dtype == jnp.bfloat16
    if NAMES[which] != "drhs":
        # the rows past the last group are nobody's to write
        got, want, exact = got[:filled], want[:filled], exact[:filled]
    assert np.isfinite(np.asarray(got, np.float32)).all()
    # two float32-accumulated bfloat16 evaluations (the tolerance of
    # test_attention_op.py), the kernel no further from float32
    assert _rel(got, want) < 6e-3
    assert _rel(got, exact) <= 1.25 * _rel(want, exact) + 1e-6


@pytest.mark.parametrize("which", range(3), ids=NAMES)
@pytest.mark.parametrize("name", CASES)
def test_kernel_matches_ragged_dot(name, which):
    _check(name, which)


@pytest.mark.slow
@pytest.mark.parametrize("which", range(3), ids=NAMES)
@pytest.mark.parametrize("name", SLOW_CASES)
def test_kernel_matches_ragged_dot_on_wider_cases(name, which):
    _check(name, which)


def test_an_empty_groups_weights_get_a_zero_gradient():
    got, _, _, _ = _both_ways("ragged_width")
    assert not np.asarray(got[2][1], np.float32).any()
    assert np.asarray(got[2][0], np.float32).any()


def test_tiles_past_the_last_group_are_not_visited():
    """The grid's bound is the number of visits: of a buffer of four row
    tiles that holds 30 rows, one tile is computed. The interpreter
    leaves what no visit wrote as NaN, the chip as whatever stood
    there."""
    got, _, _, filled = _both_ways("one_late_group")
    out, dlhs = (np.asarray(x, np.float32) for x in got[:2])
    assert filled == 30
    assert np.isfinite(out[:filled]).all() and np.isnan(out[128:]).all()
    assert np.isfinite(dlhs[:filled]).all() and np.isnan(dlhs[128:]).all()
    sizes = jnp.asarray(CASES["one_late_group"][2], jnp.int32)
    assert int(kernel.visits(sizes, ROWS, 128, False)[3]) == 1
    # the weights' gradient also visits each empty group once, to zero it
    assert int(kernel.visits(sizes, ROWS, 128, True)[3]) == 4


def test_a_tile_two_groups_share_is_visited_once_for_each():
    sizes = jnp.asarray((40, 0, 100, 70), jnp.int32)
    offsets, groups, tiles, count = kernel.visits(sizes, ROWS, 64, False)
    assert offsets.tolist() == [0, 40, 40, 140, 210]
    assert int(count) == 6
    assert groups[:6].tolist() == [0, 2, 2, 2, 3, 3]
    assert tiles[:6].tolist() == [0, 0, 1, 2, 2, 3]
    assert groups.shape == tiles.shape == (ROWS // 64 + 4 - 1,)


def test_the_three_kernels_carry_their_own_names():
    lhs, rhs, _ = _operands(256, 192)
    sizes = jnp.asarray((40, 0, 100, 70), jnp.int32)

    def loss(lhs, rhs):
        return grouped_matmul.kernel_grouped_matmul(
            lhs, rhs, sizes, _tiles((64, 128)), True
        ).astype(jnp.float32)[:210].sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(lhs, rhs)
    assert sorted(pallas_calls(jaxpr.jaxpr)) == [
        "grouped_rows_dlhs", "grouped_rows_fwd", "grouped_weights_drhs"]


@pytest.mark.parametrize("backend,rows,contracted,width,arm", [
    ("tpu", 8192, 2688, 1856, "kernel"),       # Nemotron's up product
    ("tpu", 49152, 1856, 2688, "kernel"),      # its down, the whole buffer
    ("tpu", 8192, 4096, 1280, "kernel"),       # Solar's
    ("tpu", 128, 64, 48, "ragged_dot"),        # the unit-test YAML's
    ("tpu", 8192 + 64, 2048, 1536, "ragged_dot"),   # rows the tile splits
    ("tpu", 8192, 2048, 96, "ragged_dot"),     # under a lane tile
    ("tpu", 8192, 8192, 2048, "ragged_dot"),   # a contraction not measured
    ("cpu", 8192, 2688, 1856, "ragged_dot"),
])
def test_arm_of(monkeypatch, backend, rows, contracted, width, arm):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert grouped_matmul.arm_of(rows, contracted, width) == arm


def test_the_plain_arm_is_ragged_dot_and_nothing_else():
    """On the CPU ``grouped_matmul`` lowers to the text ``lax.ragged_dot``
    lowers to: the step programs of the tests and of a CPU run are what
    they were before the kernel."""
    lhs, rhs, _ = _operands(64, 48, rows=128)
    sizes = jnp.asarray((40, 0, 60, 10), jnp.int32)
    ours = jax.jit(grouped_matmul.grouped_matmul).lower(lhs, rhs, sizes)
    plain = jax.jit(lax.ragged_dot).lower(lhs, rhs, sizes)
    assert ours.as_text().replace("grouped_matmul", "ragged_dot") \
        == plain.as_text()


@pytest.mark.parametrize("contracted,width", [
    (2688, 1856), (1856, 2688), (2048, 1536), (1536, 2048), (4096, 1280),
    (1280, 4096)])
def test_tiles_fit_the_widths_they_are_given(contracted, width):
    """Every width tile is a multiple of 128 lanes, the row tile is the
    one ``arm_of`` asks the rows to be a multiple of, and no tiling
    computes more than a twentieth past an axis's edge (1856 in three
    tiles of 640: 3.4 %)."""
    tiles = grouped_matmul.tiles_of(contracted, width)
    for (tm, tn), n in ((tiles.fwd, width), (tiles.dlhs, contracted)):
        assert tm == grouped_matmul.ROW_TILE
        assert tn % kernel.LANES == 0
        assert -(-n // tn) * tn <= 1.05 * n


@pytest.mark.parametrize("width,tile", [
    (1856, 640), (2688, 896), (1280, 640), (2048, 1024), (4096, 1024),
    (1536, 768), (48, 128), (192, 256)])
def test_the_width_tile_reaches_least_past_the_edge(width, tile):
    assert grouped_matmul.width_tile(width) == tile


@pytest.mark.parametrize("yaml,layers,hidden,width,tiers", [
    ("nemotron_h/nano_30b_a3b_ep16_share.yaml", [1, 3, 6, 8], 2688, 1856,
     [8192, 49152]),
    ("glm4_moe_lite/flash_ep8_share.yaml", [3, 5, 7, 9, 11], 2048, 1536,
     [8192, 32768]),
    ("solar_open2/250b_ep40_tp8_share.yaml", [1, 3, 5, 7], 4096, 1280,
     [8192, 65536]),
])
def test_moe_impl_says_what_the_step_runs(monkeypatch, yaml, layers, hidden,
                                          width, tiers):
    """The ``moe_impl`` meta of the published configurations' step: the
    plain arm here, the kernel on a TPU, in every expert layer; the sizes,
    the tiers and the tiles of both products."""
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.trainers import lm

    gen = Config(os.path.join(ROOT, "configs", "projects", yaml)).gen
    here = lm.moe_impl(gen, (1, 8192))
    assert here["layers"] == {str(i): "ragged_dot" for i in layers}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meta = lm.moe_impl(gen, (1, 8192))
    assert meta["layers"] == {str(i): "kernel" for i in layers}
    assert (meta["hidden"], meta["width"], meta["held"]) == (hidden, width, 8)
    assert meta["tiers"] == tiers
    assert meta["tiles"] == {
        "up": grouped_matmul.tiles_of(hidden, width)._asdict(),
        "down": grouped_matmul.tiles_of(width, hidden)._asdict()}
    assert set(meta["tiles"]["up"]) == {"fwd", "dlhs"}
    import json
    json.dumps(meta)


def test_a_model_without_expert_layers_has_no_moe_impl():
    from hybrid_lm_util import tiny_cfg
    from imaginaire_tpu.trainers import lm

    assert lm.moe_impl(tiny_cfg(pattern="M*M").gen, (2, 64)) is None
