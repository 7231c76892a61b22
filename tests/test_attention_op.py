"""``ops/attention.py``: the fused causal grouped-query attention kernel
(in Pallas's interpreter, on the CPU) against the plain arm and against a
float32 evaluation of the same rounded inputs; causality; the rule that
picks the arm."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_lm_util import pallas_bodies, pallas_calls

from imaginaire_tpu.ops import attention

# 4 query heads over 2 key-value heads, head size 128, 512 positions; tiles
# that differ between the passes and between queries and keys, so every
# pass sees tiles below, on and (skipped) above the diagonal
SHAPE = dict(bsz=2, length=512, q_heads=4, kv_heads=2, dim=128)
TILES = attention.Tiles(fwd=(256, 128), bwd=(128, 256))
SMALL = attention.Tiles(fwd=(128, 128), bwd=(128, 128))
NAMES = ("out", "dq", "dk", "dv")


def _inputs(seed=0, dtype=jnp.bfloat16, **over):
    s = dict(SHAPE, **over)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = [(s["bsz"], s["length"], s["q_heads"], s["dim"]),
              (s["bsz"], s["length"], s["kv_heads"], s["dim"]),
              (s["bsz"], s["length"], s["kv_heads"], s["dim"]),
              (s["bsz"], s["length"], s["q_heads"] * s["dim"])]
    return [jax.random.normal(k, shape, jnp.float32).astype(dtype)
            for k, shape in zip(keys, shapes)]


def _fused(q, k, v, tiles=TILES):
    return attention.fused_causal_attention(q, k, v, tiles, True)


def _with_gradients(fn, q, k, v, ct):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out, *vjp(ct.astype(out.dtype)))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@functools.lru_cache(maxsize=None)
def _three_ways():
    """(fused, plain, float32) outputs and gradients on one set of
    bfloat16 inputs; the float32 side evaluates the same rounded inputs."""
    q, k, v, ct = _inputs()
    fused = _with_gradients(_fused, q, k, v, ct)
    plain = _with_gradients(
        lambda q, k, v: attention.causal_attention(q, k, v, 128), q, k, v, ct)
    exact = _with_gradients(
        lambda q, k, v: attention.causal_attention(q, k, v, 512),
        *(x.astype(jnp.float32) for x in (q, k, v)), ct)
    return fused, plain, exact


@pytest.mark.parametrize("which", range(4), ids=NAMES)
def test_fused_arm_matches_plain_arm(which):
    fused, plain, _ = _three_ways()
    assert fused[which].shape == plain[which].shape
    assert fused[which].dtype == plain[which].dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(fused[which], np.float32)).all()
    # two bfloat16 evaluations: each is some 2 to 3e-3 from float32
    assert _rel(fused[which], plain[which]) < 6e-3


@pytest.mark.parametrize("which", range(4), ids=NAMES)
def test_fused_arm_is_as_close_to_float32_as_the_plain_arm(which):
    fused, plain, exact = _three_ways()
    fused_err = _rel(fused[which], exact[which])
    plain_err = _rel(plain[which], exact[which])
    assert plain_err < 5e-3
    assert fused_err <= 1.25 * plain_err, (fused_err, plain_err)


@pytest.mark.parametrize("which", range(4), ids=NAMES)
def test_fused_arm_at_head_size_256_matches_plain_arm(which):
    """The latent-attention cell's head: 256 wide, as many key-value heads
    as query heads; 256 positions in tiles of 128."""
    fused, plain = _at_256()
    assert fused[which].shape == plain[which].shape
    assert np.isfinite(np.asarray(fused[which], np.float32)).all()
    assert _rel(fused[which], plain[which]) < 6e-3


@pytest.mark.parametrize("which", range(4), ids=NAMES)
def test_fused_arm_at_head_size_64_matches_plain_arm(which):
    """The short-convolution cell's head (ISSUE 39): 64 wide, 4 query
    heads on 2; the arm zero-pads each head to the kernel's 128 lanes
    under the scale of 64, and output and gradients come back 64 wide,
    as close to float32 as the plain arm's."""
    fused, plain, exact = _at_64()
    assert fused[which].shape == plain[which].shape
    assert fused[which].dtype == plain[which].dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(fused[which], np.float32)).all()
    assert _rel(fused[which], plain[which]) < 6e-3
    assert _rel(fused[which], exact[which]) <= 1.25 * _rel(plain[which],
                                                          exact[which])


@functools.lru_cache(maxsize=None)
def _at_64():
    q, k, v, ct = _inputs(seed=7, bsz=1, length=256, dim=64)
    plain = lambda q, k, v: attention.causal_attention(q, k, v, 128)  # noqa: E731
    return (_with_gradients(lambda q, k, v: _fused(q, k, v, SMALL),
                            q, k, v, ct),
            _with_gradients(plain, q, k, v, ct),
            _with_gradients(plain, *(x.astype(jnp.float32)
                                     for x in (q, k, v)), ct))


def test_a_padded_head_runs_the_kernel_at_a_lane_tile():
    """Head size 64 reaches the two kernels 128 wide (4 heads: 512
    columns) and under 1/sqrt(64); a head of 128 reaches them as it is."""
    from imaginaire_tpu.ops.pallas import causal_attention_kernel as kernel

    assert [attention.kernel_head_dim(d) for d in (64, 96, 128, 192, 256)] \
        == [128, 128, 128, 256, 256]
    q, k, v, ct = _inputs(seed=8, bsz=1, length=128, dim=64)
    seen = {}

    def spy(name):
        real = getattr(kernel, name)

        def call(q, *rest, scale=None, **kwargs):
            seen[name] = (q.shape[-1], scale)
            return real(q, *rest, scale=scale, **kwargs)
        return call

    with pytest.MonkeyPatch.context() as patch:
        for name in ("forward", "backward"):
            patch.setattr(kernel, name, spy(name))
        _with_gradients(lambda q, k, v: _fused(q, k, v, SMALL), q, k, v, ct)
    assert seen == dict.fromkeys(("forward", "backward"), (4 * 128, 0.125))


@functools.lru_cache(maxsize=None)
def _at_256():
    q, k, v, ct = _inputs(seed=6, bsz=1, length=256, q_heads=2, kv_heads=2,
                          dim=256)
    return (_with_gradients(lambda q, k, v: _fused(q, k, v, SMALL),
                            q, k, v, ct),
            _with_gradients(
                lambda q, k, v: attention.causal_attention(q, k, v, 128),
                q, k, v, ct))


# each cell's layout at a small length: (query heads, key-value heads, head
# size, sequences, window), 512 positions in tiles of 128, so that a sweep
# comes back to the key-value head's standing ``dk``, ``dv`` four times a
# query head
LAYOUTS = {
    "glm4_7_flash": (2, 2, 256, 1, None),         # group 1 at head 256
    "nemotron3_nano": (32, 2, 128, 1, None),      # 16 query heads a head
    "solar_open2": (8, 1, 128, 1, None),
    "lfm2_8b_a1b": (32, 8, 64, 2, None),          # padded to 128, 2 sequences
    # the band's lower edge crosses tiles inside: 200 is no multiple of 128
    "trinity_mini": (32, 4, 128, 1, 200),
}


@functools.lru_cache(maxsize=None)
def _at_layout(name):
    q_heads, kv_heads, dim, bsz, window = LAYOUTS[name]
    q, k, v, ct = _inputs(seed=len(name), bsz=bsz, q_heads=q_heads,
                          kv_heads=kv_heads, dim=dim)
    return (_with_gradients(
        lambda q, k, v: attention.fused_causal_attention(
            q, k, v, SMALL, True, window), q, k, v, ct),
        _with_gradients(lambda q, k, v: attention.causal_attention(
            q, k, v, 128, window), q, k, v, ct))


@pytest.mark.parametrize("which", range(4), ids=NAMES)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_fused_arm_at_each_cells_layout_matches_plain_arm(layout, which):
    """ISSUE 42: one backward sweep serves every cell's heads; the
    key-value head's gradients stand in VMEM across its query heads and
    their query tiles, and come out whole."""
    fused, plain = _at_layout(layout)
    assert fused[which].shape == plain[which].shape
    assert fused[which].dtype == plain[which].dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(fused[which], np.float32)).all()
    assert _rel(fused[which], plain[which]) < 6e-3


def _products_a_branch(jaxpr):
    """The ``dot_general``s of each innermost branch of a kernel's body
    that holds any (``pl.when`` is a ``cond``)."""
    counts, here = [], 0
    for eqn in jaxpr.eqns:
        here += eqn.primitive.name == "dot_general"
        for inner in jax.core.jaxprs_in_params(eqn.params):
            counts.extend(_products_a_branch(inner))
    return counts + [here] * bool(here)


@pytest.mark.parametrize("window", [None, 200])
def test_the_backward_is_one_kernel_of_five_products_a_tile(window):
    """ISSUE 42: the gradient of the fused arm holds the forward kernel
    and ONE backward kernel; the backward's body computes five products
    in the tile with the mask and five in the tile without (the scores,
    ``do v^T`` and the three gradients), the forward's two."""
    q, k, v, ct = _inputs(seed=9, bsz=1)

    def loss(q, k, v):
        out = attention.fused_causal_attention(q, k, v, SMALL, True, window)
        return jnp.sum(out.astype(jnp.float32) * ct.astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr
    products = {name: _products_a_branch(body)
                for name, body in pallas_bodies(jaxpr)}
    assert attention.BACKWARD_PRODUCTS == 5
    assert products == {"causal_gqa_fwd": [2, 2], "causal_gqa_bwd": [5, 5]}


def test_fused_arm_in_float32_is_the_plain_arm():
    """In float32 nothing is rounded on the way: the two arms differ by
    the order of their sums alone."""
    q, k, v, ct = _inputs(seed=1, dtype=jnp.float32, bsz=1)
    fused = _with_gradients(_fused, q, k, v, ct)
    plain = _with_gradients(
        lambda q, k, v: attention.causal_attention(q, k, v, 512), q, k, v, ct)
    for name, f, p in zip(NAMES, fused, plain):
        assert _rel(f, p) < 2e-5, name


@pytest.mark.parametrize("t", [0, 127, 128, 300, 511])
def test_nothing_after_a_position_reaches_it(t):
    q, k, v, _ = _inputs(seed=2, bsz=1)
    noise = _inputs(seed=3, bsz=1)
    after = (jnp.arange(SHAPE["length"]) > t)[None, :, None, None]
    k2 = jnp.where(after, noise[1], k)
    v2 = jnp.where(after, noise[2], v)
    a = np.asarray(_fused(q, k, v), np.float32)
    b = np.asarray(_fused(q, k2, v2), np.float32)
    np.testing.assert_array_equal(a[:, :t + 1], b[:, :t + 1])
    if t + 1 < SHAPE["length"]:
        assert np.abs(a[:, t + 1:] - b[:, t + 1:]).max() > 0


def test_one_tile_and_many_tiles_agree():
    q, k, v, _ = _inputs(seed=4, bsz=1)
    whole = attention.Tiles(fwd=(512, 512), bwd=(512, 512))
    assert _rel(_fused(q, k, v, SMALL), _fused(q, k, v, whole)) < 4e-3


@pytest.mark.parametrize("backend, dim, length, arm", [
    ("tpu", 128, 8192, "fused"),
    ("tpu", 256, 2048, "fused"),
    ("cpu", 128, 8192, "blocks"),      # where the tests run
    ("tpu", 128, 8192 + 50, "blocks"),  # a ragged length
    ("tpu", 128, 512, "blocks"),       # shorter than a tile
    ("tpu", 64, 8192, "fused"),        # zero-padded to a lane tile
    ("tpu", 192, 8192, "blocks"),      # no padded form but the half tile's
    ("tpu", 96, 8192, "blocks"),
    ("tpu", 32, 8192, "blocks"),       # padding would quadruple the work
    ("tpu", 16, 8192, "blocks"),
    ("cpu", 64, 8192, "blocks"),
    # ISSUE 42: a key-value head's dk and dv stand in VMEM through the
    # backward sweep: 64 MiB of them fit beside the tiles, 128 do not
    ("tpu", 128, 65536, "fused"),
    ("tpu", 64, 65536, "fused"),
    ("tpu", 256, 32768, "fused"),
    ("tpu", 128, 131072, "blocks"),
    ("tpu", 256, 65536, "blocks"),
])
def test_the_rule(monkeypatch, backend, dim, length, arm):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert attention.arm_of(dim, length) == arm


@pytest.mark.parametrize("length", [64, 50])
def test_attention_takes_the_plain_arm_here(length):
    """On the CPU ``attention`` is ``causal_attention`` at its own
    ``QUERY_BLOCK``, to the bit; several blocks of a length the block
    divides and of a ragged one give what the one block gives."""
    q, k, v, _ = _inputs(seed=5, bsz=1, length=length, dim=16)
    assert attention.arm_of(16, length) == "blocks"
    ours = np.asarray(attention.attention(q, k, v), np.float32)
    np.testing.assert_array_equal(ours, np.asarray(
        attention.causal_attention(q, k, v, attention.QUERY_BLOCK),
        np.float32))
    np.testing.assert_allclose(ours, np.asarray(
        attention.causal_attention(q, k, v, 32), np.float32), atol=2e-6)


def test_tiles_are_lane_multiples_and_divide_the_cells_length():
    assert all(n % 128 == 0 for pair in attention.TILES for n in pair)
    assert 8192 % attention.TILES.largest == 0


# -------------------------------------------- under a block's recompute


def _block_gradients(dim, policy):
    """(the kernel calls in the gradient's jaxpr, the gradients) of a
    block ``x -> qkv -> fused attention -> W_o`` recomputed under a remat
    policy; head size 128 with 4 query heads on 2, 256 with 2 on 2."""
    from imaginaire_tpu.optim.remat import POLICIES

    q_heads, kv_heads = (4, 2) if dim == 128 else (2, 2)
    hidden, length = 64, 256
    keys = jax.random.split(jax.random.PRNGKey(dim), 5)
    x = jax.random.normal(keys[0], (1, length, hidden)).astype(jnp.bfloat16)
    widths = [q_heads * dim, kv_heads * dim, kv_heads * dim]
    kernels = [(jax.random.normal(k, (hidden, w)) / 8).astype(jnp.bfloat16)
               for k, w in zip(keys[1:4], widths)]
    kernels.append((jax.random.normal(keys[4], (q_heads * dim, hidden))
                    / 16).astype(jnp.bfloat16))

    def block(x, kernels):
        w_q, w_k, w_v, w_o = kernels
        q, k, v = ((x @ w).reshape(1, length, -1, dim)
                   for w in (w_q, w_k, w_v))
        return _fused(q, k, v, SMALL) @ w_o

    def loss(x, kernels):
        out = jax.checkpoint(block, policy=POLICIES[policy].policy)(x, kernels)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1))
    return (pallas_calls(jax.make_jaxpr(grad)(x, kernels).jaxpr),
            jax.tree_util.tree_leaves(grad(x, kernels)))


@pytest.mark.parametrize("dim", [128, 256])
def test_a_recomputed_block_runs_the_forward_kernel_once(dim):
    """ISSUE 33: under ``blocks`` the block keeps the output and the
    log-sum-exp the kernel's forward pass handed its backward passes, so
    its recompute holds no second forward call; under ``save_nothing`` it
    holds one; and the kept arrays are the ones the second call would have
    written, so no gradient moves by a bit."""
    kept_calls, kept = _block_gradients(dim, "blocks")
    again_calls, again = _block_gradients(dim, "save_nothing")
    assert sorted(kept_calls) == ["causal_gqa_bwd", "causal_gqa_fwd"]
    assert sorted(again_calls) == [
        "causal_gqa_bwd", "causal_gqa_fwd", "causal_gqa_fwd"]
    assert len(kept) == len(again) == 5
    for a, b in zip(kept, again):
        assert np.abs(np.asarray(a, np.float32)).max() > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
