"""Golden numerics for the native ops vs. independent numpy references.

The numpy references below re-derive the CUDA semantics documented in
SURVEY.md section 2.9 independently of the jnp implementations.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.ops import channelnorm, correlation, resample2d


def np_resample2d(x, flow):
    b, h, w, c = x.shape
    out = np.zeros_like(x)
    for bi in range(b):
        for i in range(h):
            for j in range(w):
                xf = j + flow[bi, i, j, 0]
                yf = i + flow[bi, i, j, 1]
                x0, y0 = np.floor(xf), np.floor(yf)
                ax, ay = xf - x0, yf - y0
                x0i = int(np.clip(x0, 0, w - 1))
                x1i = int(np.clip(x0 + 1, 0, w - 1))
                y0i = int(np.clip(y0, 0, h - 1))
                y1i = int(np.clip(y0 + 1, 0, h - 1))
                out[bi, i, j] = (
                    (1 - ay) * (1 - ax) * x[bi, y0i, x0i]
                    + (1 - ay) * ax * x[bi, y0i, x1i]
                    + ay * (1 - ax) * x[bi, y1i, x0i]
                    + ay * ax * x[bi, y1i, x1i]
                )
    return out


def np_correlation(x1, x2, pad, md, s2):
    b, h, w, c = x1.shape
    x2p = np.pad(x2, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    steps = list(range(-md, md + 1, s2))
    out = np.zeros((b, h, w, len(steps) ** 2), np.float32)
    d = 0
    for dy in steps:
        for dx in steps:
            shifted = x2p[:, pad + dy : pad + dy + h, pad + dx : pad + dx + w, :]
            out[..., d] = (x1 * shifted).sum(-1) / c
            d += 1
    return out


def test_resample2d_matches_reference(rng):
    x = rng.randn(2, 5, 6, 3).astype(np.float32)
    flow = (rng.randn(2, 5, 6, 2) * 2).astype(np.float32)
    got = np.asarray(resample2d(jnp.asarray(x), jnp.asarray(flow), implementation="jnp"))
    want = np_resample2d(x, flow)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_resample2d_identity_flow(rng):
    x = rng.randn(1, 4, 4, 2).astype(np.float32)
    flow = np.zeros((1, 4, 4, 2), np.float32)
    got = np.asarray(resample2d(jnp.asarray(x), jnp.asarray(flow), implementation="jnp"))
    np.testing.assert_allclose(got, x, rtol=1e-6)


def test_resample2d_grad_is_scatter_add(rng):
    # d/dx of a warp that maps two output pixels onto one input pixel must
    # accumulate both contributions (the CUDA atomicAdd semantics,
    # resample2d_kernel.cu:122-125).
    x = jnp.ones((1, 1, 3, 1), jnp.float32)
    flow = jnp.zeros((1, 1, 3, 2), jnp.float32).at[0, 0, 1, 0].set(-1.0)  # pixel 1 reads pixel 0
    g = jax.grad(lambda x_: resample2d(x_, flow, implementation="jnp").sum())(x)
    np.testing.assert_allclose(np.asarray(g)[0, 0, :, 0], [2.0, 0.0, 1.0])


@pytest.mark.parametrize("p", [1, 2])
def test_channelnorm(rng, p):
    x = rng.randn(2, 3, 4, 5).astype(np.float32)
    got = np.asarray(channelnorm(jnp.asarray(x), p=p))
    want = (np.abs(x) ** p).sum(-1, keepdims=True) ** (1.0 / p)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "mxu"])
def test_correlation(rng, impl):
    x1 = rng.randn(2, 6, 7, 4).astype(np.float32)
    x2 = rng.randn(2, 6, 7, 4).astype(np.float32)
    got = np.asarray(
        correlation(
            jnp.asarray(x1), jnp.asarray(x2), pad_size=2, max_displacement=2, stride2=1,
            implementation=impl,
        )
    )
    want = np_correlation(x1, x2, pad=2, md=2, s2=1)
    assert got.shape == want.shape == (2, 6, 7, 25)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "mxu"])
def test_correlation_stride2(rng, impl):
    x1 = rng.randn(1, 5, 5, 3).astype(np.float32)
    x2 = rng.randn(1, 5, 5, 3).astype(np.float32)
    got = np.asarray(
        correlation(jnp.asarray(x1), jnp.asarray(x2), pad_size=4, max_displacement=4, stride2=2,
                    implementation=impl)
    )
    want = np_correlation(x1, x2, pad=4, md=4, s2=2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_correlation_mxu_matches_jnp_flownetc_shape(rng):
    """The MXU matmul+band-gather formulation must be bit-comparable to
    the scan path at the FlowNetC operating configuration."""
    x1 = rng.randn(1, 8, 12, 16).astype(np.float32)
    x2 = rng.randn(1, 8, 12, 16).astype(np.float32)
    kw = dict(pad_size=20, max_displacement=20, stride2=2)
    a = np.asarray(correlation(jnp.asarray(x1), jnp.asarray(x2),
                               implementation="jnp", **kw))
    b = np.asarray(correlation(jnp.asarray(x1), jnp.asarray(x2),
                               implementation="mxu", **kw))
    assert a.shape == b.shape == (1, 8, 12, 441)
    np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)


def test_correlation_auto_guard_indivisible_displacement(rng):
    """auto must NOT pick mxu when max_displacement % stride2 != 0 (the
    band grid would drop the +md displacement); explicit mxu refuses."""
    x1 = rng.randn(1, 5, 5, 3).astype(np.float32)
    x2 = rng.randn(1, 5, 5, 3).astype(np.float32)
    got = np.asarray(correlation(jnp.asarray(x1), jnp.asarray(x2),
                                 pad_size=5, max_displacement=5, stride2=2,
                                 implementation="auto"))
    want = np_correlation(x1, x2, pad=5, md=5, s2=2)
    assert got.shape == want.shape  # scan-grid channel count (6x6)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    with pytest.raises(NotImplementedError, match="divisible"):
        correlation(jnp.asarray(x1), jnp.asarray(x2), pad_size=5,
                    max_displacement=5, stride2=2, implementation="mxu")
