"""Plain reference of the SPADE / GauGAN generator's inference pass.

Written from the published description (Park et al., "Semantic Image
Synthesis with Spatially-Adaptive Normalization", CVPR 2019, and NVlabs
imaginaire `generators/spade.py`), in straightforward `jax.numpy`, with no
kernels, no batching tricks and nothing imported from the program. It owns
the list of its parameters (`spec`), so the benchmark makes the weights
from the seed and hands the same arrays to the program and to this file.

`precision` is the one knob: "float32" computes every product at
`lax.Precision.HIGHEST` in float32 (the reference proper); "bfloat16" casts
parameters and inputs to bfloat16 and computes everything there (the
control of `correct`: the nearest precision below what serving states).

Departures from the paper, all the program's documented ones: the
256-pixel ladder of imaginaire (16x16 start, seven SPADE residual blocks,
four style-conditioned convolutions), spectral norm applied at inference
from a stored power-iteration vector (one step, as torch does in train
mode), batch norm from running statistics.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
from jax import lax

EPS_BN = 1e-5
EPS_SN = 1e-12
DEVICE_DEFAULT_PRODUCTS = [False]  # set only while such a trace is made


class Params:
    """Parameter access by name. Without values it records each name's
    shape and kind and hands back zeros, which is how `spec` lists them.

    `train` makes batch norm use the batch's own statistics. `advance_u`
    keeps each spectral norm's power-iteration vector after its step in
    `new_u`, and a second pass through the same layer starts from there
    (as torch's hook does in train mode). `quantize` rounds what enters
    each convolution and matrix product to float8 (e4m3): the control of
    a bfloat16 configuration."""

    def __init__(self, values=None, dtype=jnp.float32, train=False,
                 advance_u=False, quantize=False):
        self.values = values
        self.dtype = dtype
        self.train = train
        self.advance_u = advance_u
        self.quantize = quantize
        self.new_u = {}
        self.spec = {}
        self.flops = 0  # multiply-adds x 2 of convolutions and products

    def __call__(self, name, shape, kind):
        if self.values is None:
            self.spec[name] = (tuple(int(s) for s in shape), kind)
            return jnp.zeros(shape, self.dtype)
        value = self.values[name]
        if tuple(value.shape) != tuple(shape):
            raise ValueError(f"{name}: {value.shape} given, {shape} needed")
        return value.astype(self.dtype)


def _precision(dtype):
    """Products of float32 at HIGHEST (a TPU otherwise rounds their
    operands to bfloat16); `DEVICE_DEFAULT_PRODUCTS` lifts that, for the
    float32 reference at the device's own default."""
    if dtype == jnp.float32 and not DEVICE_DEFAULT_PRODUCTS[0]:
        return lax.Precision.HIGHEST
    return None


def _normalize(v):
    return v / (jnp.linalg.norm(v) + EPS_SN)


def _fp8(x):
    """x rounded to float8 e4m3, gradient passed straight through."""
    q = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x + lax.stop_gradient(q - x)


def _spectral(p, name, kernel):
    """kernel / sigma, sigma = u' W v after one power iteration off the
    stored u; no gradient flows through u and v (Miyato et al., 2018)."""
    out = kernel.shape[-1]
    w = kernel.reshape(-1, out).T
    u = p.new_u.get(name + "/u")
    if u is None:
        u = p(name + "/u", (out,), "u")
    prec = _precision(kernel.dtype)
    w_const = lax.stop_gradient(w)
    v = _normalize(jnp.matmul(w_const.T, u, precision=prec))
    u = _normalize(jnp.matmul(w_const, v, precision=prec))
    if p.advance_u:
        p.new_u[name + "/u"] = u
    sigma = jnp.dot(u, jnp.matmul(w, v, precision=prec), precision=prec)
    return kernel / sigma


def _conv(p, name, x, out, ksize, pad, stride=1, bias=True, sn=True):
    kernel = p(name + "/kernel", (ksize, ksize, x.shape[-1], out), "kernel")
    if sn:
        kernel = _spectral(p, name, kernel)
    if p.quantize:
        x, kernel = _fp8(x), _fp8(kernel)
    x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    y = lax.conv_general_dilated(
        x, kernel, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(x.dtype))
    p.flops += 2 * y.size * ksize * ksize * kernel.shape[2]
    if bias:
        y = y + p(name + "/bias", (out,), "bias")
    return y


def _linear(p, name, x, out, sn=True):
    kernel = p(name + "/kernel", (x.shape[-1], out), "kernel")
    if sn:
        kernel = _spectral(p, name, kernel)
    if p.quantize:
        x, kernel = _fp8(x), _fp8(kernel)
    y = jnp.matmul(x, kernel, precision=_precision(x.dtype))
    p.flops += 2 * y.size * kernel.shape[0]
    return y + p(name + "/bias", (out,), "bias")


def _leaky(x):
    return jnp.where(x >= 0, x, 0.2 * x)


def _batch_norm(p, name, x):
    """Batch norm without affine: over the whole batch in training, from
    the running statistics otherwise."""
    c = x.shape[-1]
    if p.train:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
    else:
        mean = p(name + "/mean", (c,), "bn_mean")
        var = p(name + "/var", (c,), "bn_var")
    return (x - mean) / jnp.sqrt(var + EPS_BN)


def _nearest_down(seg, size):
    """Nearest-neighbour resize of a square map to `size`: the sample at
    the centre of each output cell, floor((i + 0.5) * step)."""
    step = seg.shape[1] // size
    if step == 1:
        return seg
    return seg[:, step // 2::step, step // 2::step]


def _up2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def _spade(p, name, x, seg, sizes):
    """SPADE: batch norm without affine, then per-pixel scale and shift
    from the label map through a two-layer convolutional projection."""
    c = x.shape[-1]
    k = sizes["spade_kernel_size"]
    cond = _nearest_down(seg, x.shape[1])
    hidden = jax.nn.relu(_conv(p, name + "/mlp_0", cond,
                               sizes["spade_num_filters"], k, k // 2))
    gamma = _conv(p, name + "/gamma_0", hidden, c, k, k // 2)
    beta = _conv(p, name + "/beta_0", hidden, c, k, k // 2)
    return _batch_norm(p, name + "/bn", x) * (1.0 + gamma) + beta


def _res_block(p, name, x, seg, out, sizes):
    cin = x.shape[-1]
    hidden = min(cin, out)
    dx = _spade(p, name + "/conv_0/norm", x, seg, sizes)
    dx = _conv(p, name + "/conv_0", _leaky(dx), hidden, 3, 1)
    dx = _spade(p, name + "/conv_1/norm", dx, seg, sizes)
    dx = _conv(p, name + "/conv_1", _leaky(dx), out, 3, 1)
    if cin != out:
        xs = _spade(p, name + "/conv_s/norm", x, seg, sizes)
        x = _conv(p, name + "/conv_s", xs, out, 1, 0, bias=False)
    return x + dx


def _style_conv(p, name, x, z, out):
    """Convolution under batch norm modulated by the style code."""
    c = x.shape[-1]
    gamma = _linear(p, name + "/norm/fc_gamma", z, c)[:, None, None, :]
    beta = _linear(p, name + "/norm/fc_beta", z, c)[:, None, None, :]
    y = _batch_norm(p, name + "/norm/bn", x) * (1.0 + gamma) + beta
    return _conv(p, name, _leaky(y), out, 3, 1)


def generate(p, sizes, label, z):
    """label (N, 256, 256, labels) and style z (N, style_dims) to images
    (N, 256, 256, 3) in [-1, 1]."""
    nf = sizes["num_filters"]
    g = "spade_generator"
    label = label.astype(p.dtype)
    z = z.astype(p.dtype)
    z = jax.nn.relu(_linear(p, g + "/fc_0", z, 2 * sizes["style_dims"]))
    z = jax.nn.relu(_linear(p, g + "/fc_1", z, 2 * sizes["style_dims"]))
    x = _leaky(_conv(p, g + "/head_0", _nearest_down(label, 16), 8 * nf, 3, 1))
    x = _style_conv(p, g + "/cbn_head_0", x, z, 16 * nf)
    x = _res_block(p, g + "/head_1", x, label, 16 * nf, sizes)
    x = _res_block(p, g + "/head_2", x, label, 16 * nf, sizes)
    x = _up2(x)
    x = _res_block(p, g + "/up_0a", x, label, 8 * nf, sizes)
    x = _style_conv(p, g + "/cbn_up_0a", x, z, 8 * nf)
    x = _res_block(p, g + "/up_0b", x, label, 8 * nf, sizes)
    x = _up2(x)
    x = _res_block(p, g + "/up_1a", x, label, 4 * nf, sizes)
    x = _style_conv(p, g + "/cbn_up_1a", x, z, 4 * nf)
    x = _res_block(p, g + "/up_1b", x, label, 4 * nf, sizes)
    x = _up2(x)
    x = _res_block(p, g + "/up_2a", x, label, 4 * nf, sizes)
    x = _style_conv(p, g + "/cbn_up_2a", x, z, 4 * nf)
    x = _res_block(p, g + "/up_2b", x, label, 2 * nf, sizes)
    x = _up2(x)
    x = _conv(p, g + "/conv_img256", _leaky(x), 3, 5, 2)
    return jnp.tanh(x).astype(jnp.float32)


def spec(sizes):
    """{name: (shape, kind)} of every parameter `generate` reads."""
    p = Params()
    side = sizes["image_size"]
    jax.eval_shape(
        lambda: generate(p, sizes,
                         jnp.zeros((1, side, side, sizes["num_labels"])),
                         jnp.zeros((1, sizes["style_dims"]))))
    return p.spec


def style_noise(seed, style_dims):
    """The style code of a request with noise seed `seed`: a standard
    normal draw from the first key of the request's noise stream (flax's
    rule for a module's named stream: the request key with the SHA-1 of
    the draw's ordinal, 1, folded in)."""
    ordinal = int.from_bytes(hashlib.sha1(b"\x01").digest()[:4], "big")
    key = jax.random.fold_in(jax.random.PRNGKey(seed), jnp.uint32(ordinal))
    return jax.random.normal(key, (1, style_dims), jnp.float32)


PRECISIONS = ("float32", "float32_default", "bfloat16", "float8")


def params_for(values, precision, **kwargs):
    """`Params` over `values` computing in `precision`: "float32" (the
    reference: every product at HIGHEST), "float32_default" (float32 with
    products at the device's default, which on a TPU rounds their operands
    to bfloat16), "bfloat16", or "float8" (bfloat16 with what enters each
    product rounded to e4m3)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    dtype = jnp.float32 if precision.startswith("float32") else jnp.bfloat16
    return Params(values, dtype, quantize=precision == "float8", **kwargs)


class device_default_products:
    """Context: trace float32 products at the device's default precision."""

    def __init__(self, on):
        self.on = on

    def __enter__(self):
        self.before = DEVICE_DEFAULT_PRODUCTS[0]
        DEVICE_DEFAULT_PRODUCTS[0] = self.on

    def __exit__(self, *exc):
        DEVICE_DEFAULT_PRODUCTS[0] = self.before


def forward(values, sizes, label, z, precision="float32"):
    with device_default_products(precision == "float32_default"):
        return generate(params_for(values, precision), sizes, label, z)
