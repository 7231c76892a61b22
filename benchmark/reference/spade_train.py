"""Plain reference of SPADE's training steps: one discriminator update,
then one generator update, as imaginaire's `trainers/spade.py` runs them.

Straightforward `jax.numpy` in float32 at `Precision.HIGHEST`; the
gradients are `jax.grad` of the losses below. Nothing of the program is
imported, and the weights are the seed's (`benchmark/lib/weights.py`).

  discriminator  FPSE feature-pyramid discriminator (Liu et al., NeurIPS
                 2019) + two multi-resolution patch discriminators over
                 concat(label, image) (Wang et al., pix2pixHD), spectral
                 norm everywhere but the FPSE heads
  losses         hinge GAN, averaged per output then over outputs; L1
                 feature matching over the patch discriminators' layers;
                 VGG19 perceptual L1 over relu_{1..5}_1; Gaussian KL of
                 the style encoder (sum)
  optimizer      Adam (Kingma & Ba) with bias correction, no weight decay

Departures, all the program's documented ones: spectral norm's vector is
advanced only in the network being updated (the generator's in the
generator step, the discriminator's in its own step, twice there: once on
the real and once on the fake pass); batch norm uses the batch's
statistics; the style noise is an input (the program draws it in
bfloat16, so the reference is handed the same draw).
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import spade_generator as gen
from benchmark.reference.spade_generator import (_conv, _leaky, _linear,
                                                 _precision)

VGG19 = (64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
         512, 512, 512, 512, "M", 512)
PERCEPTUAL_TAPS = (0, 2, 4, 8, 12)  # relu_1_1, 2_1, 3_1, 4_1, 5_1
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


# ------------------------------------------------------------ generator


def encode_style(p, sizes, images, eps):
    """Images to (mu, logvar, z): six stride-2 convolutions, two linear
    heads, the reparameterised draw z = eps * exp(logvar / 2) + mu."""
    nf = sizes["style_enc_num_filters"]
    x = images.astype(p.dtype)
    for i, ch in enumerate([nf, 2 * nf, 4 * nf, 8 * nf, 8 * nf, 8 * nf]):
        x = _leaky(_conv(p, f"style_encoder/layer{i + 1}", x, ch, 3, 1,
                         stride=2))
    x = x.reshape(x.shape[0], -1)
    mu = _linear(p, "style_encoder/fc_mu", x, sizes["style_dims"], sn=False)
    logvar = _linear(p, "style_encoder/fc_var", x, sizes["style_dims"],
                     sn=False)
    z = eps.astype(p.dtype) * jnp.exp(0.5 * logvar) + mu
    return mu, logvar, z


def generate_training(p, sizes, batch, eps):
    mu, logvar, z = encode_style(p, sizes, batch["images"], eps)
    fake = gen.generate(p, sizes, batch["label"], z)
    return fake, mu, logvar


# -------------------------------------------------------- discriminator


def _avg_pool2(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def _up2_bilinear(x):
    n, h, w, c = x.shape
    return jax.image.resize(x, (n, 2 * h, 2 * w, c), "bilinear",
                            precision=_precision(x.dtype))


def _half_align_corners(x):
    """Half-size bilinear resize with corners aligned: output pixel i
    samples input position i * (n_in - 1) / (n_out - 1)."""

    def along(x, axis):
        n_in = x.shape[axis]
        n_out = n_in // 2
        pos = jnp.arange(n_out) * ((n_in - 1) / (n_out - 1))
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, n_in - 1)
        frac = (pos - lo).astype(x.dtype)
        shape = [1] * x.ndim
        shape[axis] = n_out
        frac = frac.reshape(shape)
        return (jnp.take(x, lo, axis=axis) * (1 - frac)
                + jnp.take(x, hi, axis=axis) * frac)

    return along(along(x, 1), 2)


def fpse(p, sizes, image, label):
    nf = sizes["dis_num_filters"]

    def down(name, x, ch):
        return _leaky(_conv(p, "fpse/" + name, x, ch, 3, 1, stride=2))

    def lateral(name, x, ch):
        return _leaky(_conv(p, "fpse/" + name, x, ch, 1, 0))

    def final(name, x, ch):
        return _leaky(_conv(p, "fpse/" + name, x, ch, 3, 1))

    f1 = down("enc1", image, nf)
    f2 = down("enc2", f1, 2 * nf)
    f3 = down("enc3", f2, 4 * nf)
    f4 = down("enc4", f3, 8 * nf)
    f5 = down("enc5", f4, 8 * nf)
    t5 = lateral("lat5", f5, 4 * nf)
    t4 = _up2_bilinear(t5) + lateral("lat4", f4, 4 * nf)
    t3 = _up2_bilinear(t4) + lateral("lat3", f3, 4 * nf)
    t2 = _up2_bilinear(t3) + lateral("lat2", f2, 4 * nf)
    feats = [final("final2", t2, 2 * nf), final("final3", t3, 2 * nf),
             final("final4", t4, 2 * nf)]
    emb = _conv(p, "fpse/embedding", label, 2 * nf, 1, 0, sn=False)
    emb = _avg_pool2(emb)
    preds = []
    for f in feats:
        emb = _avg_pool2(emb)
        pred = _conv(p, "fpse/output", f, 1, 1, 0, sn=False)
        seg = _conv(p, "fpse/seg", f, 2 * nf, 1, 0, sn=False)
        preds.append(pred + jnp.sum(emb * seg, axis=-1, keepdims=True))
    return preds


def patch_discriminator(p, name, sizes, x):
    nf = sizes["dis_num_filters"]
    layers = sizes["dis_num_layers"]
    feats = []
    x = _leaky(_conv(p, f"{name}/layer0", x, nf, 4, 1, stride=2))
    feats.append(x)
    for n in range(layers):
        nf = min(nf * 2, sizes["dis_max_num_filters"])
        stride = 2 if n < layers - 1 else 1
        x = _leaky(_conv(p, f"{name}/layer{n + 1}", x, nf, 4, 1,
                         stride=stride))
        feats.append(x)
    return _conv(p, f"{name}/layer{layers + 1}", x, 1, 3, 1), feats


def discriminate(p, sizes, label, image):
    """(outputs, features): three FPSE maps and one map per patch
    discriminator; features of the patch discriminators only."""
    label = label.astype(p.dtype)
    image = image.astype(p.dtype)
    outputs = fpse(p, sizes, image, label)
    features = []
    x = jnp.concatenate([label, image], axis=-1)
    count = sizes["dis_num_discriminators"]
    for i in range(count):
        logits, feats = patch_discriminator(p, f"patch_d_{i}", sizes, x)
        outputs.append(logits)
        features.append(feats)
        if i != count - 1:
            x = _half_align_corners(x)
    return outputs, features


# --------------------------------------------------------------- losses


def _mean_over_outputs(fn, outputs):
    return sum(fn(o.astype(jnp.float32)) for o in outputs) / len(outputs)


def hinge_d(outputs, real):
    sign = 1.0 if real else -1.0
    return _mean_over_outputs(
        lambda o: -jnp.mean(jnp.minimum(sign * o - 1.0, 0.0)), outputs)


def hinge_g(outputs):
    return _mean_over_outputs(lambda o: -jnp.mean(o), outputs)


def feature_matching(fake_features, real_features):
    total = 0.0
    for fake_d, real_d in zip(fake_features, real_features):
        for f, r in zip(fake_d, real_d):
            total = total + jnp.mean(jnp.abs(
                f.astype(jnp.float32)
                - lax.stop_gradient(r).astype(jnp.float32)))
    return total / len(fake_features)


def gaussian_kl(mu, logvar):
    mu, logvar = mu.astype(jnp.float32), logvar.astype(jnp.float32)
    return -0.5 * jnp.sum(1.0 + logvar - mu ** 2 - jnp.exp(logvar))


def vgg_features(p, x):
    """The five tapped relu activations of VGG19's feature stack."""
    taps = []
    conv_i = 0
    for v in VGG19:
        if v == "M":
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
            continue
        x = jax.nn.relu(_conv(p, f"perceptual/conv_{conv_i}", x, v, 3, 1,
                              sn=False))
        if conv_i in PERCEPTUAL_TAPS:
            taps.append(x)
        conv_i += 1
    return taps


def perceptual(p, sizes, fake, real):
    def normalise(x):
        x = (x.astype(jnp.float32) + 1.0) / 2.0
        return ((x - jnp.asarray(IMAGENET_MEAN))
                / jnp.asarray(IMAGENET_STD)).astype(p.dtype)

    f_fake = vgg_features(p, normalise(fake))
    f_real = vgg_features(p, lax.stop_gradient(normalise(real)))
    total = 0.0
    for w, a, b in zip(sizes["perceptual_weights"], f_fake, f_real):
        total = total + w * jnp.mean(jnp.abs(
            a.astype(jnp.float32)
            - lax.stop_gradient(b).astype(jnp.float32)))
    return total


# ---------------------------------------------------------------- steps


def split(values):
    """(generator, discriminator, loss-network) parameters by name."""
    g, d, v = {}, {}, {}
    for name, value in values.items():
        if name.startswith(("spade_generator/", "style_encoder/")):
            g[name] = value
        elif name.startswith("perceptual/"):
            v[name] = value
        else:
            d[name] = value
    return g, d, v


def _trainable(values):
    return {k: v for k, v in values.items() if not k.endswith("/u")}


def _vectors(values):
    return {k: v for k, v in values.items() if k.endswith("/u")}


def d_loss(d_train, d_u, g_values, sizes, batch, eps, precision):
    """Discriminator's loss and its advanced vectors."""
    pg = gen.params_for(g_values, precision, train=True)
    fake, _, _ = generate_training(pg, sizes, batch, eps)
    fake = lax.stop_gradient(fake)
    pd = gen.params_for({**d_train, **d_u}, precision, train=True,
                        advance_u=True)
    real_out, _ = discriminate(pd, sizes, batch["label"], batch["images"])
    fake_out, _ = discriminate(pd, sizes, batch["label"], fake)
    loss = hinge_d(fake_out, real=False) + hinge_d(real_out, real=True)
    return loss, pd.new_u


def g_loss(g_train, g_u, d_values, v_values, sizes, batch, eps, precision):
    """Generator's weighted loss, its terms and its advanced vectors."""
    pg = gen.params_for({**g_train, **g_u}, precision, train=True,
                        advance_u=True)
    fake, mu, logvar = generate_training(pg, sizes, batch, eps)
    pd = gen.params_for(d_values, precision, train=True)
    real_out, real_feat = discriminate(pd, sizes, batch["label"],
                                       batch["images"])
    fake_out, fake_feat = discriminate(pd, sizes, batch["label"], fake)
    pv = gen.params_for(v_values, precision)
    w = sizes["loss_weights"]
    terms = {"GAN": hinge_g(fake_out),
             "FeatureMatching": feature_matching(fake_feat, real_feat),
             "GaussianKL": gaussian_kl(mu, logvar),
             "Perceptual": perceptual(pv, sizes, fake, batch["images"])}
    total = (w["gan"] * terms["GAN"]
             + w["feature_matching"] * terms["FeatureMatching"]
             + w["kl"] * terms["GaussianKL"]
             + w["perceptual"] * terms["Perceptual"])
    return total, (terms, pg.new_u)


def adam(params, grads, nu, count, lr, b2=0.999, eps=1e-8):
    """Adam with beta1 = 0, as the recipe sets it: the first moment is
    the gradient itself. Returns (params, nu)."""
    count = count + 1
    new_nu = {k: b2 * nu[k] + (1.0 - b2) * grads[k] ** 2 for k in grads}
    correction = 1.0 - b2 ** count
    new_params = {
        k: params[k] - lr * grads[k] / (jnp.sqrt(new_nu[k] / correction)
                                        + eps)
        for k in grads}
    return new_params, new_nu


def noise_key(stream_key, step):
    """Key of a step's style noise: the network's stream key with the
    step folded in, then the draw's ordinal (1) by flax's rule for a
    module's named stream (see `spade_generator.style_noise`)."""
    ordinal = int.from_bytes(hashlib.sha1(b"\x01").digest()[:4], "big")
    return jax.random.fold_in(jax.random.fold_in(stream_key, step),
                              jnp.uint32(ordinal))


def spec(sizes):
    """{name: (shape, kind)} of every parameter the two steps read."""
    p = gen.Params(train=True)
    side, n = sizes["image_size"], 1
    batch = {"images": jnp.zeros((n, side, side, 3)),
             "label": jnp.zeros((n, side, side, sizes["num_labels"]))}

    def touch():
        fake, _, _ = generate_training(
            p, sizes, batch, jnp.zeros((n, sizes["style_dims"])))
        discriminate(p, sizes, batch["label"], fake)
        perceptual(p, sizes, fake, batch["images"])

    jax.eval_shape(touch)
    return p.spec


def step_flops(sizes, batch_size):
    """Floating-point operations one training iteration needs at these
    sizes, convolutions and matrix products only, recomputation not
    counted. A pass that is differentiated costs three forward passes
    (forward, gradient to the input, gradient to the weights), one that
    only carries a gradient through costs two, one without gradient one:

      discriminator step  G forward + D on real and on fake, trained
                          = F_G + 2 * 3 F_D
      generator step      G trained; D on fake carrying the gradient, D on
                          real for its features; VGG19 likewise
                          = 3 F_G + (2 + 1) F_D + (2 + 1) F_V
    """
    side = sizes["image_size"]
    batch = {"images": jnp.zeros((batch_size, side, side, 3)),
             "label": jnp.zeros((batch_size, side, side,
                                 sizes["num_labels"]))}
    eps = jnp.zeros((batch_size, sizes["style_dims"]))
    counts = {}

    def count(name, fn):
        p = gen.Params(train=True)
        jax.eval_shape(lambda: fn(p))
        counts[name] = p.flops

    count("G", lambda p: generate_training(p, sizes, batch, eps))
    count("D", lambda p: discriminate(p, sizes, batch["label"],
                                      batch["images"]))
    count("V", lambda p: vgg_features(p, batch["images"]))
    f_g, f_d, f_v = counts["G"], counts["D"], counts["V"]
    return {"forward": counts,
            "dis_step": f_g + 6 * f_d,
            "gen_step": 3 * f_g + 3 * f_d + 3 * f_v,
            "iteration": 4 * f_g + 9 * f_d + 3 * f_v}
