"""Shared by the hybrid token model's tests: the tiny preset's sizes, the
seed's weights as the reference names them, and the program's tree."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_YAML = os.path.join(ROOT, "configs", "unit_test", "hybrid_lm.yaml")
LATENT_YAML = os.path.join(ROOT, "configs", "unit_test",
                           "glm4_moe_lite.yaml")
DELTA_YAML = os.path.join(ROOT, "configs", "unit_test", "solar_open2.yaml")
SCONV_YAML = os.path.join(ROOT, "configs", "unit_test", "lfm2_moe.yaml")
WINDOW_YAML = os.path.join(ROOT, "configs", "unit_test", "afmoe.yaml")
EARLY_YAML = os.path.join(ROOT, "configs", "unit_test", "smallthinker.yaml")
# tiny preset -> (its YAML, its plain reference under benchmark/reference,
# the index of its first expert layer)
PRESETS = {"nemotron_h": (TINY_YAML, "nemotron_h_train", 1),
           "glm4_moe_lite": (LATENT_YAML, "glm4_moe_lite_train", 3),
           "solar_open2": (DELTA_YAML, "solar_open2_train", 1),
           "lfm2_moe": (SCONV_YAML, "lfm2_moe_train", 3),
           "afmoe": (WINDOW_YAML, "afmoe_train", 3),
           "smallthinker": (EARLY_YAML, "smallthinker_train", 1)}


def tiny_cfg(preset="nemotron_h", **gen):
    """A tiny preset's config in float32 compute (the tests compare
    with the float32 reference), with `gen` written over its gen section."""
    from imaginaire_tpu.config import Config

    cfg = Config(PRESETS[preset][0])
    cfg.gen.update(gen)
    return cfg


def sizes_of(cfg):
    """The reference's `sizes` of a program config."""
    sizes = dict(cfg.gen)
    for group in ("experts_held", "linear_attn_config"):
        if group in sizes:
            sizes[group] = dict(sizes[group])
    return sizes


def unflatten(flat):
    """{"a/b": x} -> {"a": {"b": x}}: the program's tree of the
    reference's names."""
    tree = {}
    for name, value in flat.items():
        node = tree
        *parents, leaf = name.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = value
    return tree


def seeded(cfg, seed, preset="nemotron_h"):
    """(reference module, sizes, trainable, buffers) for `cfg`."""
    import importlib

    from benchmark.lib import lm_weights

    reference = importlib.import_module(
        "benchmark.reference." + PRESETS[preset][1])
    sizes = sizes_of(cfg)
    train, buffers = reference.split(
        lm_weights.make(reference.spec(sizes), seed))
    return reference, sizes, train, buffers


def layer_params(flat, index):
    """The program's parameters of layer `index`'s mixer, from the
    reference's flat names."""
    prefix = f"layer_{index}/mixer/"
    return {k[len(prefix):]: v for k, v in flat.items()
            if k.startswith(prefix)}


def pallas_bodies(jaxpr):
    """(name, the kernel body's jaxpr) of every ``pallas_call`` in a
    jaxpr, inner jaxprs included."""
    import jax

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], eqn.params["jaxpr"]
            continue
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from pallas_bodies(inner)


def pallas_calls(jaxpr):
    """The names of every ``pallas_call`` in a jaxpr, inner jaxprs
    included."""
    return [name for name, _ in pallas_bodies(jaxpr)]
