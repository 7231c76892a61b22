"""The seam between the benchmark and the program: the program's config
for a benchmark configuration, and the seed's weights put into the
program's state by name.

Reference names and program paths differ by two rules only: a program
convolution block keeps its parameters under a child called "conv", and
batch norm keeps its running statistics under two nested flax modules.
"""

from __future__ import annotations

import importlib
import os

from benchmark.lib import harness

_BN = ("BatchNorm_0", "BatchNorm_0")

# benchmark size -> where the program's YAML holds it
SIZE_KEYS = {
    "num_filters": "gen.num_filters",
    "kernel_size": "gen.kernel_size",
    "style_dims": "gen.style_dims",
    "spade_num_filters": "gen.activation_norm_params.num_filters",
    "spade_kernel_size": "gen.activation_norm_params.kernel_size",
    "style_enc_num_filters": "gen.style_enc.num_filters",
    "dis_num_filters": "dis.num_filters",
    "dis_max_num_filters": "dis.max_num_filters",
    "dis_num_layers": "dis.num_layers",
    "dis_num_discriminators": "dis.num_discriminators",
    "train_batch_size": "data.train.batch_size",
    "gen_lr": "gen_opt.lr",
    "dis_lr": "dis_opt.lr",
    "adam_beta1": "gen_opt.adam_beta1",
    "adam_beta2": "gen_opt.adam_beta2",
}


def set_dotted(cfg, dotted, value):
    node = cfg
    parts = dotted.split(".")
    for part in parts[:-1]:
        node = node[part]
    node[parts[-1]] = value


def get_dotted(cfg, dotted):
    node = cfg
    for part in dotted.split("."):
        node = node[part]
    return node


def load_config(config, shrunk=False):
    """The program's config for this benchmark configuration: its YAML,
    the benchmark's `overrides`, and every size checked against the
    benchmark's own `sizes` (a YAML whose widths moved is another
    configuration). `shrunk` is for the CPU rehearsals, which write their
    small sizes in."""
    from imaginaire_tpu.config import Config

    cfg = Config(os.path.join(harness.ROOT, config["program_yaml"]))
    for dotted, value in config["overrides"].items():
        set_dotted(cfg, dotted, value)
    for key, dotted in SIZE_KEYS.items():
        if key not in config["sizes"]:
            continue
        want = config["sizes"][key]
        if shrunk:
            set_dotted(cfg, dotted, want)
        elif get_dotted(cfg, dotted) != want:
            raise harness.BenchmarkError(
                f"{config['program_yaml']}: {dotted} is "
                f"{get_dotted(cfg, dotted)!r}, the benchmark's "
                f"configuration says {want!r}")
    return cfg


def load_reference(config, role):
    """The configuration's plain reference for `role` ("serve", "train")."""
    return importlib.import_module(
        "benchmark.reference." + config["reference"][role])


def reference_name(path):
    """The reference's name of a program variable, from its path below the
    collection (`params`, `spectral`, `batch_stats`)."""
    parts = [p for p in path if p != "conv"]
    if tuple(parts[-3:-1]) == _BN:
        parts = parts[:-3] + ["bn", parts[-1]]
    return "/".join(parts)


def flatten(tree, prefix=()):
    """{reference name: leaf} of a program variable tree."""
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(flatten(v, prefix + (k,)))
        return out
    return {reference_name(prefix): tree}


def graft(tree, values, used, prefix=()):
    """`tree` with every leaf the reference names replaced by the seed's
    array; leaves the reference does not read stay as they are."""
    if isinstance(tree, dict):
        return {k: graft(v, values, used, prefix + (k,))
                for k, v in tree.items()}
    name = reference_name(prefix)
    if name not in values:
        return tree
    if tuple(values[name].shape) != tuple(tree.shape):
        raise harness.BenchmarkError(
            f"{name}: program holds {tree.shape}, reference "
            f"{values[name].shape}")
    used.add(name)
    return values[name]


def require_all_used(values, used):
    missing = sorted(set(values) - used)
    if missing:
        raise harness.BenchmarkError(
            f"{len(missing)} reference parameters found no place in the "
            f"program's state, first: {missing[:3]}")
