"""AOT memory-budget planner for the 2-D partition plan (ISSUE 6).

Lowers + compiles a family's step programs through the compile ledger
WITHOUT executing them — state and batch enter as ``ShapeDtypeStruct``
trees carrying the plan's ``NamedSharding``s, so shapes that do NOT fit
a real chip (spade-512 zoo, 512x1024 vid2vid) still compile on the
virtual CPU mesh and report ``memory_analysis``. Emits before/after
rows: per-executable temp/argument bytes plus the per-chip
state-tree residency under the requested mesh.

Usage (virtual mesh; run in a fresh process):
  python scripts/partition_budget.py --family spade --hw 512 512 \
      --mesh 2,2 --bs 2
  python scripts/partition_budget.py --family spade --hw 512 512 \
      --mesh 1,1 --bs 1            # replicated baseline
  python scripts/partition_budget.py --family vid2vid --hw 512 1024 \
      --mesh 2,2 --bs 2 --frames 3
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _force_virtual_mesh(n):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _spade_cfg(hw, bs):
    from imaginaire_tpu.config import Config

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = Config(os.path.join(here, "configs", "projects", "spade",
                              "cocostuff", "base128_bs4.yaml"))
    cfg.trainer.perceptual_loss.allow_random_init = True
    cfg.trainer.perceptual_loss.pop("weights_path", None)
    cfg.data.train.batch_size = bs
    return cfg


def _vid2vid_cfg(hw, bs):
    from imaginaire_tpu.config import Config

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = Config(os.path.join(here, "configs", "projects", "vid2vid",
                              "cityscapes", "bf16.yaml"))
    if "flow_network" in cfg:
        # frozen teacher weights don't resolve here; the warp-consistency
        # fallback keeps the G/D step structure identical
        cfg.pop("flow_network")
    cfg.trainer.perceptual_loss.allow_random_init = True
    cfg.trainer.perceptual_loss.pop("weights_path", None)
    return cfg


def _sds_with_shardings(shapes, shardings):
    import jax

    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def _per_chip_bytes(shapes, shardings):
    import jax

    total = 0
    for leaf, sh in zip(jax.tree_util.tree_leaves(shapes),
                        jax.tree_util.tree_leaves(
                            shardings,
                            is_leaf=lambda x: hasattr(x, "shard_shape"))):
        shard = sh.shard_shape(tuple(leaf.shape))
        total += int(math.prod(shard)) * int(leaf.dtype.itemsize)
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=("spade", "vid2vid"),
                    default="spade")
    ap.add_argument("--hw", type=int, nargs=2, default=(512, 512))
    ap.add_argument("--bs", type=int, default=2)
    ap.add_argument("--mesh", default="2,2",
                    help="data,model sizes; 1,1 = replicated baseline")
    ap.add_argument("--frames", type=int, default=3)
    ap.add_argument("--min-shard-size", type=int, default=64)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    d_size, m_size = (int(x) for x in args.mesh.split(","))
    n_dev = max(d_size * m_size, 1)
    _force_virtual_mesh(n_dev)

    import jax

    jax.config.update("jax_platforms", "cpu")
    from imaginaire_tpu.utils import compile_cache

    compile_cache.configure()
    import numpy as np

    from imaginaire_tpu.parallel.mesh import create_mesh, set_mesh
    from imaginaire_tpu.parallel.sharding import batch_pytree_shardings
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.utils.data import (
        get_paired_input_label_channel_number,
    )

    mesh = create_mesh(("data", "model"), (d_size, m_size),
                       devices=np.array(jax.devices()[:n_dev]))
    set_mesh(mesh)

    h, w = args.hw
    if args.family == "spade":
        cfg = _spade_cfg((h, w), args.bs)
    else:
        cfg = _vid2vid_cfg((h, w), args.bs)
    two_d = d_size > 1 or m_size > 1
    if two_d:
        cfg.parallel.mesh_shape = {"data": d_size, "model": m_size}
        cfg.parallel.min_shard_size = args.min_shard_size
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    n_lab = get_paired_input_label_channel_number(cfg.data)

    if args.family == "spade":
        batch = {
            "images": jax.ShapeDtypeStruct((args.bs, h, w, 3),
                                           np.float32),
            "label": jax.ShapeDtypeStruct((args.bs, h, w, n_lab),
                                          np.float32),
        }
        programs = {"dis_step": trainer._jit_dis_step,
                    "gen_step": trainer._jit_gen_step}
    else:
        batch = {
            "images": jax.ShapeDtypeStruct(
                (args.bs, args.frames, h, w, 3), np.float32),
            "label": jax.ShapeDtypeStruct(
                (args.bs, args.frames, h, w, n_lab), np.float32),
        }
        programs = {"vid_dis_step": trainer._jit_vid_dis,
                    "vid_gen_step": trainer._jit_vid_gen}

    # state SHAPES via eval_shape — the full spade-512/vid2vid-1024 state
    # never materializes; only its sharded avals reach the compiler
    print(f"# tracing {args.family} init_state at {h}x{w} bs{args.bs} "
          f"on mesh (data={d_size}, model={m_size}) ...", flush=True)
    zeros = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), batch)
    state_shapes = jax.eval_shape(
        lambda key, b: trainer.init_state(key, b),
        jax.ShapeDtypeStruct((2,), np.uint32), zeros)
    trainer.state = None  # eval_shape left SDS in self.state

    from jax.sharding import NamedSharding, PartitionSpec as P

    if two_d and trainer.partition.enabled:
        state_shardings = trainer.partition.state_shardings(state_shapes)
    else:
        state_shardings = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), state_shapes)
    state_sds = _sds_with_shardings(state_shapes, state_shardings)
    if args.family == "vid2vid":
        # the per-frame programs consume data_t (the t=0 frame here:
        # the full G fwd+bwd+opt without prev-frame inputs)
        batch = {
            "label": jax.ShapeDtypeStruct(
                batch["label"].shape[:1] + batch["label"].shape[2:],
                np.float32),
            "image": jax.ShapeDtypeStruct(
                batch["images"].shape[:1] + batch["images"].shape[2:],
                np.float32),
        }
    batch_sds = _sds_with_shardings(
        batch, batch_pytree_shardings(batch, mesh))

    rows = {}
    for label, prog in programs.items():
        print(f"# AOT compiling {label} ...", flush=True)
        mem = prog.aot_compile(state_sds, batch_sds)
        rows[label] = mem
        print(f"{label}: " + json.dumps(mem), flush=True)

    state_report = {}
    for key in ("vars_G", "vars_D", "opt_G", "opt_D", "ema_G",
                "loss_params"):
        if key in state_shapes:
            glob = sum(
                int(math.prod(s.shape)) * int(s.dtype.itemsize)
                for s in jax.tree_util.tree_leaves(state_shapes[key]))
            per = _per_chip_bytes(state_shapes[key], state_shardings[key])
            state_report[key] = {"global_bytes": glob,
                                 "per_chip_bytes": per}
    out = {
        "family": args.family, "hw": [h, w], "bs": args.bs,
        "mesh": {"data": d_size, "model": m_size},
        "executables": rows, "state": state_report,
        "state_per_chip_total": sum(r["per_chip_bytes"]
                                    for r in state_report.values()),
        "state_global_total": sum(r["global_bytes"]
                                  for r in state_report.values()),
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
