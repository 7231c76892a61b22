"""Inference entry point (ref: inference.py:37-94).

Load a config + checkpoint, run the trainer's test loop over the test
set, and write images to --output_dir.
"""

from __future__ import annotations

import argparse

import jax

from imaginaire_tpu import telemetry
from imaginaire_tpu.config import Config, cfg_get
from imaginaire_tpu.data import get_test_dataloader
from imaginaire_tpu.parallel.mesh import (
    master_only_print as print,  # noqa: A001
    maybe_init_distributed_from_env,
    mesh_from_config,
    set_mesh,
)
from imaginaire_tpu.registry import resolve
from imaginaire_tpu.utils import compile_cache
from imaginaire_tpu.utils.logging_utils import init_logging, make_logging_dir


def parse_args():
    parser = argparse.ArgumentParser(description="imaginaire-tpu inference")
    parser.add_argument("--config", required=True)
    parser.add_argument("--checkpoint", default="",
                        help="Checkpoint path; defaults to the logdir's "
                             "latest_checkpoint pointer.")
    parser.add_argument("--output_dir", required=True)
    parser.add_argument("--logdir", default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--no-serving-engine", action="store_true",
                        help="Run the legacy eager test loop instead of "
                             "routing through the serving engine's "
                             "ledgered executables.")
    return parser.parse_args()


def main():
    compile_cache.configure()
    maybe_init_distributed_from_env()
    args = parse_args()
    cfg = Config(args.config)
    # cfg.parallel.mesh_shape wins over the legacy runtime.mesh block
    # (checkpoints restore shard-aware either way — trainers reshard on
    # load via the partition sidecar)
    set_mesh(mesh_from_config(cfg))
    date_uid, logdir = init_logging(args.config, args.logdir)
    make_logging_dir(logdir)
    cfg.logdir = logdir
    # inference runs produce the same telemetry jsonl as training:
    # data_wait/eval spans from the test loop, ckpt_load spans, and the
    # xla_obs compile ledger / memory counters (ISSUE 5 satellite)
    telemetry.configure(cfg, logdir=logdir)

    test_loader = get_test_dataloader(cfg)
    trainer_cls = resolve(cfg.trainer.type, "Trainer")
    trainer = trainer_cls(cfg, val_data_loader=test_loader)

    sample = next(iter(test_loader))
    sample = trainer.start_of_iteration(sample, 0)
    trainer.init_state(jax.random.PRNGKey(args.seed), sample)
    # serving restore rides the verified path end to end (ISSUE 8
    # satellite): discovery already quarantines + falls back to the
    # last-good checkpoint; an explicit --checkpoint that fails
    # integrity is quarantined and the newest verifiable sibling
    # restores instead — a server must never deserialize bytes the
    # training integrity layer refuses (corrupt compressed chunks fed
    # to the native decoder are a heap hazard, not a wrong pixel).
    loaded = trainer.load_checkpoint(args.checkpoint or None,
                                     fallback=bool(args.checkpoint))
    if not loaded:
        print("WARNING: no checkpoint found; running with fresh weights.")

    trainer.current_epoch = -1
    trainer.current_iteration = -1
    if not args.no_serving_engine:
        # route the test loop through the serving engine (ISSUE 19):
        # the forward compiles once into the ledgered executable pool
        # (recompile tripwire armed) and every batch lands serve/*
        # SLO counters in the same telemetry jsonl. Outputs are the
        # jitted legacy computation — same weights, same noise keys.
        from imaginaire_tpu.serving import ServingEngine

        engine = ServingEngine(cfg, trainer=trainer, logdir=logdir)
        engine.register_example(sample)
        engine.refresh_weights()
        engine.attach()
    inference_args = cfg_get(cfg, "inference_args", None)
    trainer.test(test_loader, args.output_dir,
                 dict(inference_args) if inference_args else None)
    telemetry.get().shutdown()
    print(f"Done with inference. Outputs in {args.output_dir}")


if __name__ == "__main__":
    main()
