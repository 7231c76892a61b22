"""Evaluation entry point (ref: evaluate.py:33-81).

Walk the checkpoints in --checkpoint_logdir (or the single --checkpoint),
restore each, and run the trainer's metric computation (FID et al.) over
the validation set.
"""

from __future__ import annotations

import argparse
import glob
import os

import jax

from imaginaire_tpu import telemetry
from imaginaire_tpu.config import Config
from imaginaire_tpu.data import get_train_and_val_dataloader
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
    parser = argparse.ArgumentParser(description="imaginaire-tpu evaluation")
    parser.add_argument("--config", required=True)
    parser.add_argument("--logdir", default=None,
                        help="Dir for saving evaluation results.")
    parser.add_argument("--checkpoint_logdir", default=None,
                        help="Dir whose checkpoints are each evaluated.")
    parser.add_argument("--checkpoint", default=None,
                        help="Evaluate one specific checkpoint.")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--metrics", default="fid",
                        help="Comma list of metrics: fid[,kid,prdc] "
                             "(the reference's sweep computes FID only; "
                             "kid/prdc are this framework's additions).")
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
    # eval sweeps emit ckpt_load / eval / data_wait spans into the same
    # telemetry.jsonl schema as training runs
    telemetry.configure(cfg, logdir=logdir)

    train_loader, val_loader = get_train_and_val_dataloader(cfg,
                                                            seed=args.seed)
    trainer_cls = resolve(cfg.trainer.type, "Trainer")
    trainer = trainer_cls(cfg, train_data_loader=train_loader,
                          val_data_loader=val_loader)
    sample = next(iter(val_loader))
    sample = trainer.start_of_iteration(sample, 0)
    trainer.init_state(jax.random.PRNGKey(args.seed), sample)

    # The metric sweeps below device-prefetch the val loader internally
    # (trainer.data_prefetcher honors data.device_prefetch): the next
    # batch's host load + H2D overlaps the extractor/generator on the
    # current one. Video-family sweeps stay frame-sequential by design
    # (per-sequence pinned datasets mutate between windows).
    from imaginaire_tpu.data.device_prefetch import prefetch_settings

    pf_on, pf_depth = prefetch_settings(cfg)
    print(f"data.device_prefetch: {'on' if pf_on else 'off'} "
          f"(depth {pf_depth})")

    if args.checkpoint:
        checkpoints = [args.checkpoint]
    elif args.checkpoint_logdir:
        # quarantined ``*.corrupt`` renames and sidecar files must not
        # enter the sweep — training already refused them
        checkpoints = sorted(
            p for p in glob.glob(os.path.join(args.checkpoint_logdir,
                                              "*checkpoint*"))
            if (os.path.isdir(p) or p.endswith((".ckpt", ".orbax")))
            and ".corrupt" not in os.path.basename(p)
            and not p.endswith((".json", ".pkl")))
    else:
        raise SystemExit("pass --checkpoint or --checkpoint_logdir")

    metrics = [m.strip().lower() for m in args.metrics.split(",")
               if m.strip()]
    unknown = set(metrics) - {"fid", "kid", "prdc"}
    if unknown:
        raise SystemExit(f"unknown --metrics {sorted(unknown)}; "
                         "supported: fid, kid, prdc")
    from imaginaire_tpu.resilience import quarantine_checkpoint

    for checkpoint in checkpoints:
        # every restore in the sweep runs the PR-7 integrity path; a
        # checkpoint training would refuse is quarantined and SKIPPED
        # (ISSUE 8 satellite) — one corrupt snapshot must not abort a
        # whole sweep, and silently evaluating garbage weights is worse
        try:
            trainer.load_checkpoint(checkpoint, resume=True)
        except Exception as e:  # noqa: BLE001 — corrupt/truncated
            print(f"WARNING: skipping {checkpoint} — restore failed "
                  f"({type(e).__name__}: {str(e)[:200]}); quarantining")
            quarantine_checkpoint(checkpoint,
                                  reason=f"eval restore failed: "
                                         f"{type(e).__name__}")
            continue
        print(f"Evaluating {checkpoint} (epoch {trainer.current_epoch}, "
              f"iteration {trainer.current_iteration})")
        if "fid" in metrics:
            # ISSUE 18: FID routes through the sharded eval plane —
            # reference activations via the content-addressed store,
            # eval/* counters into this run's jsonl (the SAME schema
            # continuous eval emits, so check_run_health --max-fid
            # gates offline sweeps too). Trainer families without a
            # plane-capable generator closure (video rollouts) return
            # None and fall back to the classic write_metrics path.
            result = trainer.continuous_eval(trainer.current_iteration,
                                             metrics=["fid"])
            if result is None:
                trainer.write_metrics()
            else:
                print(f"  FID: {result['fid']:.5f} "
                      f"(time_to_fid {result['time_to_fid_ms']:.0f} ms, "
                      f"ref_cache_hit={result['ref_cache_hit']})")
        extra_requested = [m for m in metrics if m != "fid"]
        extra = trainer.compute_extra_metrics(extra_requested)
        if extra_requested and not extra:
            # argparse already rejected names outside {fid,kid,prdc}, so
            # an empty result means the trainer/runtime couldn't produce
            # the valid request — fail instead of a silent partial sweep
            raise SystemExit(
                f"--metrics {','.join(extra_requested)} requested but "
                f"{type(trainer).__module__} produced none (unsupported "
                "for this trainer, missing inception weights, or a val "
                "set without sequence pinning)")
        for name, value in extra.items():
            print(f"  {name}: {value:.5f}")
    telemetry.get().shutdown()
    print("Done with evaluation!!!")


if __name__ == "__main__":
    main()
