"""Training entry point (ref: train.py:33-94).

argparse -> Config -> mesh init -> dataloaders -> trainer -> epoch/iter
loop with dis_step/gen_step multipliers.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax

from imaginaire_tpu import resilience, telemetry
from imaginaire_tpu.resilience import chaos, cluster, elastic
from imaginaire_tpu.config import Config, cfg_get
from imaginaire_tpu.data import get_train_and_val_dataloader
from imaginaire_tpu.parallel.mesh import (
    create_mesh,
    fit_mesh_shape,
    master_only_print as print,  # noqa: A001
    maybe_init_distributed_from_env,
    mesh_from_config,
    set_mesh,
)
from imaginaire_tpu.registry import resolve
from imaginaire_tpu.utils import compile_cache
from imaginaire_tpu.utils.logging_utils import init_logging, make_logging_dir


def parse_args():
    parser = argparse.ArgumentParser(description="imaginaire-tpu training")
    parser.add_argument("--config", required=True)
    parser.add_argument("--logdir", default=None)
    parser.add_argument("--checkpoint", default="")
    parser.add_argument("--seed", type=int, default=2)
    parser.add_argument("--max_iter", type=int, default=None,
                        help="override cfg max_iter (smoke tests)")
    parser.add_argument("--debug-nans", action="store_true",
                        help="enable jax_debug_nans for CPU repro runs: "
                             "every primitive's output is checked and the "
                             "first NaN raises with the op's stack trace. "
                             "Implies trainer.donate_step_buffers=False — "
                             "the de-jitted re-run reads buffers donation "
                             "would already have invalidated. Expect a "
                             "large slowdown; pair with JAX_PLATFORMS=cpu "
                             "and a tiny config.")
    return parser.parse_args()


def _maybe_elastic_join():
    """Elastic joiner mode (ISSUE 11): ``IMAGINAIRE_ELASTIC_JOIN``
    names the logdir of a live elastic pod — this process announces a
    join request and blocks until the pod's grow plan admits it. The
    granted plan points the ``IMAGINAIRE_DIST_*`` contract at the
    agreed topology BEFORE any jax backend exists, so the normal
    startup path below needs no special casing; the plan's barrier
    epochs are adopted so the first counter-tagged rendezvous doesn't
    trip a spurious desync (satellite: barrier-epoch negotiation)."""
    logdir = os.environ.get("IMAGINAIRE_ELASTIC_JOIN")
    if not logdir:
        return None
    nonce = os.environ.get("IMAGINAIRE_ELASTIC_JOIN_NONCE",
                           f"join-{os.getpid()}")
    timeout_s = float(os.environ.get("IMAGINAIRE_ELASTIC_JOIN_TIMEOUT_S",
                                     "600"))
    elastic.request_join(logdir, nonce)
    plan = elastic.wait_for_join(logdir, nonce, timeout_s=timeout_s)
    cluster.adopt_barrier_epochs(plan.barrier_epochs)
    return plan


def main():
    compile_cache.configure()
    # elastic joiner rendezvous must precede distributed init: it is
    # what PRODUCES the IMAGINAIRE_DIST_* contract for a joining host
    _maybe_elastic_join()
    # multi-process pods (ISSUE 8): IMAGINAIRE_DIST_* env vars (set by
    # scripts/launch_local_pod.py or a real pod launcher) initialize
    # jax.distributed BEFORE any backend exists — every jax.devices()
    # below then spans the whole pod
    maybe_init_distributed_from_env()
    args = parse_args()
    cfg = Config(args.config)
    if args.max_iter is not None:
        cfg.max_iter = args.max_iter
    if args.debug_nans:
        # the coarse in-run triage (diagnostics/) names the term/module;
        # this flag is the fine-grained follow-up that names the exact
        # primitive. Donation must be off: jax_debug_nans re-executes
        # the step de-jitted, and the jitted call already consumed the
        # donated state buffers.
        jax.config.update("jax_debug_nans", True)
        cfg.trainer.donate_step_buffers = False
        print("--debug-nans: jax_debug_nans on, step-buffer donation off "
              "(expect higher memory + much slower steps)")

    # single mesh entry point: cfg.parallel.mesh_shape (2-D data x model
    # + sharded update state, parallel/partition.py) wins over the
    # legacy runtime.mesh block
    set_mesh(mesh_from_config(cfg))
    date_uid, logdir = init_logging(args.config, args.logdir)
    make_logging_dir(logdir)
    cfg.logdir = logdir
    # structured run telemetry (telemetry/): spans + counters fan out to
    # the configured sinks (<logdir>/telemetry.jsonl by default); the
    # watchdog/trace knobs ride the same cfg section
    tm = telemetry.configure(cfg, logdir=logdir)
    # fault-tolerance layer (resilience/): retry policy + chaos
    # injection singleton, the SIGTERM preemption guard that drains the
    # in-flight step into an emergency checkpoint (ISSUE 7), and the
    # cluster coordination policy — timed barriers, per-step preemption
    # votes, cross-host heartbeats (ISSUE 8)
    rsettings = resilience.configure(cfg)
    guard = resilience.install_preemption_guard(cfg)
    cluster.start_heartbeat(cfg)
    sync_every = rsettings["cluster"]["sync_every_n_steps"] \
        if cluster.is_active() else 0
    # elastic pods (ISSUE 11): the coordinator owns the resize
    # lifecycle — shrink consensus over the KV store, grow rendezvous
    # through <logdir>/elastic/, in-process runtime teardown/re-init
    elastic_co = resilience.ElasticCoordinator(cfg, logdir=logdir)
    elastic_on = elastic_co.enabled and cluster.is_active()

    train_loader, val_loader = get_train_and_val_dataloader(cfg, seed=args.seed)
    trainer_cls = resolve(cfg.trainer.type, "Trainer")
    trainer = trainer_cls(cfg, train_data_loader=train_loader,
                          val_data_loader=val_loader)

    # hparams dashboard entry (ref: train.py + meters.add_hparams)
    from imaginaire_tpu.utils.meters import add_hparams

    add_hparams({
        "trainer": str(cfg.trainer.type),
        "gen": str(cfg.gen.type),
        "gen_lr": float(cfg_get(cfg.gen_opt, "lr", 0)),
        "dis_lr": float(cfg_get(cfg.dis_opt, "lr", 0)),
        "batch_size": int(cfg_get(cfg.data.train, "batch_size", 1)),
        "compute_dtype": str(cfg_get(cfg.trainer, "compute_dtype",
                                     "float32")),
        "seed": args.seed,
    }, {"metrics/placeholder": 0.0})

    sample = next(iter(train_loader))
    sample = trainer.start_of_iteration(sample, 0)
    trainer.init_state(jax.random.PRNGKey(args.seed), sample)
    if args.checkpoint:
        trainer.load_checkpoint(args.checkpoint)
    else:
        trainer.load_checkpoint()  # resume from pointer file if present

    current_iteration = trainer.current_iteration
    current_epoch = trainer.current_epoch
    # bit-exact resume (resilience/runstate.py): the checkpoint's
    # runstate sidecar recorded how many batches of the interrupted
    # epoch were already consumed; the first resumed epoch fast-forwards
    # the loader past them instead of replaying the epoch from batch 0
    # (the shuffle is seeded by (seed, epoch), so the skipped prefix is
    # exactly what the killed run already trained on).
    resume_offset = int(getattr(trainer, "resume_batch_in_epoch", 0) or 0)
    max_iter = cfg_get(cfg, "max_iter", 1000000)
    max_epoch = cfg_get(cfg, "max_epoch", 200)
    dis_steps = cfg_get(cfg.trainer, "dis_step", 1)
    gen_steps = cfg_get(cfg.trainer, "gen_step", 1)

    # Async device prefetch (data/device_prefetch.py): a producer thread
    # runs the host-side _start_of_iteration hook and commits batches to
    # device as sharded arrays while the previous step computes, so the
    # loop below never blocks on H2D. The epoch_base cell hands the hook
    # the iteration each read-ahead batch will be consumed at. With
    # data.device_prefetch off, feed is the loader and
    # start_of_iteration keeps the synchronous to_device transfer.
    epoch_base = [current_iteration]
    feed = trainer.data_prefetcher(
        train_loader, iteration_of=lambda index: epoch_base[0] + index)
    prefetching = feed is not train_loader
    timed_feed = None

    # supervise loop (ISSUE 11): the epoch loop runs inside a resume
    # loop. An ``ElasticResize`` unwinding out of it is not an error —
    # the survivors tear the distributed runtime down IN-PROCESS,
    # re-init the agreed (shrunken or grown) topology, restore through
    # the layout-agnostic no-target checkpoint path, and re-enter.
    # Every other exception propagates exactly as before.
    while True:
        try:
            for epoch in range(current_epoch, max_epoch):
                print(f"Epoch {epoch} ...")
                train_loader.set_epoch(epoch)
                trainer.start_of_epoch(epoch)
                epoch_base[0] = current_iteration
                if resume_offset:
                    if hasattr(feed, "fast_forward"):
                        feed.fast_forward(resume_offset)
                        print(f"Resume: fast-forwarding {resume_offset} "
                              f"already-consumed batch(es) of epoch "
                              f"{epoch}")
                    resume_offset = 0
                # each next(feed) is timed as a data_wait span: with the
                # prefetcher healthy it is ~0; a starved queue shows up
                # as the dominant phase in the telemetry table instead
                # of vanishing into "slow steps"
                timed_feed = tm.timed_iter(
                    feed, "data_wait",
                    step_of=lambda index: epoch_base[0] + index)
                data = None
                for it, data in enumerate(timed_feed):
                    data = trainer.start_of_iteration(data,
                                                      current_iteration)
                    data = chaos.get().maybe_nan_batch(data,
                                                       current_iteration)
                    for _ in range(dis_steps):
                        trainer.dis_update(data)
                    for _ in range(gen_steps):
                        trainer.gen_update(data)
                    current_iteration += 1
                    if prefetching:
                        trainer.write_data_meters(feed.drain_stats())
                    # distributed chaos (ISSUE 8): stall-one-of-N
                    # freezes THIS process here — after the step's
                    # collectives dispatched, before any cluster
                    # rendezvous — so the surviving hosts' next timed
                    # barrier (preemption vote or checkpoint entry)
                    # names it instead of hanging
                    chaos.get().maybe_stall(current_iteration)
                    trainer.end_of_iteration(data, epoch,
                                             current_iteration)
                    chaos.get().maybe_sigterm(current_iteration)
                    chaos.get().maybe_kill(current_iteration)
                    drain = guard is not None and guard.triggered
                    flagged = []
                    if sync_every:
                        # coordinated preemption (ISSUE 8): a SIGTERM
                        # lands on ONE host but the emergency save is
                        # collective — the per-step vote makes every
                        # host observe the same OR at the same
                        # iteration, so the pod drains together instead
                        # of deadlocking (one host in the save barrier,
                        # the rest in the next step's psum). Between
                        # vote iterations a locally-triggered guard
                        # DEFERS: draining solo is the deadlock this
                        # machinery exists to avoid.
                        if current_iteration % sync_every == 0:
                            if elastic_on:
                                # peer-loss signal 1 (ISSUE 11): a host
                                # that died WITHOUT a drain vote shows
                                # up as heartbeat staleness — shrink
                                # around it from the last checkpoint
                                stale = cluster.stalled_peers()
                                if stale and elastic_co.can_shrink(
                                        stale):
                                    print(f"Peer(s) {stale} heartbeat-"
                                          f"stale at iteration "
                                          f"{current_iteration} — "
                                          f"elastic shrink")
                                    timed_feed.close()
                                    raise elastic.ElasticResize(
                                        elastic_co.plan_shrink(
                                            stale, iteration=-1,
                                            epoch=epoch))
                            voted, flagged = \
                                cluster.coordinate_preemption(
                                    current_iteration, drain,
                                    return_flagged=True)
                            if voted and not drain and guard is not None:
                                guard.trigger_remote(flagged)
                            drain = drain or (voted and guard is not None)
                            if (elastic_on and not drain
                                    and elastic_co.settings.get(
                                        "grow_back", True)):
                                # scale-up (ISSUE 13): the master folds
                                # pending join requests into a grow
                                # announcement with a strictly-future
                                # target step (the KV write
                                # happens-before every peer's next
                                # post-barrier poll); at the target
                                # step the whole pod commits a
                                # synchronous checkpoint, publishes the
                                # new topology for the joiners, and
                                # resizes; cfg.resilience.elastic
                                # .grow_back=False pins the shrunken
                                # world (joiner requests stay queued)
                                if cluster.process_index() == 0:
                                    nonces = \
                                        elastic_co.check_join_requests()
                                    if nonces:
                                        elastic_co.announce_grow(
                                            current_iteration
                                            + 2 * sync_every, nonces)
                                grow = elastic_co.poll_grow()
                                if grow and current_iteration >= int(
                                        grow["target"]):
                                    trainer.save_checkpoint(
                                        epoch, current_iteration,
                                        emergency=True)
                                    plan = elastic_co.plan_grow(
                                        grow["joiners"],
                                        current_iteration, epoch)
                                    if cluster.process_index() == 0:
                                        elastic_co.publish_topology(plan)
                                        elastic_co.consume_join_requests(
                                            grow["joiners"])
                                    timed_feed.close()
                                    raise elastic.ElasticResize(plan)
                        else:
                            drain = False
                    if drain:
                        # preemption drain: the dispatched step already
                        # landed (save blocks on the live arrays), so
                        # commit an emergency checkpoint + run state
                        trainer.emergency_checkpoint(
                            epoch, current_iteration, guard)
                        # deterministic producer shutdown: closing the
                        # timed iterator unwinds the prefetcher's
                        # generator (stop flag + queue drain + producer
                        # join) before teardown or exit
                        timed_feed.close()
                        me = cluster.process_index()
                        if (elastic_on and me not in flagged
                                and elastic_co.can_shrink(flagged)):
                            # elastic drain split (ISSUE 11): the
                            # flagged host(s) exit below as before; the
                            # survivors run the shrink consensus and
                            # keep training in-process from the
                            # emergency checkpoint the FULL world just
                            # committed — its ZeRO shards are complete
                            plan = elastic_co.plan_shrink(
                                flagged, iteration=current_iteration,
                                epoch=epoch)
                            if guard is not None:
                                guard.reset()
                            raise elastic.ElasticResize(plan)
                        _finalize_run(trainer)
                        # the exit line prints BEFORE any teardown:
                        # print here is the master-gated wrapper, and
                        # is_master() -> jax.process_index() would try
                        # to REBUILD the cpu backend after
                        # force_teardown detached the distributed
                        # client (its gloo collectives factory then
                        # gets a None client and the process dies 1,
                        # not 75)
                        print(f"Preempted at iteration "
                              f"{current_iteration}; emergency "
                              f"checkpoint committed — exit "
                              f"{resilience.EXIT_PREEMPTED} (resumable)")
                        if elastic_on:
                            # a flagged host leaving an elastic pod
                            # detaches its distributed client before
                            # exiting: the survivors LEAK (never shut
                            # down) the old coordination service, and
                            # an attached client whose coordinator
                            # later vanishes mid-exit can abort the
                            # interpreter instead of exiting 75
                            cluster.stop_heartbeat()
                            elastic.force_teardown()
                        sys.exit(resilience.EXIT_PREEMPTED)
                    if current_iteration >= max_iter:
                        print("Done with training!!!")
                        # deterministic producer shutdown (as in the
                        # drain path): the prefetcher's thread otherwise
                        # stays blocked on its queue, holding device
                        # batches and the trainer, for the life of a
                        # process that goes on after main() returns
                        timed_feed.close()
                        trainer.save_checkpoint(epoch, current_iteration)
                        _finalize_run(trainer)
                        return trainer
                if data is None:
                    # resumed exactly at an epoch boundary: every batch
                    # of this epoch was consumed before the kill —
                    # nothing to replay
                    continue
                trainer.end_of_epoch(data, epoch, current_iteration)
            print("Done with training!!!")
            _finalize_run(trainer)
            return trainer
        except elastic.ElasticResize as resize:
            plan = resize.plan
        except cluster.ClusterDesyncError as desync:
            # peer-loss signal 2 (ISSUE 11): a timed collective expired
            # and named the absent process(es). When the survivors may
            # reshape, shrink around them; otherwise fail the pod
            # loudly, exactly as before.
            if not (elastic_on and elastic_co.can_shrink(desync.absent)):
                raise
            if timed_feed is not None:
                try:
                    timed_feed.close()
                except Exception:  # noqa: BLE001 — already unwinding
                    pass
            print(f"Cluster desync (absent: {list(desync.absent)}) — "
                  f"elastic shrink instead of pod restart")
            plan = elastic_co.plan_shrink(
                desync.absent, iteration=-1,
                epoch=int(getattr(trainer, "current_epoch", 0) or 0))
            if guard is not None:
                guard.reset()

        # ---- apply the agreed resize in-process and re-enter --------
        t_down = time.perf_counter()
        print(f"Elastic resize: generation {plan.generation}, world "
              f"{plan.old_world} -> {plan.world_size} ({plan.reason})")
        try:
            # redistribution plan (ISSUE 13): route each state leaf
            # between the checkpoint reshard path and a direct carry.
            # The gather snapshot MUST land before apply() — teardown
            # clears the backend the live arrays live on.
            rplan = elastic.RedistributionPlanner(
                plan, trainer.current_iteration, trainer.state)
            carry = (rplan.snapshot(trainer.state)
                     if trainer.state is not None and rplan.routes
                     else {})
            phases = elastic_co.apply(plan)
            t_mesh = time.perf_counter()
            axes, dims = fit_mesh_shape(cfg, jax.device_count())
            set_mesh(create_mesh(axes, dims))
            phases["mesh_ms"] = round(
                (time.perf_counter() - t_mesh) * 1000.0, 3)
            t_restore = time.perf_counter()
            trainer.elastic_rebind()
            if carry and rplan.all_gather:
                # every leaf carried live: skip the orbax round-trip
                # and re-commit directly under the new shardings
                trainer.elastic_recommit(carry, plan.iteration,
                                         plan.epoch)
            else:
                trainer.set_elastic_carry(carry)
                trainer.load_checkpoint()
            phases["restore_ms"] = round(
                (time.perf_counter() - t_restore) * 1000.0, 3)
        except Exception as e:  # noqa: BLE001 — resize is best-effort
            import traceback

            traceback.print_exc()
            # builtin print, not master_only_print: process_index()
            # would boot a LOCAL backend if the re-init died mid-way
            sys.stderr.write(
                f"elastic resize failed ({e}); the checkpointed state "
                f"is intact — exit {resilience.EXIT_ELASTIC_RESTART} "
                f"for a supervisor relaunch\n")
            try:
                telemetry.get().shutdown()
            except Exception:  # noqa: BLE001 — exiting either way
                pass
            sys.exit(resilience.EXIT_ELASTIC_RESTART)
        downtime_ms = (time.perf_counter() - t_down) * 1000.0
        elastic_co.record_resize(plan, downtime_ms, phases,
                                 redistribution=rplan.summary())
        current_iteration = trainer.current_iteration
        current_epoch = trainer.current_epoch
        resume_offset = int(getattr(trainer, "resume_batch_in_epoch", 0)
                            or 0)
        epoch_base = [current_iteration]
        feed = trainer.data_prefetcher(
            train_loader,
            iteration_of=lambda index: epoch_base[0] + index)
        prefetching = feed is not train_loader
        timed_feed = None
        print(f"Elastic resize complete in {downtime_ms:.0f}ms — "
              f"resuming at iteration {current_iteration}, epoch "
              f"{current_epoch}")


def _finalize_run(trainer=None):
    """Async checkpoint saves must commit — and the health monitor's
    pending step plus telemetry's final window must flush — before the
    process exits."""
    from imaginaire_tpu.utils.checkpoint import wait_for_pending_checkpoint

    if trainer is not None:
        # the monitor polls with one-step lag; the final step's health
        # entry (and any non-finite verdict) is still pending here
        trainer.diag.drain(trainer)
    wait_for_pending_checkpoint()
    telemetry.get().shutdown()


if __name__ == "__main__":
    main()
