"""Checkpoint save/load with the reference's pointer-file contract
(ref: imaginaire/trainers/base.py:199-265, 790-829; SURVEY.md §5.4).

orbax handles the array serialization; the surrounding protocol is kept
bit-compatible in spirit:
  - checkpoints at ``<logdir>/epoch_EEEEE_iteration_IIIIIIIII_checkpoint``
  - ``<logdir>/latest_checkpoint.txt`` holds the latest checkpoint name
  - resume mode restores everything; weights-only mode restores params

Multi-host contract (the reference master-gates torch.save,
ref: trainers/base.py:790-829): ``save_checkpoint`` must be called by
EVERY process with the (possibly non-fully-addressable) sharded state —
it hands the live ``jax.Array`` pytree to orbax, whose save is a
collective: each host serializes only the shards it owns and the
coordinator commits the checkpoint atomically. The pointer file is
written by the master process only, after the commit. ``device_get`` is
deliberately NOT used here: it would materialize the full state on every
host (and raises for non-addressable arrays on real multi-host slices).

``async_save=True`` uses ``ocp.AsyncCheckpointer``: serialization runs
in a background thread after a device barrier, so training resumes
immediately (preemption-safe: an interrupted async save leaves only a
tmp dir, never a half-committed checkpoint — the pointer still names the
previous complete one). Call ``wait_for_pending_checkpoint()`` before
reading the checkpoint back or exiting the process.

Fault tolerance (ISSUE 7, ``resilience/``):
  - per-leaf crc32 checksums are computed at save time and ride the
    checkpoint's sidecar — the existing ``.partition.json`` when a
    partition descriptor is saved, ``.integrity.json`` otherwise;
  - ``load_checkpoint`` verifies the restored bytes against them and
    raises ``CheckpointIntegrityError`` on mismatch;
  - ``load_latest_verified`` implements the resume path: a corrupt /
    truncated / missing pointed checkpoint is quarantined (``*.corrupt``
    rename + ``ckpt/quarantined`` meta event) and the newest checkpoint
    that DOES verify is restored instead (``ckpt/fallback`` +
    ``resilience/ckpt_fallbacks``);
  - ``latest_checkpoint_path`` falls back to a logdir scan when the
    pointer names a dead path (a crash between quarantine/deletion and
    the next pointer write must not strand the run);
  - ``max_to_keep`` retention GC runs after each pointer write and never
    deletes the pointer target or the newest verifiable checkpoint;
  - pointer/sidecar writes retry transient IO with bounded backoff
    (``resilience/retry.py``).
"""

from __future__ import annotations

import os
import re

import orbax.checkpoint as ocp

from imaginaire_tpu import telemetry
from imaginaire_tpu.parallel.mesh import is_master

_POINTER = "latest_checkpoint.txt"
_CKPT_RE = re.compile(r"^epoch_(\d+)_iteration_(\d+)_checkpoint$")

# Lazily-built singleton: AsyncCheckpointer owns a thread pool + barrier
# state, so one per process, reused across saves.
_ASYNC_CKPT = None
# The one in-flight pointer-writer thread (see save_checkpoint): joined
# by wait_for_pending_checkpoint so pointer writes can never interleave
# across saves or be lost at process exit.
_POINTER_THREAD = None


# How many GB of a checkpoint may be in flight on the host at once
# (device-to-host copies, encode/decode buffers). Orbax's default is the
# whole tree at once: saving a zoo-width SPADE state (6.8 GiB) then took
# another 10.3 GiB of host memory on the TPU v5e host, and restoring it
# 7.3 (PR 22, measured), on a host whose 40 GiB the compiles of that
# width had already come within reach of.
_HOST_IN_FLIGHT_GB = 1


def _pytree_handler(**kwargs):
    return ocp.PyTreeCheckpointHandler(
        save_concurrent_gb=_HOST_IN_FLIGHT_GB,
        save_device_host_concurrent_gb=_HOST_IN_FLIGHT_GB,
        restore_concurrent_gb=_HOST_IN_FLIGHT_GB, **kwargs)


def _async_checkpointer():
    global _ASYNC_CKPT
    if _ASYNC_CKPT is None:
        # Directories are created inside ``save`` (behind orbax's
        # fixed-name barriers), not on a background thread: orbax's
        # asynchronous directory creation signals peers through KV keys
        # that embed a PER-PROCESS operation counter, and an elastic
        # joiner (ISSUE 13) has a shorter save history than the
        # survivors, so it would wait on a key nobody sets.
        _ASYNC_CKPT = ocp.AsyncCheckpointer(
            _pytree_handler(),
            async_options=ocp.options.AsyncOptions(
                create_directories_asynchronously=False))
    return _ASYNC_CKPT


def checkpoint_name(epoch, iteration):
    return f"epoch_{epoch:05d}_iteration_{iteration:09d}_checkpoint"


def parse_checkpoint_name(name):
    m = re.search(r"epoch_(\d+)_iteration_(\d+)", os.path.basename(name))
    if not m:
        return 0, 0
    return int(m.group(1)), int(m.group(2))


def scan_checkpoints(logdir):
    """Committed checkpoints under ``logdir``, oldest first, as
    ``[(epoch, iteration, path), ...]``. Only exact
    ``epoch_*_iteration_*_checkpoint`` directory names count —
    quarantined ``*.corrupt`` renames and tmp dirs never match."""
    try:
        names = os.listdir(logdir)
    except OSError:
        return []
    out = []
    for name in names:
        m = _CKPT_RE.match(name)
        path = os.path.join(logdir, name)
        if m and os.path.isdir(path):
            out.append((int(m.group(1)), int(m.group(2)), path))
    out.sort()
    return out


def save_checkpoint(logdir, state, epoch, iteration, max_to_keep=None,
                    async_save=False, partition_descriptor=None,
                    checksum=True):
    """Collective save of the sharded state + master-only pointer write.

    Every process passes its live state pytree; orbax writes each array
    shard from the host that owns it (ref contract: base.py:790-829).
    With ``async_save`` the call returns as soon as device arrays are
    snapshotted; the pointer is then written by a completion callback so
    it never names an uncommitted checkpoint.

    ``checksum`` computes per-leaf crc32 checksums of the state at
    dispatch time (one device_get of the addressable leaves) and
    writes them into the checkpoint's
    sidecar after the commit; ``partition_descriptor`` (the active
    partition plan's ``describe()``) makes that sidecar the existing
    ``.partition.json``, otherwise checksums land in
    ``.integrity.json``. ``max_to_keep`` enables retention GC after the
    pointer write (never deletes the pointer target or the newest
    verifiable checkpoint).
    """
    from imaginaire_tpu.resilience import chaos

    name = checkpoint_name(epoch, iteration)
    path = os.path.abspath(os.path.join(logdir, name))
    # commit any in-flight async save first: back-to-back saves would
    # otherwise race the existence check below (orbax also serializes
    # saves internally, so this costs nothing extra)
    wait_for_pending_checkpoint()
    # Multi-process entry barrier (ISSUE 8): orbax's collective save
    # blocks untimed on every host — a peer that never arrives (dead or
    # stalled) used to hang the pod here forever. The timed rendezvous
    # raises ClusterDesyncError NAMING the absent process instead; once
    # everyone has passed it, the collective itself is entered together.
    from imaginaire_tpu.resilience import cluster

    cluster.timed_barrier("ckpt_enter", tag=name)

    def _write_pointer():
        if is_master():
            from imaginaire_tpu.resilience.retry import retry_call

            def _write():
                with open(os.path.join(logdir, _POINTER), "w") as f:
                    f.write(name + "\n")

            retry_call(_write, label="ckpt_pointer")

    def _after_commit():
        """Sidecar + pointer + GC + chaos hook — runs strictly after
        the array data is committed, in commit order. The committed
        files' raw-byte digests join the integrity record here (they
        only exist post-commit): restore verifies THEM before the
        deserializer touches the data — feeding corrupt bytes to a
        native decoder is a heap hazard, not just a wrong answer."""
        full = integrity
        if full is not None:
            try:
                from imaginaire_tpu.resilience.integrity import (
                    file_digests,
                )

                full = dict(full, files=file_digests(path))
            except Exception as e:  # noqa: BLE001 — never fail a save
                import logging

                logging.getLogger(__name__).warning(
                    "checkpoint file-digest pass failed: %s", e)
        _write_sidecars(path, partition_descriptor, full)
        # All-host commit barrier BEFORE the pointer moves (ISSUE 8):
        # the pointer must never name a checkpoint some host has not
        # finished committing — a restart racing that window would
        # resume half the pod from the new checkpoint and half from
        # the old one. Timed, so a host that died mid-commit surfaces
        # as a named ClusterDesyncError, not a wedged pointer thread.
        from imaginaire_tpu.resilience import cluster

        cluster.timed_barrier("ckpt_commit", tag=name)
        _write_pointer()
        gc_checkpoints(logdir, max_to_keep, protect=(path,))
        chaos.get().maybe_corrupt_checkpoint(path, iteration)

    if os.path.exists(path):
        # idempotent per (epoch, iteration): the final-iteration save and
        # a coinciding snapshot_save_iter save name the same state; orbax
        # refuses to overwrite a committed checkpoint, and the reference's
        # torch.save overwrite would be a no-op here anyway. Still (re)write
        # the pointer — a crash between a past commit and its pointer write
        # must not leave the newer checkpoint unnamed forever.
        print(f"Checkpoint {name} already exists; skipping duplicate save")
        _write_pointer()
        return path

    # checksums are computed from the live arrays BEFORE dispatch: after
    # an async save returns, the caller's buffers may be donated to the
    # next step, so the commit thread must never touch ``state`` again
    integrity = None
    if checksum and is_master():
        from imaginaire_tpu.resilience.integrity import tree_checksums

        with telemetry.span("ckpt_checksum"):
            try:
                integrity = tree_checksums(state)
            except Exception as e:  # noqa: BLE001 — never fail a save
                import logging

                logging.getLogger(__name__).warning(
                    "checkpoint checksum computation failed: %s", e)

    if async_save:
        global _POINTER_THREAD
        ckpt = _async_checkpointer()
        with telemetry.span("ckpt"):
            # async path: the span covers only the device snapshot +
            # save dispatch (what the step loop actually pays); the
            # background commit gets its own ckpt_commit span
            ckpt.save(path, state)
        # orbax finalizes the save (tmp-dir rename) on its background
        # thread; queue the pointer write behind that commit so readers
        # never observe pointer-before-commit. The thread handle is kept
        # so wait_for_pending_checkpoint can join it — otherwise a later
        # save's pointer could be overwritten by this older thread, or
        # the write lost at process exit. Both a commit failure and a
        # pointer-write failure are stashed on the thread and re-raised
        # at the join, never swallowed — and the pointer is only written
        # when the commit actually succeeded, so it can never name a
        # checkpoint that failed to finalize.
        import threading

        def _commit_then_point():
            try:
                with telemetry.span("ckpt_commit"):
                    ckpt.wait_until_finished()
                _after_commit()
            except BaseException as e:  # re-raised by the joiner
                _commit_then_point.error = e

        _commit_then_point.error = None
        # named so watchdog stack dumps identify a wedged commit
        _POINTER_THREAD = threading.Thread(target=_commit_then_point,
                                           daemon=True, name="ckpt-pointer")
        _POINTER_THREAD._pointer_fn = _commit_then_point
        _POINTER_THREAD.start()
    else:
        with telemetry.span("ckpt"):
            with ocp.Checkpointer(_pytree_handler()) as ckpt:
                ckpt.save(path, state)
        _after_commit()
        telemetry.get().heartbeat()
    return path


def wait_for_pending_checkpoint():
    """Block until any in-flight async save has committed AND its
    pointer write has landed."""
    global _POINTER_THREAD
    if _ASYNC_CKPT is not None:
        with telemetry.span("ckpt_wait"):
            _ASYNC_CKPT.wait_until_finished()
        telemetry.get().heartbeat()
    if _POINTER_THREAD is not None:
        thread = _POINTER_THREAD
        _POINTER_THREAD = None
        thread.join()
        err = getattr(thread._pointer_fn, "error", None)
        if err is not None:
            raise RuntimeError(
                "async checkpoint commit or pointer write failed; "
                "latest_checkpoint.txt still names the previous complete "
                "checkpoint") from err


def latest_checkpoint_path(logdir):
    """The pointed checkpoint (ref: base.py:225-233) — falling back to
    the newest parseable checkpoint in ``logdir`` when the pointer names
    a missing/unreadable path (quarantined, GC'd by an older policy, or
    torn by a crash). No pointer file at all still returns None: only
    the master ever writes it, and a fresh logdir must not resume from
    stray directories."""
    pointer = os.path.join(logdir, _POINTER)
    if not os.path.exists(pointer):
        return None
    try:
        with open(pointer) as f:
            name = f.read().strip()
    except OSError:
        name = ""
    path = os.path.join(logdir, name) if name else None
    if path and os.path.exists(path):
        return path
    entries = scan_checkpoints(logdir)
    if not entries:
        return None
    fallback = entries[-1][2]
    telemetry.get().meta("ckpt/pointer_fallback", pointer=name or None,
                         fallback=fallback)
    import logging

    logging.getLogger(__name__).warning(
        "latest_checkpoint.txt names %r which does not exist; falling "
        "back to newest checkpoint in logdir: %s", name, fallback)
    return fallback


# ------------------------------------------------------------- sidecars


def _write_sidecars(path, partition_descriptor, integrity):
    """Write the checkpoint's sidecar(s): checksums ride the partition
    sidecar when a descriptor is saved, ``.integrity.json`` otherwise
    (replicated checkpoints carry no ``.partition.json`` — legacy
    readers treat its absence as 'saved replicated')."""
    if partition_descriptor is not None:
        write_partition_sidecar(path, partition_descriptor,
                                integrity=integrity)
    elif integrity is not None:
        write_integrity_sidecar(path, integrity)


def write_partition_sidecar(path, descriptor, integrity=None):
    """Persist the saving run's partition-plan descriptor (mesh axes/
    shape + update-state sharding knobs, see
    ``PartitionPlan.describe``) as a ``<ckpt>.partition.json`` sibling —
    like the ``.ema_bn.pkl`` sibling, a sidecar keeps the state tree's
    structure stable across checkpoint versions. Master-only; a missing
    sidecar means 'saved replicated' (pre-ISSUE-6 checkpoints). The
    per-leaf ``integrity`` checksums ride the same file under the
    reserved ``integrity`` key (``read_partition_sidecar`` strips it)."""
    import json

    if not is_master():
        return
    payload = dict(descriptor or {})
    if integrity is not None:
        payload["integrity"] = integrity
    try:
        from imaginaire_tpu.resilience.retry import retry_call

        def _write():
            tmp = str(path) + ".partition.json.tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, default=str)
            os.replace(tmp, str(path) + ".partition.json")

        retry_call(_write, label="partition_sidecar")
    except Exception as e:  # noqa: BLE001 — a sidecar must never fail a save
        import logging

        logging.getLogger(__name__).warning(
            "partition sidecar write failed: %s", e)


def write_integrity_sidecar(path, integrity):
    """``<ckpt>.integrity.json`` for checkpoints without a partition
    descriptor. Master-only; never fails a save."""
    import json

    if not is_master():
        return
    try:
        from imaginaire_tpu.resilience.retry import retry_call

        def _write():
            tmp = str(path) + ".integrity.json.tmp"
            with open(tmp, "w") as f:
                json.dump(integrity, f, indent=1, default=str)
            os.replace(tmp, str(path) + ".integrity.json")

        retry_call(_write, label="integrity_sidecar")
    except Exception as e:  # noqa: BLE001
        import logging

        logging.getLogger(__name__).warning(
            "integrity sidecar write failed: %s", e)


def read_partition_sidecar(path):
    """The saved partition descriptor, or None (replicated / legacy).
    The ``integrity`` key (ISSUE 7 checksums sharing the file) is
    stripped — descriptor comparisons stay byte-compatible with
    pre-ISSUE-7 sidecars."""
    import json
    import os as _os

    sidecar = str(path) + ".partition.json"
    if not _os.path.exists(sidecar):
        return None
    try:
        with open(sidecar) as f:
            payload = json.load(f)
    except Exception:  # noqa: BLE001
        return None
    if isinstance(payload, dict):
        payload = {k: v for k, v in payload.items() if k != "integrity"}
        return payload or None
    return payload


def read_integrity_sidecar(path):
    """The saved per-leaf checksums, or None (legacy checkpoint):
    ``.partition.json``'s ``integrity`` key when present, else the
    standalone ``.integrity.json``."""
    import json
    import os as _os

    sidecar = str(path) + ".partition.json"
    if _os.path.exists(sidecar):
        try:
            with open(sidecar) as f:
                payload = json.load(f)
            if isinstance(payload, dict) and payload.get("integrity"):
                return payload["integrity"]
        except Exception:  # noqa: BLE001
            pass
    sidecar = str(path) + ".integrity.json"
    if not _os.path.exists(sidecar):
        return None
    try:
        with open(sidecar) as f:
            return json.load(f)
    except Exception:  # noqa: BLE001
        return None


# ------------------------------------------------------------ retention


def gc_checkpoints(logdir, max_to_keep, protect=()):
    """Retention GC: keep the newest ``max_to_keep`` checkpoints.

    Never deletes the pointer target, anything in ``protect``, or the
    newest checkpoint that carries integrity checksums (the last
    *verifiable* one — fallback must always have somewhere to land).
    Master-only; emits a ``ckpt/gc`` telemetry meta event naming what
    was deleted."""
    if not max_to_keep or int(max_to_keep) <= 0 or not is_master():
        return []
    entries = scan_checkpoints(logdir)
    if len(entries) <= int(max_to_keep):
        return []
    protected = {os.path.abspath(str(p)) for p in protect}
    pointer = os.path.join(logdir, _POINTER)
    if os.path.exists(pointer):
        try:
            with open(pointer) as f:
                pointed = f.read().strip()
            if pointed:
                protected.add(os.path.abspath(
                    os.path.join(logdir, pointed)))
        except OSError:
            pass
    # the newest verifiable checkpoint stays: it is where a corrupt
    # pointer target falls back to
    for _, _, path in reversed(entries):
        if read_integrity_sidecar(path) is not None:
            protected.add(os.path.abspath(path))
            break
    doomed = [path for _, _, path in entries[:-int(max_to_keep)]
              if os.path.abspath(path) not in protected]
    if not doomed:
        return []
    import logging
    import shutil

    from imaginaire_tpu.resilience.integrity import sidecar_files

    deleted = []
    for path in doomed:
        try:
            shutil.rmtree(path)
        except OSError as e:
            logging.getLogger(__name__).warning(
                "checkpoint GC failed to delete %s: %s", path, e)
            continue
        for sidecar in sidecar_files(path):
            try:
                os.remove(sidecar)
            except OSError:
                pass
        deleted.append(path)
    if deleted:
        tm = telemetry.get()
        if tm.enabled:
            tm.meta("ckpt/gc", deleted=[os.path.basename(p)
                                        for p in deleted],
                    kept=len(entries) - len(deleted),
                    max_to_keep=int(max_to_keep))
            tm.counter("resilience/ckpt_gc_deleted", len(deleted))
        logging.getLogger(__name__).info(
            "checkpoint GC deleted %d checkpoint(s) (max_to_keep=%d): %s",
            len(deleted), int(max_to_keep),
            [os.path.basename(p) for p in deleted])
    return deleted


# -------------------------------------------------------------- restore


def _restore_checkpointer():
    """A PyTree checkpointer whose restore syncs with no other process.

    ``Checkpointer.restore`` closes with ``sync_global_processes`` — an
    UNTIMED all-device sync. In an elastic pod (ISSUE 13) restores are
    legitimately asymmetric: a joiner restores the published checkpoint
    at startup while the survivors re-commit their live state and never
    touch orbax, so the joiner's barrier waits for peers that never
    arrive — and a fallback scan that walks a different number of
    candidates on one host leaves its collective sequence offset from
    its peers'. Restore is read-only, so the barrier guards nothing;
    pod-wide resume agreement is the KV-store consensus vote (timed,
    and it NAMES the absent process). Naming this process as the only
    active one is orbax's own way to say so. Saves keep their sync: the
    pre-finalize barrier is what stops the primary from renaming the
    tmp directory while peers are still writing."""
    import jax

    me = jax.process_index()
    opts = ocp.options.MultiprocessingOptions(primary_host=me,
                                              active_processes={me})
    return ocp.Checkpointer(
        _pytree_handler(multiprocessing_options=opts),
        multiprocessing_options=opts)


def _host_template(target):
    """A host-numpy zeros pytree with ``target``'s structure: what
    orbax needs from ``item`` is the tree structure (optimizer
    namedtuples survive the round-trip) and per-leaf dtypes/shapes —
    not the values. Building zeros instead of ``jax.device_get(target)``
    skips a full state materialization per restore and works when the
    live state is a non-addressable pod-sharded tree (ISSUE 8), where
    ``device_get`` raises."""
    import jax
    import numpy as np

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return np.zeros(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(leaf, target)


def load_checkpoint(path, target=None, verify=True):
    """Restore a state pytree; ``target`` gives structure/dtypes.

    Arrays come back as host numpy; callers ``device_put`` them with
    their own shardings (trainers re-shard on resume). This keeps
    restore layout-agnostic — a checkpoint written on one mesh shape
    loads on another.

    ``verify`` is two-layered: the sidecar's raw-file digests are
    checked with plain Python reads BEFORE orbax deserializes anything
    (corrupt compressed chunks fed to a native decoder are a heap
    hazard, not just a wrong answer), then the per-leaf checksums are
    replayed against the restored arrays. Either mismatch raises
    ``CheckpointIntegrityError``; checkpoints saved without checksums
    restore unverified, as before.
    """
    import jax

    integrity = read_integrity_sidecar(path) if verify else None
    if verify:
        from imaginaire_tpu.resilience.integrity import verify_files

        verify_files(os.path.abspath(path),
                     (integrity or {}).get("files"), context=str(path))
    with telemetry.span("ckpt_load"), _restore_checkpointer() as ckpt:
        if target is not None:
            # force host-numpy restore here too (ISSUE 11): without
            # restore args orbax replays the SAVED shardings from the
            # sharding file — fine when the topology matches, a
            # ``ValueError: sharding ... Got None`` when it does not
            # (an elastic pod restoring a checkpoint written by a
            # world whose devices no longer exist). The item keeps the
            # tree structure (optimizer namedtuples) and true shapes.
            import numpy as np

            item = _host_template(target)
            restore_args = jax.tree_util.tree_map(
                lambda x: (ocp.RestoreArgs(restore_type=np.ndarray)
                           if hasattr(x, "shape") else ocp.RestoreArgs()),
                item)
            payload = ckpt.restore(os.path.abspath(path), item=item,
                                   restore_args=restore_args)

            def _item_shape(v, t):
                # scalar zarr arrays come back shape-(1,) on the numpy
                # restore path; the template remembers the true shape
                if hasattr(t, "shape") and hasattr(v, "shape") \
                        and tuple(v.shape) != tuple(t.shape):
                    return np.asarray(v).reshape(tuple(t.shape))
                return v

            payload = jax.tree_util.tree_map(_item_shape, payload, item)
        else:
            # no target: force every array leaf to restore as host
            # numpy (ISSUE 8). Without restore args orbax replays the
            # SAVED shardings — a checkpoint written by an N-process
            # pod then refuses to restore in any other topology (the
            # mesh in the sharding file names devices this process
            # does not have). numpy restore keeps the documented
            # contract: restores are layout-agnostic, callers commit
            # under their own shardings.
            import numpy as np

            meta = ckpt.metadata(os.path.abspath(path)).item_metadata.tree
            restore_args = jax.tree_util.tree_map(
                lambda m: (ocp.RestoreArgs(restore_type=np.ndarray)
                           if hasattr(m, "shape") else ocp.RestoreArgs()),
                meta)
            payload = ckpt.restore(os.path.abspath(path),
                                   restore_args=restore_args)

            def _true_shape(v, m):
                # orbax hands scalar zarr arrays back as shape (1,)
                # ndarrays on the numpy restore path; the metadata
                # remembers the saved shape
                if hasattr(m, "shape") and hasattr(v, "shape") \
                        and tuple(v.shape) != tuple(m.shape):
                    return np.asarray(v).reshape(tuple(m.shape))
                return v

            payload = jax.tree_util.tree_map(_true_shape, payload, meta)
    if verify:
        from imaginaire_tpu.resilience.integrity import verify_tree

        verify_tree(payload, integrity, context=str(path))
        tm = telemetry.get()
        if tm.enabled:
            tm.meta("ckpt/verified", checkpoint=str(path),
                    verified=integrity is not None,
                    n_leaves=(integrity or {}).get("n_leaves"))
    return payload


def load_latest_verified(logdir, target=None, verify=True):
    """The resume path with last-good fallback: restore the pointed
    checkpoint, quarantining any candidate that is corrupt / truncated
    / unrestorable and falling back to the next-newest until one
    verifies.

    Returns ``(payload, path, fallbacks)`` — ``payload`` None when the
    logdir has no pointer (fresh run). Raises when a pointer exists but
    EVERY candidate failed: resuming from scratch over a logdir full of
    corrupt checkpoints must be an explicit operator decision, not a
    silent restart."""
    from imaginaire_tpu.resilience.integrity import (
        CheckpointIntegrityError,
        quarantine_checkpoint,
    )

    pointer = os.path.join(logdir, _POINTER)
    if not os.path.exists(pointer):
        return None, None, 0
    try:
        with open(pointer) as f:
            pointed_name = f.read().strip()
    except OSError:
        pointed_name = ""
    pointed = (os.path.abspath(os.path.join(logdir, pointed_name))
               if pointed_name else None)
    candidates = []
    if pointed and os.path.exists(pointed):
        candidates.append(pointed)
    for _, _, path in reversed(scan_checkpoints(logdir)):
        if os.path.abspath(path) != pointed:
            candidates.append(os.path.abspath(path))
    if not candidates:
        import logging

        logging.getLogger(__name__).warning(
            "latest_checkpoint.txt names %r but no checkpoint exists in "
            "%s", pointed_name, logdir)
        return None, None, 0
    tm = telemetry.get()
    fallbacks = 0
    errors = []
    for cand in candidates:
        try:
            payload = load_checkpoint(cand, target=target, verify=verify)
        except CheckpointIntegrityError as e:
            errors.append(f"{cand}: {e}")
            quarantine_checkpoint(cand, reason="integrity mismatch")
            fallbacks += 1
            _note_fallback(tm, cand, fallbacks, str(e))
            continue
        except (ImportError, AttributeError, TypeError, NameError):
            # a fault of the program (a moved import, a changed call
            # signature), not evidence about THIS checkpoint's bytes:
            # quarantining on it would rename every healthy candidate
            # `.corrupt` in turn. Leave the checkpoints where they are.
            raise
        except Exception as e:  # noqa: BLE001 — truncated/unrestorable
            if type(e).__name__ in ("XlaRuntimeError",
                                    "JaxRuntimeError"):
                # runtime/collective infrastructure failure, no more
                # telling about the bytes than a program fault (ISSUE
                # 13: seen as gloo context timeouts when a resize left
                # the pod's collective layer wedged). Fail the restore
                # loudly and leave the checkpoints alone.
                raise
            errors.append(f"{cand}: {type(e).__name__}: {e}")
            quarantine_checkpoint(cand,
                                  reason=f"restore failed: "
                                         f"{type(e).__name__}")
            fallbacks += 1
            _note_fallback(tm, cand, fallbacks, str(e))
            continue
        if fallbacks and tm.enabled:
            tm.counter("resilience/ckpt_fallbacks", fallbacks)
        return payload, cand, fallbacks
    raise RuntimeError(
        f"no verifiable checkpoint in {logdir}: every candidate failed "
        f"to restore ({len(errors)} quarantined). Delete or repair the "
        f"logdir to restart from scratch. Errors: "
        + " | ".join(errors[:3]))


def _note_fallback(tm, path, fallbacks, error):
    import logging

    if tm.enabled:
        tm.meta("ckpt/fallback", skipped=str(path), fallbacks=fallbacks,
                error=error[:500])
    logging.getLogger(__name__).error(
        "checkpoint %s failed to restore (%s); falling back to the "
        "next-newest checkpoint", path, error[:500])
