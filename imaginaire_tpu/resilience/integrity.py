"""Checkpoint integrity: per-leaf checksums, restore-time verification,
and quarantine of corrupt checkpoints (ISSUE 7).

A preempted/killed run must never come back up on silently-corrupted
state: a half-written array shard restores as garbage that trains for
hours before the loss explodes. ``tree_checksums`` fingerprints every
leaf of the saved payload (crc32 over the raw bytes + shape + dtype);
the record rides the checkpoint's sidecar (``.partition.json`` when a
partition plan is active, ``.integrity.json`` otherwise — see
``utils/checkpoint.py``) and ``verify_tree`` replays it against the
restored arrays. A mismatch raises ``CheckpointIntegrityError``; the
caller quarantines the checkpoint (``quarantine_checkpoint`` renames it
``*.corrupt`` so scans skip it forever) and falls back to the newest
checkpoint that does verify.

Leaf matching is by pytree key path; when the restored structure names
leaves differently (orbax restores namedtuple optimizer states as plain
containers when no target is given), verification falls back to
comparing the multiset of (dtype, shape, crc) records — byte corruption
still cannot hide, only a swap of two bit-identical leaves could.
"""

from __future__ import annotations

import logging
import os
import zlib

import numpy as np

logger = logging.getLogger(__name__)

INTEGRITY_VERSION = 1
# sidecar files that ride a checkpoint directory and must follow it
# through quarantine (and die with it in GC)
SIDECAR_SUFFIXES = (".partition.json", ".integrity.json",
                    ".runstate.json", ".ema_bn.pkl")


def sidecar_files(path):
    """Existing sidecar paths for a checkpoint: the fixed suffixes plus
    the per-host ``.runstate.p<i>.json`` family (ISSUE 8) and its
    epoch-keyed ``.runstate.e<E>.p<i>.json`` variant from resized pods
    (ISSUE 13) — quarantine and GC must move/delete the whole set,
    discovered by glob so a pod of any size is covered. After an
    elastic shrink the family can name MORE processes than the pod now
    has; those orphans still die with the checkpoint in GC, but
    quarantine leaves them in place (see ``orphan_sidecars``)."""
    import glob as _glob

    path = str(path)
    out = [path + s for s in SIDECAR_SUFFIXES
           if os.path.exists(path + s)]
    out.extend(sorted(_glob.glob(_glob.escape(path)
                                 + ".runstate.p*.json")))
    out.extend(sorted(_glob.glob(_glob.escape(path)
                                 + ".runstate.e*.p*.json")))
    return out


def runstate_index(sidecar_path):
    """Process index of a per-host ``.runstate.p<i>.json`` (or
    epoch-keyed ``.runstate.e<E>.p<i>.json``, ISSUE 13) sidecar path,
    or None for every other sidecar kind."""
    import re

    m = re.search(r"\.runstate\.(?:e\d+\.)?p(\d+)\.json$",
                  str(sidecar_path))
    return int(m.group(1)) if m else None


def runstate_epoch(sidecar_path):
    """Membership epoch of an epoch-keyed runstate sidecar; 0 for the
    legacy unkeyed family, None for non-runstate sidecars."""
    import re

    s = str(sidecar_path)
    m = re.search(r"\.runstate\.e(\d+)\.p\d+\.json$", s)
    if m:
        return int(m.group(1))
    if re.search(r"\.runstate(?:\.p\d+)?\.json$", s):
        return 0
    return None


def orphan_sidecars(path, world_size=None):
    """Per-host runstate sidecars whose process index no longer exists
    (``i >= world_size``): an elastic shrink (ISSUE 11) leaves the dead
    hosts' sidecars behind on checkpoints written by the larger world.
    They are harmless — resume never reads them (each live process
    reads its own index, falling back to p0) — so readers warn and
    ignore; only checkpoint GC retires them, together with the
    checkpoint they ride."""
    if world_size is None:
        try:
            from imaginaire_tpu.parallel.mesh import get_world_size

            world_size = get_world_size()
        except Exception:  # noqa: BLE001 — no backend: nothing orphan
            return []
    out = []
    for sidecar in sidecar_files(path):
        idx = runstate_index(sidecar)
        if idx is not None and idx >= int(world_size):
            out.append(sidecar)
    return out


class CheckpointIntegrityError(RuntimeError):
    """A restored checkpoint's bytes do not match its saved checksums."""


def _host_copy(leaf):
    """The leaf's bytes on the host, without leaving them there: jax
    caches a fetched value on the array it was fetched from, and the
    leaves of a live train state stay alive — a checksum pass over a
    zoo-width SPADE state left 6.8 GiB of host copies behind (PR 22,
    TPU v5e). Fetch through a second array over the same device buffers
    instead; the copy dies with it."""
    import jax

    if isinstance(leaf, jax.Array) and not leaf.is_deleted():
        leaf = jax.make_array_from_single_device_arrays(
            leaf.shape, leaf.sharding,
            [s.data for s in leaf.addressable_shards])
    return np.asarray(jax.device_get(leaf))


def _leaf_record(leaf):
    """(record dict, skip reason). Non-addressable / object leaves are
    skipped with a reason instead of forcing a gather — EXCEPT fully
    replicated multi-process arrays (the pod DP steady state, ISSUE 8):
    the local replica IS the global value, so per-leaf checksums keep
    covering pod checkpoints instead of degrading to file digests
    only."""
    if not getattr(leaf, "is_fully_addressable", True):
        if getattr(leaf, "is_fully_replicated", False):
            try:
                arr = np.asarray(leaf.addressable_data(0))
                if arr.dtype == object:
                    return None, "object_dtype"
                # ascontiguousarray promotes 0-d to (1,) — record the
                # promoted shape, matching what the addressable path
                # (and restore-time verification) computes
                arr = np.ascontiguousarray(arr)
                return {
                    "crc": int(zlib.crc32(arr.tobytes())),
                    "shape": [int(s) for s in arr.shape],
                    "dtype": str(arr.dtype),
                }, None
            except Exception:  # noqa: BLE001
                return None, "not_fully_addressable"
        return None, "not_fully_addressable"
    try:
        arr = _host_copy(leaf)
    except Exception:  # noqa: BLE001 — fall back to a plain asarray
        try:
            arr = np.asarray(leaf)
        except Exception:  # noqa: BLE001
            return None, "not_array"
    if arr.dtype == object:
        return None, "object_dtype"
    arr = np.ascontiguousarray(arr)
    return {
        "crc": int(zlib.crc32(arr.tobytes())),
        "shape": [int(s) for s in arr.shape],
        "dtype": str(arr.dtype),
    }, None


def tree_checksums(tree):
    """Per-leaf crc32 record for a state pytree.

    Returns ``{"version", "algo", "leaves": {keypath: record},
    "skipped": {keypath: reason}}``. The whole-tree crc (``tree_crc``,
    order-independent) gives run logs a one-number state identity.
    """
    import jax

    leaves, skipped = {}, {}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        record, reason = _leaf_record(leaf)
        if record is None:
            skipped[key] = reason
        else:
            leaves[key] = record
    tree_crc = 0
    for rec in sorted((r["crc"] for r in leaves.values())):
        tree_crc = zlib.crc32(str(rec).encode(), tree_crc)
    return {"version": INTEGRITY_VERSION, "algo": "crc32",
            "leaves": leaves, "skipped": skipped,
            "tree_crc": int(tree_crc), "n_leaves": len(leaves)}


def verify_tree(tree, integrity, context=""):
    """Raise ``CheckpointIntegrityError`` when ``tree``'s bytes diverge
    from a ``tree_checksums`` record; no-op for None/empty records
    (legacy checkpoints saved before ISSUE 7)."""
    if not integrity or not integrity.get("leaves"):
        return None
    got = tree_checksums(tree)
    want_leaves = integrity["leaves"]
    mismatches = []
    if set(got["leaves"]) == set(want_leaves):
        for key, want in want_leaves.items():
            have = got["leaves"][key]
            for field in ("crc", "shape", "dtype"):
                if have[field] != want[field]:
                    mismatches.append(
                        f"{key}: {field} {want[field]} -> {have[field]}")
                    break
    else:
        # structure renamed (e.g. no-target restore flattens optimizer
        # namedtuples): byte corruption still cannot hide from the
        # (dtype, shape, crc) multiset
        def multiset(leaves):
            return sorted((r["dtype"], tuple(r["shape"]), r["crc"])
                          for r in leaves.values())

        if multiset(got["leaves"]) != multiset(want_leaves):
            want_set = multiset(want_leaves)
            got_set = multiset(got["leaves"])
            missing = [r for r in want_set if r not in got_set]
            mismatches.append(
                f"leaf multiset differs ({len(missing)} saved leaf "
                f"record(s) unmatched, e.g. {missing[:3]})")
    if mismatches:
        raise CheckpointIntegrityError(
            f"checkpoint integrity verification failed"
            f"{' for ' + context if context else ''}: "
            + "; ".join(mismatches[:8])
            + (f" (+{len(mismatches) - 8} more)"
               if len(mismatches) > 8 else ""))
    return got


def file_digests(root):
    """Raw-byte (size, crc32) records for every file under a committed
    checkpoint directory, keyed by relative path.

    This is the FIRST verification layer: restoring a byte-corrupted
    checkpoint is not merely wrong, it is *dangerous* — the serializer
    decodes compressed chunks, and feeding corrupt bytes to a native
    decoder can corrupt the heap before any leaf checksum gets a chance
    to run (observed: NaN params + delayed SIGSEGV after restoring a
    chaos-corrupted checkpoint). ``verify_files`` replays these records
    with plain Python reads, so corruption is caught before the
    deserializer touches a single byte."""
    out = {}
    root = str(root)
    for dirpath, _, files in os.walk(root):
        for name in files:
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, root)
            crc, size = 0, 0
            with open(path, "rb") as f:
                while True:
                    chunk = f.read(1 << 20)
                    if not chunk:
                        break
                    crc = zlib.crc32(chunk, crc)
                    size += len(chunk)
            out[rel] = {"size": size, "crc": int(crc)}
    return out


def verify_files(root, records, context=""):
    """Raise ``CheckpointIntegrityError`` when the on-disk files diverge
    from a ``file_digests`` record; no-op for None/empty (legacy)."""
    if not records:
        return
    mismatches = []
    for rel, want in records.items():
        path = os.path.join(str(root), rel)
        if not os.path.isfile(path):
            mismatches.append(f"{rel}: missing")
            continue
        crc, size = 0, 0
        try:
            with open(path, "rb") as f:
                while True:
                    chunk = f.read(1 << 20)
                    if not chunk:
                        break
                    crc = zlib.crc32(chunk, crc)
                    size += len(chunk)
        except OSError as e:
            mismatches.append(f"{rel}: unreadable ({e})")
            continue
        if size != want.get("size"):
            mismatches.append(
                f"{rel}: size {want.get('size')} -> {size}")
        elif int(crc) != want.get("crc"):
            mismatches.append(
                f"{rel}: file crc {want.get('crc')} -> {int(crc)}")
    if mismatches:
        raise CheckpointIntegrityError(
            f"checkpoint file verification failed"
            f"{' for ' + context if context else ''} (refusing to "
            f"deserialize corrupt bytes): " + "; ".join(mismatches[:8])
            + (f" (+{len(mismatches) - 8} more)"
               if len(mismatches) > 8 else ""))


def quarantine_checkpoint(path, reason="corrupt"):
    """Rename a corrupt checkpoint (and its sidecars) out of the resume
    scan: ``<ckpt>`` -> ``<ckpt>.corrupt`` (numbered on collision).
    Returns the quarantine path, or None when nothing was moved.

    Multi-process (ISSUE 8): only process 0 renames — on a shared
    checkpoint directory a non-master rename would yank the files out
    from under peers mid-verification; the master's quarantine is
    cluster-wide truth and the resume consensus handles any host that
    raced past it."""
    from imaginaire_tpu import telemetry
    from imaginaire_tpu.parallel.mesh import is_master

    path = str(path)
    if not os.path.exists(path):
        return None
    if not is_master():
        logger.error("corrupt checkpoint %s detected on process >0 "
                     "(%s); master owns the quarantine rename", path,
                     reason)
        tm = telemetry.get()
        if tm.enabled:
            tm.meta("ckpt/quarantine_deferred", checkpoint=path,
                    reason=str(reason))
        return None
    target = path + ".corrupt"
    n = 0
    while os.path.exists(target):
        n += 1
        target = f"{path}.corrupt{n}"
    suffix = target[len(path):]
    try:
        os.replace(path, target)
    except OSError as e:
        logger.error("failed to quarantine corrupt checkpoint %s: %s",
                     path, e)
        return None
    orphans = set(orphan_sidecars(path))
    for sidecar in sidecar_files(path):
        if sidecar in orphans:
            # elastic shrink leftovers (ISSUE 11): a sidecar for a
            # process index the pod no longer has must NOT follow the
            # rename — the numbered-collision suffix of a later
            # quarantine at the same path would disagree with where its
            # checkpoint went. Resume ignores it; GC retires it.
            logger.warning(
                "quarantine: leaving orphan runstate sidecar %s in "
                "place (process index >= current world size — an "
                "elastic shrink left it behind)", sidecar)
            continue
        try:
            os.replace(sidecar, path + suffix + sidecar[len(path):])
        except OSError:  # the data dir moved; sidecars best-effort
            pass
    tm = telemetry.get()
    if tm.enabled:
        tm.meta("ckpt/quarantined", checkpoint=path, quarantine=target,
                reason=str(reason))
        tm.counter("resilience/ckpt_quarantined", 1)
    logger.error("quarantined corrupt checkpoint %s -> %s (%s)", path,
                 target, reason)
    return target
