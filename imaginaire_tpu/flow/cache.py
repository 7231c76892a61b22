"""Teacher-output amortization for the frozen FlowNet2 ground-truth
flow supervision (ISSUE 4 tentpole).

The vid2vid FlowLoss teacher only ever sees *real* frames — its
``(flow, conf)`` output is a pure function of the data batch — yet the
reference (and our in-graph port) recomputes it inside the
differentiated step program, identically every epoch (about a quarter
of the gen step on an earlier installation; not measured on this
one). This module moves the teacher OFF
the step's critical path, in two layers:

1. **Off-step execution** (``TeacherFlowCache.attach``): the teacher
   runs as its own jitted, stop-gradiented program in whatever host
   thread prepares the batch — under the device-prefetch pipeline
   that is the producer thread, overlapped with the running step — and
   its outputs ride the batch as plain numeric ``flow_gt``/``conf_gt``
   entries the step programs consume as inputs. The compiled D/G step
   programs then carry no FlowNet2 parameters at all (smaller
   executables; the 162M-param cascade is what pushes 512x1024 vid2vid
   programs over the remote-compile size cap).

2. **On-disk content-addressed cache** (``FlowCacheStore``): teacher
   outputs are persisted keyed by (dataset identity, frame-pair stems,
   canonical resolution, resize chain, teacher version). Flow is
   computed at the *canonical* resolution (after the deterministic
   resize ops, before crop/flip) and the random crop/hflip
   augmentations are applied to the cached flow equivariantly — slice
   for crop, mirror + negate-u for hflip — so a sample hits the cache
   regardless of its augmentation draw: epoch >= 2 (or a
   ``scripts/precompute_flow.py`` warm) pays ~zero teacher cost.
   Batches without dataset metadata (synthetic benches) fall back to a
   whole-batch content hash.

Config group ``flow_cache`` (see config.py): ``enabled``, ``mode``
(auto | producer | disk), ``dir``, ``store_dtype``.

Telemetry: ``flow_cache/hit_rate``, ``flow_cache/compute_ms``,
``flow_cache/pairs`` counters land in the run JSONL through the
existing sinks; ``drain_stats()`` feeds the trainer meters like the
device prefetcher's.
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
import time

import numpy as np

from imaginaire_tpu.config import AttrDict, cfg_get

logger = logging.getLogger(__name__)

# Bump when the teacher definition changes incompatibly (cascade
# architecture, confidence threshold); stale shards then simply miss.
TEACHER_VERSION = "flownet2-v1"


def flow_cache_settings(cfg):
    """Parse the ``flow_cache`` config group (missing -> disabled)."""
    fcfg = cfg_get(cfg or {}, "flow_cache", None) or {}
    return AttrDict(
        enabled=bool(cfg_get(fcfg, "enabled", False)),
        mode=str(cfg_get(fcfg, "mode", "auto")),
        dir=cfg_get(fcfg, "dir", None),
        store_dtype=str(cfg_get(fcfg, "store_dtype", "float16")),
    )


def resolve_cache_dir(cfg):
    """The on-disk cache directory: ``flow_cache.dir`` > ``<logdir>/
    flow_cache`` > None (mode 'auto' then degrades to producer-only)."""
    settings = flow_cache_settings(cfg)
    if settings.dir:
        return str(settings.dir)
    logdir = cfg_get(cfg or {}, "logdir", None)
    if logdir:
        return os.path.join(str(logdir), "flow_cache")
    return None


def teacher_id(weights_path=None):
    """Identity of the teacher weights baked into every cache key: a
    converted checkpoint is identified by (name, size, mtime); absent
    weights (allow_random_init, tests) get a per-process tag so a
    random teacher never poisons a shared cache."""
    if weights_path and os.path.exists(weights_path):
        st = os.stat(weights_path)
        return (f"{TEACHER_VERSION}:{os.path.basename(weights_path)}"
                f":{st.st_size}:{int(st.st_mtime)}")
    return f"{TEACHER_VERSION}:random-init:{os.getpid()}"


def pair_key(dataset_name, root_idx, seq, stem_a, stem_b, canonical_hw,
             teacher):
    """Content-addressed key for one (frame_a -> frame_b) teacher
    evaluation at canonical resolution. ``stem_a`` is the *target*
    frame (t), ``stem_b`` the previous frame (t-1) — matching
    ``FlowLoss._gt(tgt_image, real_prev_image)`` argument order."""
    payload = "|".join([
        str(dataset_name), str(root_idx), str(seq), str(stem_a),
        str(stem_b), f"{int(canonical_hw[0])}x{int(canonical_hw[1])}",
        str(teacher),
    ])
    return hashlib.sha1(payload.encode()).hexdigest()


def content_key(images, teacher):
    """Whole-batch fallback key for batches without dataset metadata
    (synthetic bench batches): hash of the raw image bytes + shape."""
    arr = np.ascontiguousarray(np.asarray(images))
    digest = hashlib.sha1()
    digest.update(str(arr.shape).encode())
    digest.update(str(arr.dtype).encode())
    digest.update(arr.tobytes())
    digest.update(str(teacher).encode())
    return digest.hexdigest()


def transform_flow(flow, conf, record):
    """Apply a sample's spatial augmentation to canonical-resolution
    ``(flow, conf)`` equivariantly.

    flow: (..., H, W, 2) in pixel units (u = x, v = y); conf: (..., H,
    W, 1). Crop is a pure slice (pixel units are crop-invariant);
    horizontal flip mirrors the width axis and negates u (a rightward
    motion in the source is leftward in the mirrored frame); conf
    mirrors without negation.
    """
    crop = record.get("crop")
    if crop is not None:
        top, left, ch, cw = crop
        flow = flow[..., top:top + ch, left:left + cw, :]
        conf = conf[..., top:top + ch, left:left + cw, :]
    if record.get("hflip"):
        flow = flow[..., ::-1, :] * np.asarray([-1.0, 1.0], flow.dtype)
        conf = conf[..., ::-1, :]
    return np.ascontiguousarray(flow), np.ascontiguousarray(conf)


class FlowCacheStore:
    """Content-addressed (flow, conf) shards on disk.

    One ``.npz`` per key under ``<root>/<key[:2]>/<key>.npz`` with flow
    stored at ``store_dtype`` (float16 default — |flow| <= ~40 px, so
    the quantization error is < 0.05 px) and conf as uint8 (it is a
    binary mask). Writes are atomic (tmp + rename) so concurrent
    producer threads / precompute workers never read torn shards.
    """

    def __init__(self, root, store_dtype="float16"):
        self.root = str(root)
        self.store_dtype = np.dtype(store_dtype)
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.corrupt_shards = 0

    def path(self, key):
        return os.path.join(self.root, key[:2], key + ".npz")

    def has(self, key):
        return os.path.exists(self.path(key))

    def _read(self, path):
        """One shard read — the retried unit (transient OSErrors recover
        on the next attempt) and the chaos harness's flow-store site."""
        from imaginaire_tpu.resilience import chaos

        chaos.get().maybe_io_error("flow_store")
        with np.load(path) as npz:
            return (npz["flow"].astype(np.float32),
                    npz["conf"].astype(np.float32))

    def _quarantine(self, path, error):
        """A corrupt shard degrades to a miss ONCE: renamed to
        ``*.corrupt`` so it is never re-read (and re-missed) every
        epoch, counted in ``flow_cache/corrupt_shards``."""
        from imaginaire_tpu import telemetry

        with self._lock:
            self.corrupt_shards += 1
            count = self.corrupt_shards
        try:
            os.replace(path, path + ".corrupt")
        except FileNotFoundError:
            # another host of a shared store already quarantined it
            pass
        except OSError:
            try:
                os.remove(path)
            except OSError:
                pass
        logger.warning("flow cache: quarantined corrupt shard %s (%s)",
                       path, error)
        tm = telemetry.get()
        if tm.enabled:
            tm.counter("flow_cache/corrupt_shards", count)
            tm.meta("flow_cache/corrupt_shard", shard=str(path),
                    error=str(error)[:200])

    def get(self, key):
        """(flow float32, conf float32) or None. Transient IO retries
        with bounded backoff (resilience/retry.py); a shard that still
        fails — or fails to parse — is quarantined and degrades to a
        miss (the teacher simply recomputes)."""
        import zipfile

        from imaginaire_tpu.resilience import retry_call

        path = self.path(key)
        if not os.path.exists(path):
            with self._lock:
                self.misses += 1
            return None
        try:
            flow, conf = retry_call(self._read, path, label="flow_store")
        except (OSError, KeyError, ValueError, EOFError,
                zipfile.BadZipFile) as e:
            self._quarantine(path, e)
            with self._lock:
                self.misses += 1
            return None
        with self._lock:
            self.hits += 1
        return flow, conf

    def put(self, key, flow, conf):
        from imaginaire_tpu.resilience import retry_call

        path = self.path(key)
        if os.path.exists(path):
            # multi-writer shared directory (ISSUE 8): another host's
            # producer already published this shard — content-addressed
            # keys make its bytes equivalent, so skip the redundant
            # write (and the rename-over-live-file hazard on
            # non-POSIX-atomic shared filesystems)
            return
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # tmp name unique across THREADS and HOSTS: pids collide between
        # machines sharing a filesystem, so a random token joins the
        # pid/tid pair (np.savez appends '.npz' unless the name already
        # ends with it)
        import uuid

        tmp = (f"{path}.{os.getpid()}.{threading.get_ident()}."
               f"{uuid.uuid4().hex[:8]}.tmp.npz")

        def _write():
            np.savez(tmp, flow=np.asarray(flow).astype(self.store_dtype),
                     conf=np.asarray(conf).astype(np.uint8))
            os.replace(tmp, path)

        try:
            retry_call(_write, label="flow_store_write")
        except OSError as e:
            logger.warning("flow cache write failed for %s: %s", path, e)
            try:
                os.remove(tmp)
            except OSError:
                pass

    def count_miss(self, n=1):
        with self._lock:
            self.misses += n

    def stats(self):
        with self._lock:
            total = self.hits + self.misses
            return {"hits": self.hits, "misses": self.misses,
                    "corrupt_shards": self.corrupt_shards,
                    "hit_rate": (self.hits / total) if total else 0.0}


class TeacherFlowCache:
    """Producer-side facade the trainer owns: runs the frozen teacher
    off the step path and attaches ``flow_gt``/``conf_gt`` to batches.

    Args:
        flow_net_wrapper: the ``flow.FlowNet`` frozen-teacher wrapper
            (params already initialized).
        settings: parsed ``flow_cache`` config group.
        cache_dir: resolved on-disk cache directory (None degrades
            'auto' to producer-only).
    """

    def __init__(self, flow_net_wrapper, settings=None, cache_dir=None):
        self.wrapper = flow_net_wrapper
        self.settings = settings or flow_cache_settings({})
        self.requested_mode = str(self.settings.mode)
        mode = str(self.settings.mode)
        if mode == "auto":
            mode = "disk" if cache_dir else "producer"
        if mode == "disk" and not cache_dir:
            logger.warning("flow_cache.mode=disk but no cache dir "
                           "resolves (set flow_cache.dir or logdir); "
                           "falling back to producer mode")
            mode = "producer"
        self.mode = mode
        self.store = (FlowCacheStore(cache_dir, self.settings.store_dtype)
                      if mode == "disk" else None)
        self.teacher = teacher_id(getattr(flow_net_wrapper, "weights_path",
                                          None))
        self._stats_lock = threading.Lock()
        self._stats = {}
        # per-pair hit/miss accounting across BOTH halves of the disk
        # path (dataset-side loads count as hits, producer recomputes as
        # misses) — the number flow_cache/hit_rate reports
        self.pair_hits = 0
        self.pair_misses = 0

    def hit_rate(self):
        total = self.pair_hits + self.pair_misses
        return (self.pair_hits / total) if total else 0.0

    # ------------------------------------------------------ observability

    def _record_stat(self, name, value):
        with self._stats_lock:
            self._stats.setdefault(name, []).append(float(value))

    def drain_stats(self):
        """Pop accumulated {meter_name: [values]} — plain host floats
        (the DevicePrefetcher ``drain_stats`` contract)."""
        with self._stats_lock:
            out, self._stats = self._stats, {}
        return out

    # ----------------------------------------------------------- teacher

    def _teacher_pairs(self, im_a, im_b):
        """Run the jitted teacher on stacked frame pairs; returns host
        float32 (flow, conf). ``im_a`` is the target frame, ``im_b``
        the previous frame (the FlowLoss._gt order)."""
        flow, conf = self.wrapper._jit_flow(
            self.wrapper.params, np.asarray(im_a, np.float32),
            np.asarray(im_b, np.float32))
        return (np.asarray(flow, np.float32),
                np.asarray(conf, np.float32))

    # ------------------------------------------------------------ attach

    def attach(self, batch):
        """Attach ``flow_gt`` (B, T-1, H, W, 2) and ``conf_gt``
        (B, T-1, H, W, 1) to a video batch, consuming any per-sample
        ``_flow_cache`` payloads the dataset prepared. ``flow_gt[:, t-1]``
        supervises frame ``t`` against frame ``t-1``. Non-video batches
        (or T < 2) pass through untouched."""
        if not isinstance(batch, dict):
            return batch
        images = batch.get("images")
        metas = batch.pop("_flow_cache", None)
        if images is None or getattr(images, "ndim", 0) != 5 \
                or images.shape[1] < 2 or "flow_gt" in batch:
            return batch
        from imaginaire_tpu import telemetry

        t0 = time.perf_counter()
        with telemetry.span("flow_teacher"):
            if isinstance(metas, (list, tuple)) \
                    and len(metas) == images.shape[0] \
                    and all(isinstance(m, dict) for m in metas):
                flow, conf = self._attach_from_meta(metas, images)
            else:
                flow, conf = self._attach_from_content(images)
        compute_ms = (time.perf_counter() - t0) * 1e3
        batch["flow_gt"] = flow
        batch["conf_gt"] = conf
        self._record_stat("flow_cache/compute_ms", compute_ms)
        n_pairs = images.shape[0] * (images.shape[1] - 1)
        self._record_stat("flow_cache/pairs", n_pairs)
        tm = telemetry.get()
        if tm.enabled:
            tm.counter("flow_cache/compute_ms", compute_ms)
            if self.mode == "disk":
                tm.counter("flow_cache/hit_rate", self.hit_rate())
        if self.mode == "disk":
            self._record_stat("flow_cache/hit_rate", self.hit_rate())
        return batch

    def _attach_from_content(self, images):
        """No dataset metadata: compute on the augmented frames
        directly (identical inputs to the in-graph teacher), with a
        whole-batch content-hash disk key so static batches (benches,
        deterministic-augmentation epochs) still hit."""
        images = np.asarray(images)
        b, t = images.shape[:2]
        n_pairs = b * (t - 1)
        key = None
        # whole-batch content keys only persist under an EXPLICIT disk
        # mode: randomly-augmented batches without dataset metadata
        # would otherwise write a never-hit shard per batch forever
        # (mode 'auto' still serves the canonical per-sample path)
        if self.store is not None and self.requested_mode == "disk":
            key = content_key(images, self.teacher)
            cached = self.store.get(key)
            if cached is not None:
                self.pair_hits += n_pairs
                return cached
        self.pair_misses += n_pairs
        im_a = images[:, 1:].reshape((-1,) + images.shape[2:])
        im_b = images[:, :-1].reshape((-1,) + images.shape[2:])
        flow, conf = self._teacher_pairs(im_a, im_b)
        flow = flow.reshape((b, t - 1) + flow.shape[1:])
        conf = conf.reshape((b, t - 1) + conf.shape[1:])
        if key is not None:
            self.store.put(key, flow, conf)
        return flow, conf

    def _attach_from_meta(self, metas, images):
        """Canonical-resolution path: per-sample payloads carry either
        disk-cached canonical (flow, conf) (dataset-side hit) or the
        canonical source frames (miss). Misses are batched per
        canonical shape, computed once, written back to the store, and
        every sample's canonical flow is transformed equivariantly to
        its augmentation draw."""
        images = np.asarray(images)
        b, t = images.shape[:2]
        hw = images.shape[2:4]
        per_sample = [None] * b
        pending = {}  # canonical shape -> [(sample_idx, meta)]
        for i, meta in enumerate(metas):
            if meta.get("flow") is not None:
                self.pair_hits += t - 1
                per_sample[i] = (meta["flow"], meta["conf"])
            elif meta.get("src") is not None:
                self.pair_misses += t - 1
                src = np.asarray(meta["src"], np.float32)
                pending.setdefault(src.shape, []).append((i, meta))
            else:
                # unsupported augmentation for the canonical path:
                # compute on this sample's augmented frames directly
                self.pair_misses += t - 1
                flow, conf = self._teacher_pairs(images[i, 1:],
                                                 images[i, :-1])
                per_sample[i] = (flow, conf)
        for _, group in pending.items():
            srcs = np.stack([np.asarray(m["src"], np.float32)
                             for _, m in group])  # (G, T, Hc, Wc, 3)
            g, tt = srcs.shape[:2]
            im_a = srcs[:, 1:].reshape((-1,) + srcs.shape[2:])
            im_b = srcs[:, :-1].reshape((-1,) + srcs.shape[2:])
            flow, conf = self._teacher_pairs(im_a, im_b)
            flow = flow.reshape((g, tt - 1) + flow.shape[1:])
            conf = conf.reshape((g, tt - 1) + conf.shape[1:])
            for j, (i, meta) in enumerate(group):
                if self.store is not None:
                    keys = meta.get("keys") or []
                    for p, key in enumerate(keys):
                        self.store.put(key, flow[j, p], conf[j, p])
                per_sample[i] = (flow[j], conf[j])
        flows, confs = [], []
        for i, meta in enumerate(metas):
            flow_i, conf_i = per_sample[i]
            record = meta.get("record") or {}
            if meta.get("flow") is not None or meta.get("src") is not None:
                # canonical-resolution entries carry the augmentation
                # still to apply (hit or freshly computed alike)
                flow_i, conf_i = transform_flow(flow_i, conf_i, record)
            if flow_i.shape[1:3] != tuple(hw):
                # transform/record mismatch — never train on misaligned
                # supervision; recompute from the augmented frames
                logger.warning(
                    "flow cache: transformed flow %s does not match the "
                    "augmented batch %s; recomputing sample %d in-place",
                    flow_i.shape, hw, i)
                flow_i, conf_i = self._teacher_pairs(images[i, 1:],
                                                     images[i, :-1])
            flows.append(flow_i)
            confs.append(conf_i)
        return np.stack(flows), np.stack(confs)


class DatasetFlowCacheHook:
    """Dataset-side half of the disk path, owned by video datasets.

    On every training item it builds the per-sample ``_flow_cache``
    payload: the augmentation record, the per-pair cache keys, and —
    on a store hit — the canonical ``(flow, conf)`` loaded in the
    loader worker thread (parallel IO, zero teacher cost), or — on a
    miss — the canonical source frames for the producer-thread teacher.
    The payload rides the batch as a host-side ('_'-prefixed) entry and
    is consumed by ``TeacherFlowCache.attach``.
    """

    def __init__(self, cfg, dataset_name, image_type, normalize,
                 weights_path=None):
        from imaginaire_tpu.flow.flow_net import DEFAULT_WEIGHTS

        # mirror the FlowNet wrapper's default so dataset-side keys
        # match the producer-side writes
        weights_path = weights_path or DEFAULT_WEIGHTS
        self.settings = flow_cache_settings(cfg)
        cache_dir = resolve_cache_dir(cfg)
        self.active = (self.settings.enabled
                       and self.settings.mode in ("auto", "disk")
                       and cache_dir is not None)
        self.store = (FlowCacheStore(cache_dir, self.settings.store_dtype)
                      if self.active else None)
        self.image_type = image_type
        self.normalize = bool(normalize)
        self.dataset_name = dataset_name
        self.teacher = teacher_id(weights_path)

    def _canonical_src(self, canonical_frames):
        """Stack captured canonical frames to (T, Hc, Wc, 3) float32 in
        the teacher's input range (mirrors process_item's normalize)."""
        frames = []
        for f in canonical_frames:
            arr = np.asarray(f)
            if arr.ndim == 2:
                arr = arr[:, :, None]
            was_uint8 = arr.dtype == np.uint8
            arr = arr.astype(np.float32)
            if was_uint8:
                arr = arr / 255.0
            if self.normalize:
                arr = arr * 2.0 - 1.0
            frames.append(arr)
        return np.stack(frames, axis=0)

    def attach_item(self, out, root_idx, seq, stems, record, canonical):
        """Attach the per-item payload to dataset item ``out``."""
        if not self.active or len(stems) < 2:
            return out
        if not record or not record.get("canonical_ok") \
                or canonical is None:
            out["_flow_cache"] = {"record": dict(record or {})}
            return out
        hw = record["canonical_hw"]
        keys = [pair_key(self.dataset_name, root_idx, seq, stems[p + 1],
                         stems[p], hw, self.teacher)
                for p in range(len(stems) - 1)]
        cached = [self.store.get(k) if self.store.has(k) else None
                  for k in keys]
        payload = {"record": dict(record), "keys": keys}
        if all(c is not None for c in cached):
            payload["flow"] = np.stack([c[0] for c in cached])
            payload["conf"] = np.stack([c[1] for c in cached])
        else:
            # some pairs hit, some missed: recompute the whole window
            # (the producer batches per-sample anyway; partial reuse
            # would complicate the payload for a one-epoch transient)
            payload["src"] = self._canonical_src(canonical)
        out["_flow_cache"] = payload
        return out
