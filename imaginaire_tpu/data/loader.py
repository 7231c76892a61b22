"""Batching + per-host sharding loader
(ref: imaginaire/utils/dataset.py:24-83).

Replaces DataLoader + DistributedSampler: each JAX process takes the
index slice ``process_index::process_count`` of the shuffled epoch
(ref sharding: utils/dataset.py:46-50), batches on the host, and yields
dicts of stacked NHWC arrays. ``set_epoch`` reseeds the shuffle like
``DistributedSampler.set_epoch`` (ref: train.py:70).

Elastic pods (ISSUE 11) add a second split mode: with
``global_batch_size`` set, the loader fixes the GLOBAL batch and splits
each global batch block-contiguously — host ``i`` takes rows
``[i*share, (i+1)*share)`` of every batch, and the per-host batch size
is derived from the LIVE world size at iteration time. The strided
split permutes the sample -> mesh-position assignment whenever the
world size changes (different hosts, different rows — a float reduction
over a different operand order is not bit-stable); the block split
keeps global batch ``k`` == ``order[k*G:(k+1)*G]`` in mesh-device order
for ANY world size, which is what makes a 3->2->3 resize bit-exact
against the never-resized run.
"""

from __future__ import annotations

import logging

import numpy as np

from imaginaire_tpu import telemetry
from imaginaire_tpu.config import cfg_get
from imaginaire_tpu.parallel.mesh import get_rank, get_world_size
from imaginaire_tpu.registry import resolve

logger = logging.getLogger(__name__)


class DataLoader:
    def __init__(self, dataset, batch_size, shuffle=True, seed=0,
                 drop_last=True, num_workers=0, prefetch_batches=2,
                 shard_by_process=True, global_batch_size=None):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self.drop_last = drop_last
        self.num_workers = num_workers
        self.prefetch_batches = max(prefetch_batches, 1)
        # False = every process sees every item, in order — required when
        # the items are sequential frames of one pinned video sequence
        # (the video eval harness shards by *sequence* instead)
        self.shard_by_process = shard_by_process
        # one-shot batch skip for mid-epoch resume (resilience/, ISSUE
        # 7): the next __iter__ drops the first N index-batches of the
        # (deterministically seeded) epoch order without loading them
        self._skip_batches = 0
        # elastic (ISSUE 11): a set global_batch_size pins the GLOBAL
        # batch and switches to the block-contiguous split; the
        # per-host batch size becomes global // live-world, re-derived
        # at every access so the SAME loader object keeps yielding
        # correctly after an in-process mesh resize
        self.global_batch_size = (int(global_batch_size)
                                  if global_batch_size else None)
        self._warned_indivisible = None

    @property
    def batch_size(self):
        if self.global_batch_size:
            world = get_world_size() if self.shard_by_process else 1
            share, rem = divmod(self.global_batch_size, max(world, 1))
            if rem and self._warned_indivisible != world:
                self._warned_indivisible = world
                logger.warning(
                    "global_batch_size %d is not divisible by world "
                    "size %d — flooring the per-host batch to %d "
                    "(global batch shrinks to %d; cross-world-size "
                    "bit-exactness is lost at this world)",
                    self.global_batch_size, world, max(share, 1),
                    max(share, 1) * world)
            return max(share, 1)
        return self._batch_size

    @batch_size.setter
    def batch_size(self, value):
        self._batch_size = value

    def set_epoch(self, epoch):
        self.epoch = epoch

    def fast_forward(self, n_batches):
        """Skip the first ``n_batches`` of the NEXT epoch pass (one-shot).

        The epoch order is a pure function of (seed, epoch), so the
        skipped prefix is exactly the batches a killed run already
        consumed — no item is loaded or decoded for them."""
        self._skip_batches = max(int(n_batches), 0)

    def _consume_skip(self, n_batches_total):
        skip = min(self._skip_batches, n_batches_total)
        self._skip_batches = 0
        return skip

    def _fetch(self, idx):
        """One dataset item, with transient-IO retry (a flaky NFS read
        must not kill a run) and the chaos harness's loader fault site."""
        from imaginaire_tpu.resilience import chaos, retry_call

        def _read():
            chaos.get().maybe_io_error("loader")
            return self.dataset[int(idx)]

        # one sample's read, decode, augment and label encoding, on the
        # worker thread that runs it
        with telemetry.span("loader_fetch"):
            return retry_call(_read, label="loader")

    def __len__(self):
        if self.global_batch_size and self.shard_by_process:
            # block mode: the epoch is measured in GLOBAL batches, a
            # world-size-invariant count (each host sees len() batches
            # of its share of every global batch)
            return max(len(self.dataset) // self.global_batch_size, 1)
        shards = get_world_size() if self.shard_by_process else 1
        n = len(self.dataset) // shards
        if self.drop_last:
            return max(n // self.batch_size, 1)
        return (n + self.batch_size - 1) // self.batch_size

    def _order(self):
        order = np.arange(len(self.dataset))
        if self.shuffle:
            rng = np.random.RandomState(self.seed + self.epoch)
            rng.shuffle(order)
        if not self.shard_by_process:
            return order
        world = get_world_size()
        if self.global_batch_size:
            # block-contiguous split (ISSUE 11): global batch k is
            # order[k*G:(k+1)*G] regardless of world size; host i owns
            # rows [i*share, (i+1)*share) of each. Concatenated across
            # hosts in process order (== mesh-device order under the
            # even-spread sub-mesh pick), every global batch is
            # IDENTICAL at any world size — the property the elastic
            # bit-exactness drill checks.
            g = self.global_batch_size
            share = self.batch_size
            nb = len(order) // g
            blocks = order[:nb * g].reshape(nb, g)
            i = get_rank()
            return blocks[:, i * share:(i + 1) * share].reshape(-1)
        # every process must see the SAME number of items per epoch
        # (ISSUE 8): the bare strided split hands early ranks one item
        # more when len(dataset) is not divisible — on a pod that means
        # one host finishes its epoch (and enters the end-of-epoch
        # checkpoint barrier) while its peers are still blocked in a
        # step collective waiting for it: a guaranteed desync every
        # epoch. Truncating to the common floor (the contract __len__
        # already promises) keeps all ranks in lockstep; the dropped
        # remainder rotates with the epoch shuffle.
        usable = (len(order) // world) * world
        return order[:usable][get_rank()::world]

    def __iter__(self):
        if self.num_workers > 0:
            yield from self._iter_prefetch()
            return
        order = self._order()
        skip = self._consume_skip(len(order) // self.batch_size
                                  if self.batch_size else 0)
        order = order[skip * self.batch_size:]
        batch = []
        for idx in order:
            batch.append(self._fetch(idx))
            if len(batch) == self.batch_size:
                yield self._collate(batch)
                batch = []
        if batch and not self.drop_last:
            yield self._collate(batch)

    def _iter_prefetch(self):
        """Worker-threaded pipeline (the num_workers contract of the
        reference's DataLoader, ref: utils/dataset.py:56-61): samples
        load+decode in a thread pool (cv2/numpy release the GIL; packed
        shards read through the native C++ pool) while the trainer
        consumes the previous batch; a bounded queue caps read-ahead.

        Lifecycle: worker exceptions travel through the queue and re-raise
        in the consumer; abandoning the iterator early (next(iter(...)),
        break, GeneratorExit) sets a stop flag and drains the queue so the
        producer's blocked put always unwinds — no deadlock either way."""
        import queue
        import threading
        from concurrent.futures import ThreadPoolExecutor

        order = self._order()
        batches = [order[i:i + self.batch_size]
                   for i in range(0, len(order), self.batch_size)]
        if self.drop_last and batches and \
                len(batches[-1]) < self.batch_size:
            batches.pop()
        batches = batches[self._consume_skip(len(batches)):]
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch_batches)
        stop = threading.Event()
        sentinel = object()

        def put(item):
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return
                except queue.Full:
                    continue

        def produce():
            try:
                with ThreadPoolExecutor(
                        self.num_workers,
                        thread_name_prefix="loader-worker") as pool:
                    for idxs in batches:
                        if stop.is_set():
                            return
                        futures = [pool.submit(self._fetch, int(i))
                                   for i in idxs]
                        items = [f.result() for f in futures]
                        with telemetry.span("loader_collate"):
                            batch = self._collate(items)
                        put(batch)
            except BaseException as e:  # forwarded to the consumer
                put(e)
            finally:
                put(sentinel)

        producer = threading.Thread(target=produce, daemon=True,
                                    name="loader-producer")
        producer.start()
        try:
            while True:
                item = q.get()
                if item is sentinel:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            producer.join(timeout=10)

    @staticmethod
    def _collate(items):
        out = {}
        for k in items[0]:
            vals = [it[k] for it in items]
            if isinstance(vals[0], np.ndarray) and vals[0].dtype != object:
                out[k] = np.stack(vals, axis=0)
            else:
                out[k] = vals
        return out


def _build_dataset(cfg, is_inference=False, is_test=False):
    """(ref: utils/dataset.py:24-43)."""
    dataset_cls = resolve(cfg.test_data.type if is_test else cfg.data.type,
                          "Dataset")
    return dataset_cls(cfg, is_inference=is_inference, is_test=is_test)


def get_train_and_val_dataloader(cfg, seed=0):
    """(ref: utils/dataset.py:63-83)."""
    train_ds = _build_dataset(cfg, is_inference=False)
    val_ds = _build_dataset(cfg, is_inference=True)
    num_workers = cfg_get(cfg.data, "num_workers", 0)
    prefetch = cfg_get(cfg.data, "prefetch", 2)
    # elastic pods (ISSUE 11): data.train.global_batch_size pins the
    # GLOBAL batch and activates the block-contiguous split — the
    # per-host batch follows the live world size across resizes
    global_bs = cfg_get(cfg.data.train, "global_batch_size", None)
    train = DataLoader(train_ds, cfg_get(cfg.data.train, "batch_size", 1),
                       shuffle=True, seed=seed, num_workers=num_workers,
                       prefetch_batches=prefetch,
                       global_batch_size=global_bs)
    val = DataLoader(val_ds, cfg_get(cfg.data.val, "batch_size", 1),
                     shuffle=False, seed=seed, num_workers=num_workers,
                     prefetch_batches=prefetch,
                     global_batch_size=cfg_get(cfg.data.val,
                                               "global_batch_size",
                                               None))
    return train, val


def get_test_dataloader(cfg):
    ds = _build_dataset(cfg, is_inference=True, is_test=True)
    return DataLoader(ds, cfg_get(cfg.test_data.test, "batch_size", 1),
                      shuffle=False, drop_last=False)
