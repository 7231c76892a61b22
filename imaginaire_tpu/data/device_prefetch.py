"""Async device-prefetch layer: overlap host batch prep, host->device
transfer, and XLA step dispatch.

The host ``DataLoader`` already overlaps decode/augment with compute
(``num_workers`` thread pool), but its batches land on the host — the
trainer then paid a synchronous, uncommitted ``jnp.asarray`` transfer at
the top of every iteration (``to_device``), stalling the step dispatch
for the full H2D latency. ``DevicePrefetcher`` closes that gap, the
jax analogue of the reference's ``pin_memory=True`` +
``.cuda(non_blocking=True)`` pair: a producer thread pulls host
batches, runs the trainer's host-side ``_start_of_iteration`` hook,
splits numeric leaves from host-only entries (``numeric_only``
semantics — strings, per-sample key lists, '_'-prefixed host payloads
stay put), and issues ``jax.device_put`` with committed
``NamedSharding(mesh, P('data', ...))`` specs so arrays arrive already
laid out for the SPMD step program — no post-hoc redistribution inside
jit. A bounded queue keeps up to ``depth`` batches resident on device
ahead of the consumer.

A dataset's mask label crosses the host as its int32 index map
(``BaseDataset.index_map_label``); ``expand_index_labels`` turns it into
the float32 channel stack on the device. The trainer hands it in as
``on_device`` (``BaseTrainer._on_device``) and the producer enqueues it
right after the placement, without waiting for it.

Observability: per-batch ``data/host_wait_ms`` (producer blocked on the
host loader), ``data/transfer_ms`` (placement, to the batch being on the
device), ``data/h2d_mb`` (what the placement was handed) and
``data/queue_depth`` (ready batches at consume time) accumulate in a
lock-guarded buffer; ``drain_stats()`` hands them to the trainer's
meters, flushed on ``logging_iter`` with the loss meters — nothing here
ever blocks the step loop on a device sync.

Lifecycle contract (mirrors ``DataLoader._iter_prefetch``): the wrapper
is re-iterable — each ``__iter__`` spawns a fresh producer; worker
exceptions travel through the queue and re-raise in the consumer;
abandoning the iterator early (``break`` / GeneratorExit) sets a stop
flag and drains the queue so a blocked producer put always unwinds.

Config: the ``data.device_prefetch`` knob ({enabled, depth}, defaults
on / depth 2) is honored by every family config via the defaults tree;
with it off, consumers keep the synchronous ``to_device`` path.

The producer thread is also where the vid2vid family's amortized
FlowNet2 teacher executes (``flow/cache.py``): the trainer's
``_start_of_iteration`` hook — run here as ``host_preprocess`` —
attaches the teacher's ``(flow, conf)`` ground truth to the batch, so
the 52.2 ms/frame teacher forward overlaps the running step and its
outputs ship through the same committed-sharding transfer as the rest
of the batch (the ``flow_teacher`` span nests under
``prefetch_preprocess`` in the phase table).
"""

from __future__ import annotations

import queue
import threading
import time

from imaginaire_tpu.config import cfg_get


class PrefetchedBatch(dict):
    """Marker type for batches a ``DevicePrefetcher`` produced: the
    host-side ``_start_of_iteration`` hook already ran and numeric
    leaves are committed device arrays — consumers must skip their own
    preprocess + transfer (``BaseTrainer.start_of_iteration`` does)."""


_EXPAND_PROGRAMS = {}


def expand_index_labels(data, num_label_channels):
    """A placed batch whose ``label`` is the dataset's integer index map
    -> the same batch with ``label`` the float32 (B,H,W,C) stack of
    ``num_label_channels`` the host encoding builds: the one-hot of the
    mask channels (exactly ``BaseDataset._encode_onehot``'s 0.0 / 1.0;
    an index past the last channel, the dropped dont-care, is
    ``one_hot``'s zero row) followed by ``label_float``, which leaves
    the batch. Any other batch comes back as it is.

    One small device program, enqueued and not waited for. Its output's
    sharding is stated: the batch dimension over the axis the placed
    label is laid on, as ``place_committed_batch`` lays a host-encoded
    stack; an uncommitted label (``to_device``) gives an uncommitted
    stack. One ledgered program per layout, so an evaluation batch after
    a training batch is not read as a recompile."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    label = data.get("label") if isinstance(data, dict) else None
    if label is None or not jnp.issubdtype(label.dtype, jnp.integer):
        return data
    floats = data.get("label_float")
    num_label_channels = int(num_label_channels)
    num_mask_channels = num_label_channels - (
        0 if floats is None else floats.shape[-1])
    sharding = None
    if isinstance(getattr(label, "sharding", None), NamedSharding):
        lead = tuple(label.sharding.spec)[:1]
        sharding = NamedSharding(
            label.sharding.mesh,
            P(*lead, *([None] * label.ndim)) if lead else P())
    key = (num_label_channels, num_mask_channels, sharding)
    program = _EXPAND_PROGRAMS.get(key)
    if program is None:
        from imaginaire_tpu.telemetry import xla_obs

        def expand(label, floats):
            # written into the whole stack's buffer: a concatenate keeps
            # the one-hot as a second, temporary stack (193 MB at
            # 4x256x256x184, the v5e compiler's memory analysis). The
            # dropped dont-care index is the first float channel's: set
            # over it
            stack = jax.nn.one_hot(label, num_label_channels,
                                   dtype=jnp.float32)
            if floats is not None:
                stack = stack.at[..., num_mask_channels:].set(
                    floats.astype(jnp.float32))
            return stack

        program = _EXPAND_PROGRAMS.setdefault(key, xla_obs.compiled_program(
            "expand_labels", expand, allow_shape_growth=True,
            out_shardings=sharding))
    out = dict(data, label=program(label, floats))
    out.pop("label_float", None)
    return out


def prefetch_settings(cfg):
    """(enabled, depth) from the ``data.device_prefetch`` config knob.

    Accepts a missing knob (defaults on, depth 2), a bare bool, or the
    {enabled, depth} mapping the defaults tree carries.
    """
    pcfg = cfg_get(cfg_get(cfg, "data", {}) or {}, "device_prefetch", None)
    if pcfg is None:
        return True, 2
    if isinstance(pcfg, bool):
        return pcfg, 2
    return (bool(cfg_get(pcfg, "enabled", True)),
            max(int(cfg_get(pcfg, "depth", 2)), 1))


class DevicePrefetcher:
    """Wrap a host batch iterable; keep ``depth`` batches on device
    ahead of the consumer.

    Args:
        loader: host batch iterable (``DataLoader`` or any iterable of
            dict batches). ``set_epoch``/``__len__``/``dataset`` pass
            through when present.
        host_preprocess: optional ``fn(batch, index) -> batch`` run in
            the producer thread BEFORE transfer — the trainer's
            host-side ``_start_of_iteration`` hook. ``index`` counts
            batches within the current iteration pass, so callers can
            derive the consuming iteration number.
        on_device: optional ``fn(batch) -> batch`` run on the placed
            numeric leaves: device programs (the trainer's
            ``_on_device``: the label expansion), enqueued behind
            whatever step is running and not waited for.
        depth: number of batches kept resident on device ahead of the
            consumer (the queue bound).
        mesh: mesh for the committed batch sharding; defaults to the
            process mesh (``peek_mesh``), degrading to uncommitted
            ``to_device`` placement when none is configured.
    """

    def __init__(self, loader, host_preprocess=None, depth=2, mesh=None,
                 axis="data", on_device=None):
        self.loader = loader
        self.host_preprocess = host_preprocess
        self.on_device = on_device
        self.depth = max(int(depth), 1)
        self.mesh = mesh
        self.axis = axis
        self._stats_lock = threading.Lock()
        self._stats = {}
        self._drop_batches = 0  # fast_forward fallback (one-shot)

    # ------------------------------------------------- loader passthrough

    def set_epoch(self, epoch):
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def fast_forward(self, n_batches):
        """Mid-epoch resume: skip the first ``n_batches`` of the next
        iteration pass. Delegates to the wrapped loader (no item is
        loaded or transferred for the skipped prefix); loaders without
        the knob fall back to a producer-side drop counter — batches
        are produced then discarded before preprocess/transfer."""
        if hasattr(self.loader, "fast_forward"):
            self.loader.fast_forward(n_batches)
        else:
            self._drop_batches = max(int(n_batches), 0)

    def __len__(self):
        return len(self.loader)

    @property
    def dataset(self):
        return getattr(self.loader, "dataset", None)

    # ------------------------------------------------------ observability

    def _record(self, name, value):
        with self._stats_lock:
            self._stats.setdefault(name, []).append(float(value))

    def drain_stats(self):
        """Pop accumulated {meter_name: [values]} — plain host floats,
        safe to write into meters without a device sync."""
        with self._stats_lock:
            out, self._stats = self._stats, {}
        return out

    # ------------------------------------------------------------ pipeline

    def _transfer(self, batch):
        """Split host-only leaves out and commit the numeric remainder
        as sharded device arrays. Non-dict batches place whole. Returns
        (placed, host) once the batch IS on the device: the producer
        thread waits, off the step path, so a queued batch is a resident
        one and ``prefetch_transfer`` is the H2D time."""
        import jax

        from imaginaire_tpu.parallel.sharding import place_committed_batch
        from imaginaire_tpu.utils.misc import split_host_leaves

        numeric, host = split_host_leaves(batch)
        self._record("data/h2d_mb", sum(
            getattr(x, "nbytes", 0)
            for x in jax.tree_util.tree_leaves(numeric)) / 1e6)
        return jax.block_until_ready(place_committed_batch(
            numeric, mesh=self.mesh, axis=self.axis)), host

    def _finish(self, placed, host, tm):
        """The placed leaves through ``on_device`` (enqueued, not waited
        for: the device runs it in order before the step that reads its
        output, and waiting here would tie the feed's period to the
        device's backlog), then re-merged with the host-only leaves."""
        from imaginaire_tpu.utils.misc import merge_host_leaves

        if not isinstance(placed, dict):
            return placed
        if self.on_device is not None:
            with tm.span("prefetch_expand"):
                placed = self.on_device(placed)
        return PrefetchedBatch(merge_host_leaves(placed, host))

    def __iter__(self):
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
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
            # producer-side telemetry spans (prefetch_host / _preprocess
            # / _transfer / _expand / _put) are tagged with this thread's
            # name: the hang watchdog's stack dump and the phase table
            # both show where the pipeline actually spends its time, off
            # the step path
            from imaginaire_tpu import telemetry

            tm = telemetry.get()
            try:
                source = iter(self.loader)
                drop, self._drop_batches = self._drop_batches, 0
                for _ in range(drop):
                    try:
                        next(source)
                    except StopIteration:
                        return
                index = 0
                while not stop.is_set():
                    t0 = time.perf_counter()
                    with tm.span("prefetch_host"):
                        try:
                            batch = next(source)
                        except StopIteration:
                            return
                    self._record("data/host_wait_ms",
                                 (time.perf_counter() - t0) * 1e3)
                    if self.host_preprocess is not None:
                        with tm.span("prefetch_preprocess"):
                            batch = self.host_preprocess(batch, index)
                    t1 = time.perf_counter()
                    with tm.span("prefetch_transfer"):
                        placed, host = self._transfer(batch)
                    self._record("data/transfer_ms",
                                 (time.perf_counter() - t1) * 1e3)
                    batch = self._finish(placed, host, tm)
                    with tm.span("prefetch_put"):
                        put(batch)
                    index += 1
            except BaseException as e:  # forwarded to the consumer
                put(e)
            finally:
                put(sentinel)

        producer = threading.Thread(target=produce, daemon=True,
                                    name="device-prefetch")
        producer.start()
        try:
            while True:
                item = q.get()
                if item is sentinel:
                    break
                if isinstance(item, BaseException):
                    raise item
                # depth actually in use: this batch + what is still queued
                self._record("data/queue_depth", q.qsize() + 1)
                yield item
        finally:
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            producer.join(timeout=10)
