"""Sharding helpers: batch-sharded data, replicated params.

The DP story (replaces DDP + DistributedSampler, ref:
imaginaire/utils/trainer.py:193-216, utils/dataset.py:46-59): arrays in a
batch pytree are sharded on their leading axis over the ``data`` mesh
axis; parameters/optimizer state are replicated. A train step jitted with
these shardings makes XLA partition the program SPMD-style and insert the
gradient all-reduce automatically.

Cross-replica batch norm comes for free under this scheme: a plain
``jnp.mean`` over the (globally sharded) batch axis *is* the global batch
statistic — XLA lowers it to a local reduce + psum over ICI — so the
reference's SyncBatchNorm (ref: layers/activation_norm.py:403-410) needs
no special layer here.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from imaginaire_tpu.parallel.mesh import DATA_AXIS, get_mesh, peek_mesh


def replicated_sharding(mesh=None):
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P())


def batch_sharding(mesh=None, axis=DATA_AXIS):
    """Sharding that splits the leading (batch) dim over the data axis."""
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P(axis))


def _batch_spec_for(x, axis, axis_size=None):
    """Leading-dim spec over ``axis``; replicated (P()) for scalars and
    for leaves whose dim 0 the NAMED AXIS size does not divide (a bs-2
    batch on an 8-device 1-D mesh must not fail the whole transfer).

    The divisibility check is against ``axis_size`` — the size of the
    ``data`` axis alone — never ``mesh.size``: on a 2-D ``(data=2,
    model=2)`` mesh a bs-2 batch shards fine over ``data`` (each data
    row's model devices replicate their slice), and demanding
    divisibility by all 4 chips would silently demote every 2-D-mesh
    run to the uncommitted synchronous transfer path.
    """
    if hasattr(x, "ndim") and x.ndim >= 1:
        if axis_size is not None and (
                x.shape[0] == 0 or x.shape[0] % axis_size != 0):
            return P()
        return P(axis, *([None] * (x.ndim - 1)))
    return P()


def batch_pytree_shardings(batch, mesh=None, axis=DATA_AXIS):
    """Per-leaf NamedShardings sharding dim 0 of every array leaf over
    the named ``axis`` (replicated where dim 0 is not divisible by that
    axis's size — NOT the whole mesh size; extra mesh axes like
    ``model`` replicate batch leaves)."""
    mesh = mesh or get_mesh()
    size = dict(mesh.shape).get(axis)
    if size is None:
        # a mesh without the requested axis can't shard the batch at
        # all — replicate every leaf rather than KeyError the transfer
        return jax.tree.map(lambda x: NamedSharding(mesh, P()), batch)
    return jax.tree.map(
        lambda x: NamedSharding(mesh, _batch_spec_for(x, axis, size)), batch)


def shard_batch(batch, mesh=None, axis=DATA_AXIS):
    """Device-put a host batch pytree with leading-dim sharding."""
    shardings = batch_pytree_shardings(batch, mesh, axis)
    return jax.device_put(batch, shardings)


def place_committed_batch(batch, mesh=None, axis=DATA_AXIS):
    """Device-put a numeric batch pytree as COMMITTED ``NamedSharding``
    arrays over the data mesh axis — the device-prefetch transfer path.

    Arrays arrive already laid out the way the jitted step wants them
    (batch dim over ``axis``, no post-hoc redistribution inside jit);
    leaves whose leading dim the axis size does not divide are placed
    replicated. Without a configured mesh (``peek_mesh()`` is None and
    no ``mesh`` given) this degrades to ``to_device``'s uncommitted
    ``jnp.asarray`` placement so single-device scripts keep working.

    Multi-process (ISSUE 8): the loader batch is this HOST's slice of
    the global batch (``DataLoader`` shards ``process_index::
    process_count``); the leaves assemble into GLOBAL arrays via
    ``jax.make_array_from_process_local_data`` — each host commits only
    its addressable shards and the jitted step sees one global batch
    sharded over the pod's ``data`` axis. This replaces the old
    synchronous uncommitted-transfer fallback, which silently ran N
    *independent* single-host programs (no gradient all-reduce at all)
    on multi-process runs.
    """
    from imaginaire_tpu.utils.misc import to_device

    mesh = mesh if mesh is not None else peek_mesh()
    if mesh is None:
        return to_device(batch)
    if jax.process_count() > 1:
        return place_process_local_batch(batch, mesh, axis)
    shardings = batch_pytree_shardings(batch, mesh, axis)
    if not _any_leaf_shards(shardings, axis):
        # nothing actually shards (batch dim indivisible everywhere):
        # committing replicated arrays would only drag every consumer
        # program onto the full mesh — keep the uncommitted placement
        return to_device(batch)
    return jax.device_put(batch, shardings)


def _any_leaf_shards(shardings, axis):
    specs = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda s: isinstance(s, NamedSharding))
    return any(len(s.spec) and s.spec[0] == axis for s in specs)


def batch_commits(batch, axis=DATA_AXIS):
    """Whether ``place_committed_batch`` would commit this (single-
    process) batch: a process mesh is set and the ``axis`` size divides
    some leaf's leading dim."""
    mesh = peek_mesh()
    return mesh is not None and _any_leaf_shards(
        batch_pytree_shardings(batch, mesh, axis), axis)


def place_process_local_batch(batch, mesh, axis=DATA_AXIS):
    """Assemble per-host batch slices into committed GLOBAL arrays.

    Each array leaf whose leading dim the host's LOCAL device count on
    ``axis`` divides becomes one global ``jax.Array`` sharded over the
    pod-wide ``axis`` (global batch = concat of the hosts' slices in
    process order — exactly the ``DataLoader``'s strided split
    reassembled). Leaves that cannot shard locally are placed
    replicated from local data — only correct for values identical
    across hosts (epoch scalars, broadcast constants), which is what
    indivisible leaves are in practice; per-host payloads belong in the
    host-only half of the batch (``split_host_leaves``)."""
    import numpy as np

    # this host's share of the sharded axis (``local_mesh`` is the
    # sub-mesh of this process's addressable devices)
    try:
        local_on_axis = dict(mesh.local_mesh.shape).get(axis, 0)
    except Exception:  # noqa: BLE001 — no local devices in this mesh
        local_on_axis = 0
    axis_in_mesh = axis in dict(mesh.shape)

    def place(x):
        x = np.asarray(x)
        spec = P()
        if axis_in_mesh and x.ndim >= 1 and local_on_axis > 0 \
                and x.shape[0] > 0 and x.shape[0] % local_on_axis == 0:
            spec = P(axis, *([None] * (x.ndim - 1)))
        elif x.ndim >= 1 and x.shape[0] > 1:
            # replication assembles THIS host's value as the global
            # one — wrong for per-host batch data. Batched leaves
            # should divide the per-host device share; say so loudly
            # instead of silently corrupting the global batch.
            import logging

            logging.getLogger(__name__).warning(
                "multi-process batch leaf with leading dim %d does not "
                "divide this host's %d device(s) on %r — placing "
                "REPLICATED from local data, which is only correct for "
                "host-identical values", x.shape[0], local_on_axis,
                axis)
        sharding = NamedSharding(mesh, spec)
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree.map(place, batch)


def assemble_global(tree, shardings):
    """Commit a host-replicated pytree under (possibly multi-process)
    shardings WITHOUT cross-process traffic.

    Every process already holds the full value of every leaf — the
    same-seed ``init_state`` and the layout-agnostic checkpoint restore
    both guarantee it — so each host materializes exactly its
    addressable shards through ``jax.make_array_from_callback``.

    This is NOT an optimization of ``jax.device_put``; that path is
    unsound here. ``device_put`` of a numpy/uncommitted leaf onto a
    non-fully-addressable sharding routes through
    ``multihost_utils.assert_equal``, i.e. one value-broadcast
    collective per leaf. Besides shipping every param tensor over the
    wire at init, the per-leaf sync only drains the FIRST local shard
    (``addressable_data(0)``) — with more than one local device per
    process (the elastic over-provisioned pods, ISSUE 11) the next
    leaf's broadcast overlaps the previous one's in-flight ops on the
    same transport pair and the CPU collective layer aborts the process
    with a raw size-mismatch (``op.preamble.length <= op.nbytes``).

    Leaves that are already multi-process global arrays (a resharding
    restore) pass through ``device_put``, which reshards committed
    arrays without the assert broadcast. ``shardings`` may be a single
    sharding (applied to every leaf) or a matching pytree."""
    import numpy as np

    def _one(x, s):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return jax.device_put(x, s)
        if isinstance(x, (jax.Array, np.ndarray, np.generic)):
            host = np.asarray(x)
        else:
            # python scalars: canonical jax dtypes (int32/float32 under
            # x32), not numpy's 64-bit defaults
            import jax.numpy as jnp

            host = np.asarray(jnp.asarray(x))
        return jax.make_array_from_callback(
            host.shape, s, lambda idx, v=host: v[idx])

    if isinstance(shardings, jax.sharding.Sharding):
        return jax.tree_util.tree_map(lambda x: _one(x, shardings), tree)
    return jax.tree_util.tree_map(_one, tree, shardings)


def data_axis_size(mesh=None, axis=DATA_AXIS):
    mesh = mesh or get_mesh()
    return mesh.shape[axis]
