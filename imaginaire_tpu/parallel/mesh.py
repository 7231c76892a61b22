"""Device mesh construction and process-level helpers.

TPU-native replacement for ``init_dist / get_rank / get_world_size /
master_only`` (ref: imaginaire/utils/distributed.py:11-58). A *process*
here is a JAX host process (one per TPU VM host), not one-per-chip like
the reference's one-process-per-GPU model; chips within a host are
addressed through the mesh, not through processes.

Mesh axes (all optional except ``data``):
  data    : data parallelism — batch sharded, grads psum'd; with
            ``cfg.parallel.shard_update_state`` the optimizer/EMA trees
            shard over this axis too (parallel/partition.py).
  model   : tensor parallelism — wide generator/discriminator conv
            channel dims shard here per the ``cfg.parallel.rules``
            logical-axis table (parallel/partition.py). Requesting a
            model axis that no rule consumes logs a loud warning
            instead of silently replicating (the old reserved-but-dead
            MODEL_AXIS trap).
  seq     : context/sequence parallelism for long video rollouts (frame axis
            sharding with ppermute ring exchange of carried frames) — the
            TPU-native extension filling SURVEY.md section 5.7.

``mesh_from_config`` is the single config entry point: it prefers the
``cfg.parallel`` group (``mesh_shape``/``axes``) and falls back to the
legacy ``cfg.runtime.mesh`` block.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh

_GLOBAL_MESH: Mesh | None = None

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"


def init_distributed(coordinator_address=None, num_processes=None, process_id=None):
    """Initialize multi-host JAX (replaces dist.init_process_group, ref:
    imaginaire/utils/distributed.py:11-17). No-op for single-process runs."""
    if num_processes is not None and num_processes > 1:
        import os

        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu") or \
                jax.config.jax_platforms == "cpu":
            # CPU pods (scripts/launch_local_pod.py, tests): cross-
            # process collectives need the gloo transport; harmless to
            # set, fatal to forget (collectives silently unavailable)
            try:
                jax.config.update("jax_cpu_collectives_implementation",
                                  "gloo")
            except Exception:  # noqa: BLE001 — older jaxlib: no knob
                pass
        if os.environ.get("IMAGINAIRE_ELASTIC") == "1":
            # elastic pods (resilience/elastic.py, ISSUE 11): the
            # runtime must survive peer loss (benign missed-heartbeat
            # callback) and tolerate in-process teardown/re-init — the
            # stock initializer's client kills the process on a lost
            # peer and blocks at exit in a collective shutdown barrier
            from imaginaire_tpu.resilience import elastic

            elastic.raw_init(coordinator_address, int(num_processes),
                             int(process_id or 0),
                             settings=elastic.env_settings())
            return
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )


def maybe_init_distributed_from_env():
    """Initialize ``jax.distributed`` from ``IMAGINAIRE_DIST_*`` env
    vars (ISSUE 8) — the contract ``scripts/launch_local_pod.py`` and
    real pod launchers use to make every entry point (train.py,
    inference.py, evaluate.py) pod-aware without CLI plumbing:

      IMAGINAIRE_DIST_COORDINATOR   host:port of process 0
      IMAGINAIRE_DIST_NUM_PROCESSES total process count
      IMAGINAIRE_DIST_PROCESS_ID    this process's index

    Must run BEFORE any jax backend initializes (entry points call it
    first thing in ``main``). No-op when the vars are absent
    or name a single process. Returns True when initialization ran."""
    import os

    n = os.environ.get("IMAGINAIRE_DIST_NUM_PROCESSES")
    if not n or int(n) <= 1:
        return False
    init_distributed(
        coordinator_address=os.environ.get("IMAGINAIRE_DIST_COORDINATOR"),
        num_processes=int(n),
        process_id=int(os.environ.get("IMAGINAIRE_DIST_PROCESS_ID", "0")),
    )
    return True


def _resolve_dims(axes, shape, n_devices):
    """Normalize a mesh shape request into a dims list aligned with
    ``axes`` (None => all devices on the first axis)."""
    if shape is None:
        return [int(n_devices)] + [1] * (len(axes) - 1)
    if isinstance(shape, (list, tuple)):
        if len(shape) != len(axes):
            raise ValueError(f"shape {shape} does not align with axes {axes}")
        return [int(s) for s in shape]
    return [int(shape[a]) if (hasattr(shape, "__getitem__") and a in shape) else 1 for a in axes]


def _submesh_devices(flat, want):
    """Pick ``want`` of the available devices for a sub-mesh.

    Single-process: the first ``want`` in id order (the seed behavior,
    byte-stable for every existing virtual-device test). Multi-process
    (ISSUE 11): spread the pick EVENLY across processes in
    ``(process_index, id)`` order — elastic pods over-provision
    devices per host so the logical mesh can stay constant across
    resizes, and a first-``want`` pick would park entire hosts outside
    the mesh (a 6-device mesh on 3 hosts x 3 devices would take all of
    p0+p1 and none of p2, leaving p2 with no addressable shard). Falls
    back to the first ``want`` when the spread doesn't divide evenly.
    """
    devs = sorted(flat.tolist(),
                  key=lambda d: (getattr(d, "process_index", 0), d.id))
    by_proc = {}
    for d in devs:
        by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
    n_procs = len(by_proc)
    per = want // n_procs if n_procs else 0
    if (n_procs > 1 and want % n_procs == 0
            and all(len(v) >= per for v in by_proc.values())):
        return np.array([d for p in sorted(by_proc)
                         for d in by_proc[p][:per]])
    return np.array(devs[:want])


def create_mesh(axes=("data",), shape=None, devices=None):
    """Create a Mesh over the given logical axes.

    ``shape=None`` puts every device on the first axis (pure DP, the
    reference's only parallelism mode). An explicit shape — a mapping
    like ``{"data": 4, "model": 2}`` or a sequence aligned with ``axes``
    like ``(4, 2)`` — builds an N-D mesh.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    axes = tuple(axes)
    dims = _resolve_dims(axes, shape, devices.size)
    want = int(np.prod(dims))
    if want > devices.size:
        raise ValueError(f"mesh shape {dims} != device count {devices.size}")
    if want < devices.size:
        # an explicit sub-mesh request (e.g. a (2,2) plan on an 8-chip
        # host): take prod(shape) devices instead of failing — evenly
        # spread across processes on a pod (see _submesh_devices), the
        # remaining devices simply stay out of this mesh
        import logging

        logging.getLogger(__name__).info(
            "mesh shape %s uses %d of %d devices", dims, want,
            devices.size)
        devices = _submesh_devices(devices.reshape(-1), want)
    return Mesh(devices.reshape(dims), axes)


def fit_mesh_shape(cfg, total_devices):
    """(axes, dims) the configured mesh takes on ``total_devices``
    devices — the elastic re-derivation (ISSUE 11).

    When the configured shape still fits (elastic pods over-provision
    devices per host precisely so it does), it is returned unchanged
    and the logical mesh — hence the training math — survives the
    resize bit-exactly. When the surviving devices can no longer cover
    it, the shape shrinks by the divisibility rules: data parallelism
    is preserved first (the ZeRO update-state sharding lives there),
    the model/other axes keep the largest divisor that still maximizes
    used devices, ties collapse toward pure DP. A model axis collapsed
    to 1 warns loudly (its partition rules go dead — params replicate);
    devices left idle at odd world sizes warn too.
    """
    import logging
    import math

    from imaginaire_tpu.config import cfg_get

    log = logging.getLogger(__name__)
    pcfg = cfg_get(cfg or {}, "parallel", None) or {}
    shape = cfg_get(pcfg, "mesh_shape", None)
    if shape is not None:
        axes = tuple(cfg_get(pcfg, "axes", None) or (DATA_AXIS, MODEL_AXIS))
    else:
        rcfg = cfg_get(cfg_get(cfg or {}, "runtime", None) or {}, "mesh",
                       None) or {}
        axes = tuple(cfg_get(rcfg, "axes", None) or (DATA_AXIS,))
        shape = cfg_get(rcfg, "shape", None)
    if shape is None:
        return axes, None  # all devices on the first axis, any count
    total = int(total_devices)
    dims = _resolve_dims(axes, shape, total)
    if int(np.prod(dims)) <= total:
        return axes, dims
    data_idx = axes.index(DATA_AXIS) if DATA_AXIS in axes else 0
    other_total = int(np.prod([d for k, d in enumerate(dims)
                               if k != data_idx]))
    # pick the non-data extent m (a divisor of the requested extent)
    # maximizing used devices m * (total // m); ties collapse toward
    # pure DP — the update-state sharding rides the data axis
    best_m, best_used = 1, 0
    for m in range(1, other_total + 1):
        if other_total % m or m > total:
            continue
        used = m * (total // m)
        if used > best_used:
            best_m, best_used = m, used
    new_dims = list(dims)
    new_dims[data_idx] = max(total // best_m, 1)
    remaining = best_m
    for k in range(len(dims)):
        if k == data_idx:
            continue
        d = math.gcd(remaining, int(dims[k]))
        new_dims[k] = d
        remaining //= d
    if remaining != 1:
        # the divisor doesn't factor over the axes' caps — collapse the
        # leftovers into the data axis rather than over-claim devices
        new_dims = [1 if k != data_idx else max(total // 1, 1)
                    for k in range(len(dims))]
        new_dims[data_idx] = total
    model_idx = axes.index(MODEL_AXIS) if MODEL_AXIS in axes else None
    if (model_idx is not None and int(dims[model_idx]) > 1
            and int(new_dims[model_idx]) == 1):
        log.warning(
            "elastic resize: model axis collapsed %d -> 1 at %d "
            "device(s) — the partition rules that sharded over 'model' "
            "go dead (params replicate) until the pod grows back",
            int(dims[model_idx]), total)
    used = int(np.prod(new_dims))
    if used < total:
        log.warning(
            "elastic resize: mesh %s uses %d of %d device(s) — %d "
            "idle at this world size (indivisible shape)",
            new_dims, used, total, total - used)
    log.info("elastic resize: mesh shape %s -> %s on %d device(s)",
             dims, new_dims, total)
    return axes, new_dims


def mesh_from_config(cfg, devices=None):
    """Build the process mesh from a full experiment config.

    The ``cfg.parallel`` group wins when its ``mesh_shape`` is set (the
    2-D data x model entry point, see parallel/partition.py); otherwise
    the legacy ``cfg.runtime.mesh`` {axes, shape} block applies, whose
    default (axes=['data'], shape=None) is the seed's pure-DP layout.
    """
    from imaginaire_tpu.config import cfg_get

    pcfg = cfg_get(cfg or {}, "parallel", None) or {}
    shape = cfg_get(pcfg, "mesh_shape", None)
    if shape is not None:
        axes = tuple(cfg_get(pcfg, "axes", None) or (DATA_AXIS, MODEL_AXIS))
        return create_mesh(axes, shape, devices=devices)
    rcfg = cfg_get(cfg_get(cfg or {}, "runtime", None) or {}, "mesh",
                   None) or {}
    axes = tuple(cfg_get(rcfg, "axes", None) or (DATA_AXIS,))
    mesh = create_mesh(axes, cfg_get(rcfg, "shape", None), devices=devices)
    if dict(mesh.shape).get(MODEL_AXIS, 1) > 1:
        # the old reserved-but-dead MODEL_AXIS trap: a legacy
        # runtime.mesh model axis has no consumer unless cfg.parallel
        # activates the partition plan — say so instead of silently
        # replicating params across it
        import logging

        logging.getLogger(__name__).warning(
            "runtime.mesh requests a model axis of size %d but "
            "cfg.parallel.mesh_shape is unset — no partition rules will "
            "consume it (params replicate across the axis). Set "
            "parallel.mesh_shape to activate the 2-D partition plan.",
            dict(mesh.shape)[MODEL_AXIS])
    return mesh


def set_mesh(mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    return mesh


def get_mesh():
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = create_mesh()
    return _GLOBAL_MESH


def peek_mesh():
    """The configured process mesh, or None — WITHOUT creating one.

    Layer-level sharding opt-ins (e.g. hyper_ops' data-sharded
    per-sample conv) must consult the mesh passively: get_mesh()'s
    auto-create would silently install a global all-device mesh as a
    side effect of a layer op in programs that never called set_mesh."""
    return _GLOBAL_MESH


# Last values jax reported before an elastic teardown window (ISSUE
# 13): between force_teardown and the re-init, jax.process_index()
# does not just fail — it tries to REBUILD the cpu backend, whose gloo
# collectives factory needs the now-detached distributed client. Any
# master-gated print/log in that window would crash the process.
_LAST_RANK = None
_LAST_WORLD = None


def get_rank():
    """Host-process index (ref: utils/distributed.py:20-26)."""
    global _LAST_RANK
    try:
        _LAST_RANK = jax.process_index()
        return _LAST_RANK
    except RuntimeError:
        if _LAST_RANK is not None:
            return _LAST_RANK
        raise


def get_world_size():
    """Number of host processes (ref: utils/distributed.py:29-35)."""
    global _LAST_WORLD
    try:
        _LAST_WORLD = jax.process_count()
        return _LAST_WORLD
    except RuntimeError:
        if _LAST_WORLD is not None:
            return _LAST_WORLD
        raise


def is_master():
    return get_rank() == 0


def master_only(func):
    """Run only on process 0 (ref: utils/distributed.py:38-47)."""

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        if is_master():
            return func(*args, **kwargs)
        return None

    return wrapper


@master_only
def master_only_print(*args, **kwargs):
    """Print only on process 0 (ref: utils/distributed.py:55-58)."""
    print(*args, **kwargs)
