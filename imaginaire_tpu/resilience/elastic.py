"""Elastic pods: dynamic mesh resize with live state redistribution
(ISSUE 11).

PRs 7-8 made preemption survivable but stop-the-world: one lost host
idles the whole pod until the SAME world size comes back. This module
lets the survivors keep training. On a peer-loss signal (a drain vote
whose flagged host won't return, heartbeat staleness, or a
``ClusterDesyncError`` from a timed collective) the surviving processes
run a consensus round over the coordination-service KV store they
already share, agree on the new topology + resume iteration, tear the
jax distributed runtime down IN-PROCESS, re-initialize it with the
shrunken world on a fresh port, rebuild the mesh/partition plan, and
restore the emergency checkpoint through the existing layout-agnostic
no-target path — optimizer/EMA shards land redistributed under the new
NamedShardings (the portable-collective reshard of arXiv:2112.01075,
reusing PR-6's reshard-on-load instead of inventing a second path).
Scale-up on rejoin is the same flow in reverse, rendezvoused through
``<logdir>/elastic/``.

Three hard-won mechanics (validated against jax 0.4.37 on the CPU pod
harness; see tests/test_elastic.py):

- ``jax.distributed.shutdown()`` HANGS when a peer died abruptly (the
  shutdown barrier waits for everyone) and a second ``initialize``
  refuses to run. ``force_teardown`` instead detaches the old
  client/service from ``distributed.global_state``, shuts the old
  client down on a daemon thread bounded by its ``shutdown_timeout``,
  and deliberately LEAKS the old coordination service — a dead-peer
  error poll on a leaked service is noise; a blocked main thread is an
  outage.
- jax's default missed-heartbeat callback terminates the process —
  exactly wrong for a survivor. Elastic runs init through the raw
  distributed-runtime client with a benign callback, so peer loss is
  an event we *observe*, not one that kills us.
- ``xla_bridge.process_count`` (and friends) are ``lru_cache``'d:
  after re-init the pod would keep reporting the OLD world size.
  Teardown clears the backend table AND those caches.

The per-process virtual device count is fixed at launch
(``--xla_force_host_platform_device_count`` parses once, in C++), so
elastic pods OVER-PROVISION devices per process and keep the *logical*
mesh constant across resizes where possible: a 6-device data mesh is 3
procs x 2 devices before the kill and 2 procs x 3 devices after, and
because the global batch is composed block-contiguously (data/loader
block split) the training math is bit-identical across the transition.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time

from imaginaire_tpu.config import cfg_get

logger = logging.getLogger(__name__)

TOPOLOGY_FILE = "topology.json"
JOIN_DIR = "join"


class ElasticResize(Exception):
    """Raised out of the train loop to unwind into the supervise loop
    with an agreed ``ResizePlan`` (train.py catches it, applies the
    plan, and re-enters the loop — nothing about it is an error)."""

    def __init__(self, plan):
        super().__init__(f"elastic resize -> world {plan.world_size} "
                         f"(generation {plan.generation})")
        self.plan = plan


def elastic_settings(cfg):
    """Parse ``cfg.resilience.elastic`` (see config.py defaults)."""
    rcfg = cfg_get(cfg or {}, "resilience", None) or {}
    ecfg = cfg_get(rcfg, "elastic", None) or {}
    return {
        "enabled": bool(cfg_get(ecfg, "enabled", False)),
        "min_world_size": int(cfg_get(ecfg, "min_world_size", 2)),
        "grow_back": bool(cfg_get(ecfg, "grow_back", True)),
        "resize_timeout_s": float(
            cfg_get(ecfg, "resize_timeout_s", 60.0) or 0.0),
        "join_poll_s": float(cfg_get(ecfg, "join_poll_s", 0.25) or 0.25),
        "join_timeout_s": float(
            cfg_get(ecfg, "join_timeout_s", 600.0) or 0.0),
        "port_stride": int(cfg_get(ecfg, "port_stride", 17) or 1),
        "heartbeat_interval_s": float(
            cfg_get(ecfg, "heartbeat_interval_s", 1.0) or 1.0),
        "max_missing_heartbeats": int(
            cfg_get(ecfg, "max_missing_heartbeats", 5) or 5),
        "init_timeout_s": float(
            cfg_get(ecfg, "init_timeout_s", 120.0) or 120.0),
        "shutdown_timeout_s": float(
            cfg_get(ecfg, "shutdown_timeout_s", 5.0) or 5.0),
    }


# --------------------------------------------------- raw init / teardown

# old (client, service) pairs kept alive on purpose: destroying a
# service whose registered peers died abruptly can block; a leaked one
# only logs "tasks unhealthy" on its error poll until process exit
_LEAKED = []
_PEER_LOSS_EVENTS = []


def _benign_missed_heartbeat(status):
    """jax's default callback terminates the process on peer loss; a
    survivor must treat it as a *signal* instead."""
    _PEER_LOSS_EVENTS.append(str(status))
    logger.warning("elastic: coordination-service heartbeat reports a "
                   "lost peer: %s", status)


def raw_init(coordinator_address, num_processes, process_id,
             settings=None):
    """Initialize ``jax.distributed`` through the raw runtime client.

    Equivalent to ``jax.distributed.initialize`` except: the
    missed-heartbeat callback is benign (peer loss must not kill a
    survivor), ``shutdown_on_destruction`` is off (an elastic process's
    exit must never block in the collective shutdown barrier of a world
    that no longer exists), and the client heartbeat is fast so peer
    loss is *detected* within seconds, not minutes. Populates
    ``distributed.global_state`` exactly like the stock initializer so
    every downstream consumer (gloo collectives, ``cluster.client()``)
    is untouched.
    """
    from jax._src import distributed
    from jax._src.lib import xla_extension as xe

    s = settings or elastic_settings({})
    gs = distributed.global_state
    if gs.client is not None:
        raise RuntimeError("elastic raw_init: a distributed client is "
                           "already live — force_teardown() first")
    hb = max(int(round(s["heartbeat_interval_s"])), 1)
    miss = max(int(s["max_missing_heartbeats"]), 2)
    if process_id == 0:
        bind = "[::]:" + str(coordinator_address).rsplit(":", 1)[1]
        gs.service = xe.get_distributed_runtime_service(
            bind, num_processes, heartbeat_interval=hb,
            max_missing_heartbeats=miss)
    gs.client = xe.get_distributed_runtime_client(
        coordinator_address, process_id,
        init_timeout=int(s["init_timeout_s"]),
        shutdown_timeout=int(s["shutdown_timeout_s"]),
        heartbeat_interval=hb, max_missing_heartbeats=miss,
        missed_heartbeat_callback=_benign_missed_heartbeat,
        shutdown_on_destruction=False, use_compression=True)
    gs.client.connect()
    gs.process_id = int(process_id)
    gs.num_processes = int(num_processes)
    gs.coordinator_address = str(coordinator_address)


def force_teardown():
    """Detach the live distributed runtime so a new one can start.

    The cooperative ``jax.distributed.shutdown`` is a collective — it
    waits for peers that may be dead. This path never blocks: detach
    the client/service from ``global_state``, shut the old client down
    on a daemon thread (bounded by its own ``shutdown_timeout``), leak
    the old service, drop every backend, and clear the lru-cached
    process topology (``jax.process_count`` would otherwise keep
    reporting the dead world)."""
    import jax
    import jax.extend.backend
    from jax._src import distributed

    gs = distributed.global_state
    old_client, old_service = gs.client, gs.service
    gs.client = None
    gs.service = None
    gs.preemption_sync_manager = None
    gs.coordinator_address = None
    gs.process_id = 0
    gs.num_processes = None
    if old_client is not None:
        def _shutdown():
            try:
                old_client.shutdown()
            except Exception as e:  # noqa: BLE001 — leaked world noise
                logger.debug("elastic: old client shutdown: %s", e)

        threading.Thread(target=_shutdown, daemon=True,
                         name="elastic-old-client-shutdown").start()
    if old_client is not None or old_service is not None:
        _LEAKED.append((old_client, old_service))
    # every backend client goes, and with it what jax caches per world:
    # the process topology (``jax.process_count`` would otherwise keep
    # reporting the dead one) and the jitted executables, which baked
    # the dead world's device ids into their bindings
    jax.extend.backend.clear_backends()
    jax.clear_caches()


# ------------------------------------------------------------ the plan

class ResizePlan:
    """The agreed post-resize topology — everything a member needs to
    tear down, re-init, and resume, JSON-able so it can ride the KV
    store (shrink consensus) or ``topology.json`` (rejoin).

    ``members`` is an ordered list of member tokens; a member's NEW
    process id is its index. Survivors are ``"p<old_id>"`` (sorted, so
    surviving ids stay stable where possible — the old master stays
    master); joiners are their join-request nonces, appended last."""

    def __init__(self, generation, members, coordinator, iteration=-1,
                 epoch=0, mesh_axes=None, mesh_shape=None,
                 barrier_epochs=None, reason="shrink", old_world=None,
                 old_mesh_shape=None):
        self.generation = int(generation)
        self.members = list(members)
        self.coordinator = str(coordinator)
        self.iteration = int(iteration)
        self.epoch = int(epoch)
        self.mesh_axes = list(mesh_axes) if mesh_axes else None
        self.mesh_shape = (list(mesh_shape)
                           if mesh_shape is not None else None)
        self.barrier_epochs = dict(barrier_epochs or {})
        self.reason = str(reason)
        self.old_world = old_world
        self.old_mesh_shape = (list(old_mesh_shape)
                               if old_mesh_shape is not None else None)

    @property
    def world_size(self):
        return len(self.members)

    def process_id_of(self, token):
        try:
            return self.members.index(str(token))
        except ValueError:
            return None

    def to_json(self):
        return json.dumps({
            "version": 1, "generation": self.generation,
            "members": self.members, "coordinator": self.coordinator,
            "iteration": self.iteration, "epoch": self.epoch,
            "mesh_axes": self.mesh_axes, "mesh_shape": self.mesh_shape,
            "barrier_epochs": self.barrier_epochs,
            "reason": self.reason, "old_world": self.old_world,
            "old_mesh_shape": self.old_mesh_shape,
        })

    @classmethod
    def from_json(cls, text):
        rec = json.loads(text)
        return cls(rec["generation"], rec["members"],
                   rec["coordinator"], rec.get("iteration", -1),
                   rec.get("epoch", 0), rec.get("mesh_axes"),
                   rec.get("mesh_shape"), rec.get("barrier_epochs"),
                   rec.get("reason", "shrink"), rec.get("old_world"),
                   rec.get("old_mesh_shape"))


# ------------------------------------------------- state redistribution

class RedistributionPlanner:
    """Per-leaf routing for the state move a resize implies (ISSUE 13).

    Two routes exist:

    - ``"gather"``: the live leaf is pulled to host memory BEFORE the
      old runtime is torn down and re-committed directly under the new
      world's shardings — no checkpoint round-trip. Only sound when the
      leaf's full value is locally present (replicated / single-device
      sharding) AND the live iteration equals the plan's consensus
      iteration, so the carried bytes are bit-identical to what the
      rest of the pod restores.
    - ``"checkpoint"``: the leaf rides the emergency checkpoint through
      the layout-agnostic reshard-on-load path (PR-6) — the only route
      for cross-process shards (survivors hold partial data) and for
      joiners (no live state at all).

    Byte totals mirror ``partition.state_bytes_report`` (same
    size*itemsize accounting via ``tree_bytes``), so the telemetry the
    resize emits is directly comparable to the partition ledger.

    When EVERY leaf routes ``"gather"`` the executor (train.py +
    ``trainer.elastic_recommit``) skips the orbax restore entirely —
    the big downtime win for replicated pods. A mixed plan restores the
    full tree and overwrites the gather-routed leaves with the carried
    live values.
    """

    def __init__(self, plan, live_iteration, state):
        self.plan = plan
        self.live_iteration = int(live_iteration)
        self.routes = {}          # path-key -> "gather" | "checkpoint"
        self.gather_bytes = 0
        self.checkpoint_bytes = 0
        self._build(state)

    # ----------------------------------------------------------- build

    @staticmethod
    def _leaf_key(path):
        import jax

        return jax.tree_util.keystr(path)

    @staticmethod
    def _leaf_bytes(leaf):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is None or dtype is None:
            return 0
        try:
            return int(size) * int(dtype.itemsize)
        except Exception:  # noqa: BLE001 — extension dtypes
            return 0

    @staticmethod
    def _locally_complete(leaf):
        """Whether this process holds the leaf's FULL value: replicated
        shardings and plain host/single-device arrays qualify; a leaf
        sharded across processes does not (a survivor only owns its
        shard — carrying it would truncate the tensor)."""
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            return True  # host numpy / python scalar
        rep = getattr(sharding, "is_fully_replicated", None)
        if rep is not None:
            return bool(rep)
        try:
            return len(sharding.device_set) <= 1
        except Exception:  # noqa: BLE001 — exotic sharding
            return False

    def _build(self, state):
        import jax

        live_matches = (self.live_iteration >= 0
                        and self.plan.iteration == self.live_iteration)
        leaves = (jax.tree_util.tree_flatten_with_path(state)[0]
                  if state is not None else [])
        for path, leaf in leaves:
            nbytes = self._leaf_bytes(leaf)
            if live_matches and self._locally_complete(leaf):
                self.routes[self._leaf_key(path)] = "gather"
                self.gather_bytes += nbytes
            else:
                self.routes[self._leaf_key(path)] = "checkpoint"
                self.checkpoint_bytes += nbytes

    # --------------------------------------------------------- queries

    @property
    def total_bytes(self):
        return self.gather_bytes + self.checkpoint_bytes

    @property
    def all_gather(self):
        """True when every leaf can skip the checkpoint round-trip."""
        return bool(self.routes) and all(
            r == "gather" for r in self.routes.values())

    def route_counts(self):
        gather = sum(1 for r in self.routes.values() if r == "gather")
        return {"gather": gather,
                "checkpoint": len(self.routes) - gather}

    def summary(self):
        """The redistribution record ``record_resize`` folds into the
        ``elastic/resize`` meta event."""
        counts = self.route_counts()
        return {
            "redistributed_bytes": int(self.total_bytes),
            "gather_bytes": int(self.gather_bytes),
            "checkpoint_bytes": int(self.checkpoint_bytes),
            "gather_leaves": counts["gather"],
            "checkpoint_leaves": counts["checkpoint"],
        }

    # -------------------------------------------------------- snapshot

    def snapshot(self, state):
        """Pull every gather-routed leaf to an OWNED host copy. Must
        run while the old backend is still alive — after
        ``force_teardown`` the arrays' buffers are gone. The copy is
        deliberate: a zero-copy view into a device buffer would dangle
        once the backend table is cleared."""
        import jax
        import numpy as np

        carry = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
            key = self._leaf_key(path)
            if self.routes.get(key) != "gather":
                continue
            try:
                carry[key] = np.array(leaf)  # copy=True by default
            except Exception as e:  # noqa: BLE001 — fall back to ckpt
                logger.warning(
                    "elastic: gather snapshot failed for %s (%s) — "
                    "leaf demoted to the checkpoint route", key, e)
                self.routes[key] = "checkpoint"
                nbytes = self._leaf_bytes(leaf)
                self.gather_bytes -= nbytes
                self.checkpoint_bytes += nbytes
        return carry


# ----------------------------------------------------- the coordinator

class ElasticCoordinator:
    """Owns the resize lifecycle for one training process.

    Shrink: ``plan_shrink(dead, ...)`` runs the survivor consensus over
    the OLD KV store (a poll-based rendezvous — the service barrier
    would wait on the dead) and returns the agreed ``ResizePlan``.
    Grow: the master polls ``<logdir>/elastic/join/`` for join-request
    nonces, announces a strictly-future target step through the KV
    store (``announce_grow``/``poll_grow``), and at the target step
    every survivor derives the identical ``plan_grow``. ``apply(plan)``
    performs the actual teardown/re-init and barrier-epoch adoption;
    the caller (train.py) rebuilds mesh/plan/state around it.
    """

    def __init__(self, cfg, logdir=None):
        self.cfg = cfg
        self.settings = elastic_settings(cfg)
        self.logdir = str(logdir) if logdir else None
        self.generation = int(os.environ.get(
            "IMAGINAIRE_ELASTIC_GENERATION", "0"))
        # the generation-0 coordinator anchors the port schedule: every
        # later generation lives at base_port + gen * port_stride, so
        # each resize rendezvouses on a fresh service while remaining
        # deterministic for every member
        self._base_coordinator = os.environ.get(
            "IMAGINAIRE_ELASTIC_BASE_COORDINATOR",
            os.environ.get("IMAGINAIRE_DIST_COORDINATOR", ""))
        self._announced_grow = None
        self.resizes = 0
        self.downtime_ms = 0.0
        self.redistributed_bytes = 0

    @property
    def enabled(self):
        return bool(self.settings["enabled"])

    # ------------------------------------------------------------ paths

    def elastic_dir(self):
        if not self.logdir:
            return None
        return os.path.join(self.logdir, "elastic")

    def topology_path(self):
        d = self.elastic_dir()
        return os.path.join(d, TOPOLOGY_FILE) if d else None

    # ----------------------------------------------------------- shrink

    def coordinator_for(self, generation):
        """Deterministic coordinator address of a generation."""
        base = self._base_coordinator
        if not base or ":" not in base:
            raise RuntimeError(
                "elastic: no base coordinator address (set "
                "IMAGINAIRE_DIST_COORDINATOR)")
        host, port = base.rsplit(":", 1)
        return f"{host}:{int(port) + int(generation) * self.settings['port_stride']}"

    def can_shrink(self, dead, world=None):
        """Whether the survivors can reshape instead of exiting: the
        master (KV host) must survive, and the surviving world must
        stay at or above ``min_world_size``."""
        from imaginaire_tpu.resilience import cluster

        if not self.enabled:
            return False
        n = int(world if world is not None else cluster.process_count())
        dead = set(int(d) for d in dead)
        if not dead or 0 in dead:
            return False  # the coordinator died with the KV store
        return (n - len(dead)) >= max(self.settings["min_world_size"], 1)

    def plan_shrink(self, dead, iteration=-1, epoch=0):
        """Survivor consensus over the OLD KV store. Returns the agreed
        ``ResizePlan`` or raises ``ClusterDesyncError`` when a survivor
        never votes within ``resize_timeout_s``."""
        from imaginaire_tpu.resilience import cluster

        n = cluster.process_count()
        i = cluster.process_index()
        dead = sorted(set(int(d) for d in dead))
        survivors = [p for p in range(n) if p not in dead]
        gen = self.generation + 1
        payload = {"it": int(iteration), "ep": int(epoch),
                   "tok": f"p{i}"}
        votes = cluster.agree_survivors(
            "shrink", gen, payload, survivors,
            timeout_s=self.settings["resize_timeout_s"])
        its = [int(v.get("it", -1)) for v in votes.values()]
        valid = [v for v in its if v >= 0]
        agreed_it = min(valid) if valid else -1
        agreed_ep = min(int(v.get("ep", 0)) for v in votes.values())
        mesh_axes, mesh_shape = self._fit_shape(len(survivors))
        plan = ResizePlan(
            gen, [f"p{p}" for p in survivors],
            self.coordinator_for(gen), iteration=agreed_it,
            epoch=agreed_ep, mesh_axes=mesh_axes, mesh_shape=mesh_shape,
            barrier_epochs=cluster.export_barrier_epochs(),
            reason="shrink", old_world=n,
            old_mesh_shape=self._current_mesh_shape())
        if i == min(survivors):
            # consensus done; the master's plan is identical to every
            # other survivor's (same votes, same derivation) — publish
            # the topology file for observers and future joiners
            self.publish_topology(plan)
        return plan

    def _fit_shape(self, new_world):
        """(axes, dims) the new world's mesh will use — the constant
        logical mesh when the surviving devices still cover it, else
        the re-derived shape from the divisibility rules."""
        import jax

        from imaginaire_tpu.parallel import mesh as mesh_lib

        try:
            per_proc = jax.local_device_count()
        except Exception:  # noqa: BLE001 — backend already torn down
            per_proc = 1
        total = per_proc * int(new_world)
        axes, dims = mesh_lib.fit_mesh_shape(self.cfg, total)
        return list(axes), (list(dims) if dims is not None else None)

    def _current_mesh_shape(self):
        from imaginaire_tpu.parallel.mesh import peek_mesh

        mesh = peek_mesh()
        if mesh is None:
            return None
        return [int(s) for s in mesh.devices.shape]

    # ------------------------------------------------------------- grow

    def check_join_requests(self):
        """Sorted join-request nonces present in the join dir minus the
        ones already part of the current membership (master-side poll;
        cheap: one listdir)."""
        d = self.elastic_dir()
        if not d:
            return []
        join_dir = os.path.join(d, JOIN_DIR)
        try:
            names = os.listdir(join_dir)
        except OSError:
            return []
        return sorted(os.path.splitext(name)[0] for name in names
                      if name.endswith(".json"))

    def announce_grow(self, target_step, joiners):
        """Master: publish the grow decision through the KV store. Every
        member reads it at a barrier-synced step strictly BEFORE
        ``target_step`` (the write happens-before the next barrier
        release), so the whole pod acts at the same iteration."""
        from imaginaire_tpu.resilience import cluster

        c = cluster.client()
        if c is None:
            return None
        rec = {"target": int(target_step),
               "joiners": sorted(str(j) for j in joiners),
               "generation": self.generation + 1}
        if self._announced_grow == rec["joiners"]:
            return None
        try:
            c.key_value_set(f"elastic/grow/g{self.generation}",
                            json.dumps(rec), allow_overwrite=True)
            self._announced_grow = rec["joiners"]
        except Exception as e:  # noqa: BLE001 — retried next sync step
            logger.warning("elastic: grow announce failed: %s", e)
            return None
        logger.info("elastic: grow announced — joiner(s) %s attach at "
                    "step %d", rec["joiners"], rec["target"])
        return rec

    def poll_grow(self):
        """The pending grow record ``{"target", "joiners",
        "generation"}`` for this generation, or None."""
        from imaginaire_tpu.resilience import cluster

        c = cluster.client()
        if c is None:
            return None
        prefix = "elastic/grow/"
        try:
            entries = c.key_value_dir_get(prefix)
        except Exception:  # noqa: BLE001 — no announcement yet
            return None
        for key, value in entries:
            if key.rsplit("/", 1)[-1] == f"g{self.generation}":
                try:
                    return json.loads(value)
                except ValueError:
                    return None
        return None

    def plan_grow(self, joiners, iteration, epoch):
        """Deterministic grow plan every survivor derives identically
        from the announced grow record — no extra consensus round."""
        from imaginaire_tpu.resilience import cluster

        n = cluster.process_count()
        gen = self.generation + 1
        members = [f"p{p}" for p in range(n)]
        members.extend(sorted(str(j) for j in joiners))
        mesh_axes, mesh_shape = self._fit_shape(len(members))
        return ResizePlan(
            gen, members, self.coordinator_for(gen),
            iteration=int(iteration), epoch=int(epoch),
            mesh_axes=mesh_axes, mesh_shape=mesh_shape,
            barrier_epochs=cluster.export_barrier_epochs(),
            reason="grow", old_world=n,
            old_mesh_shape=self._current_mesh_shape())

    # -------------------------------------------------------- topology

    def publish_topology(self, plan):
        """Write ``<logdir>/elastic/topology.json`` atomically — the
        rendezvous document joiners poll (and the operator's view of
        the live topology)."""
        path = self.topology_path()
        if not path:
            return None
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(plan.to_json())
        os.replace(tmp, path)
        return path

    def consume_join_requests(self, joiners):
        """Retire the join-request files a grow plan absorbed (master,
        post-publish) so the next poll doesn't re-admit them."""
        d = self.elastic_dir()
        if not d:
            return
        for nonce in joiners:
            try:
                os.remove(os.path.join(d, JOIN_DIR, f"{nonce}.json"))
            except OSError:
                pass

    # ------------------------------------------------------------ apply

    def apply(self, plan, my_token=None):
        """Execute the resize on this process: tear the old runtime
        down, point the ``IMAGINAIRE_DIST_*`` contract at the new
        topology, re-init through ``mesh.maybe_init_distributed_from_env``
        (routed back here via ``IMAGINAIRE_ELASTIC``), and re-align the
        barrier epochs (a fresh member would otherwise desync every
        counter-tagged rendezvous). Returns phase timings in ms."""
        from imaginaire_tpu.parallel import mesh as mesh_lib
        from imaginaire_tpu.resilience import cluster

        if my_token is None:
            my_token = f"p{cluster.process_index()}"
        new_id = plan.process_id_of(my_token)
        if new_id is None:
            raise RuntimeError(
                f"elastic: this process ({my_token}) is not a member of "
                f"generation {plan.generation}")
        timings = {}
        t0 = time.perf_counter()
        cluster.stop_heartbeat()
        force_teardown()
        timings["teardown_ms"] = round(
            (time.perf_counter() - t0) * 1000.0, 3)
        os.environ["IMAGINAIRE_DIST_COORDINATOR"] = plan.coordinator
        os.environ["IMAGINAIRE_DIST_NUM_PROCESSES"] = str(
            plan.world_size)
        os.environ["IMAGINAIRE_DIST_PROCESS_ID"] = str(new_id)
        os.environ["IMAGINAIRE_ELASTIC"] = "1"
        os.environ["IMAGINAIRE_ELASTIC_GENERATION"] = str(
            plan.generation)
        if self._base_coordinator:
            os.environ["IMAGINAIRE_ELASTIC_BASE_COORDINATOR"] = \
                self._base_coordinator
        t1 = time.perf_counter()
        mesh_lib.maybe_init_distributed_from_env()
        timings["reinit_ms"] = round(
            (time.perf_counter() - t1) * 1000.0, 3)
        cluster.adopt_barrier_epochs(plan.barrier_epochs)
        cluster.start_heartbeat()
        self.generation = plan.generation
        self._announced_grow = None
        self.resizes += 1
        logger.info(
            "elastic: generation %d live — world %d -> %d, process %s "
            "-> %d, coordinator %s (teardown %.0fms, re-init %.0fms)",
            plan.generation, plan.old_world or -1, plan.world_size,
            my_token, new_id, plan.coordinator,
            timings["teardown_ms"], timings["reinit_ms"])
        return timings

    def record_resize(self, plan, downtime_ms, phases=None,
                      redistribution=None):
        """Emit the ``elastic/resize`` meta event + counters every
        downstream reader keys on (check_run_health's changed-process-
        count acceptance, report.py's elasticity section, bench's leg
        summary). ``redistribution`` is
        ``RedistributionPlanner.summary()`` — the per-route byte
        accounting of the state move this resize performed."""
        from imaginaire_tpu import telemetry

        self.downtime_ms += float(downtime_ms)
        redist = dict(redistribution or {})
        self.redistributed_bytes += int(
            redist.get("redistributed_bytes", 0) or 0)
        tm = telemetry.get()
        if tm.enabled:
            tm.meta("elastic/resize", generation=plan.generation,
                    reason=plan.reason, old_world=plan.old_world,
                    new_world=plan.world_size,
                    old_shape=plan.old_mesh_shape,
                    new_shape=plan.mesh_shape,
                    iteration=plan.iteration,
                    downtime_ms=round(float(downtime_ms), 3),
                    phases=dict(phases or {}),
                    redistribution=redist)
            # counters are read latest-value-as-total (report.py), so
            # emit the cumulative figures, not the per-event deltas
            tm.counter("elastic/resizes", self.resizes)
            tm.counter("elastic/downtime_ms",
                       round(self.downtime_ms, 3))
            tm.counter("elastic/redistributed_bytes",
                       self.redistributed_bytes)
            tm.flush()


def maybe_elastic_init_from_env():
    """The ``IMAGINAIRE_ELASTIC=1`` branch of
    ``mesh.maybe_init_distributed_from_env``: same ``IMAGINAIRE_DIST_*``
    contract, but the runtime comes up through ``raw_init`` (benign
    heartbeat callback, non-blocking teardown) so the process can
    survive — and perform — later resizes. Returns True when it ran."""
    n = os.environ.get("IMAGINAIRE_DIST_NUM_PROCESSES")
    if not n or int(n) <= 1:
        return False
    raw_init(os.environ.get("IMAGINAIRE_DIST_COORDINATOR"), int(n),
             int(os.environ.get("IMAGINAIRE_DIST_PROCESS_ID", "0")),
             settings=env_settings())
    return True


def env_settings():
    """Init-time knobs can't come from cfg (the runtime boots before
    the config loads on re-exec'd joiners) — the launcher forwards them
    through the environment, defaults otherwise."""
    s = elastic_settings({})
    for env, key, cast in (
            ("IMAGINAIRE_ELASTIC_HEARTBEAT_S", "heartbeat_interval_s",
             float),
            ("IMAGINAIRE_ELASTIC_MAX_MISSING", "max_missing_heartbeats",
             int),
            ("IMAGINAIRE_ELASTIC_INIT_TIMEOUT_S", "init_timeout_s",
             float)):
        raw = os.environ.get(env)
        if raw:
            try:
                s[key] = cast(raw)
            except ValueError:
                pass
    return s


# ------------------------------------------------------------- joiners

def request_join(logdir, nonce):
    """Joiner: announce this process wants in. Returns the request
    path. The master absorbs the nonce into the next grow plan."""
    join_dir = os.path.join(str(logdir), "elastic", JOIN_DIR)
    os.makedirs(join_dir, exist_ok=True)
    path = os.path.join(join_dir, f"{nonce}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"nonce": str(nonce), "time": time.time(),
                   "pid": os.getpid()}, f)
    os.replace(tmp, path)
    return path


def wait_for_join(logdir, nonce, timeout_s=600.0, poll_s=0.25):
    """Joiner: block until ``topology.json`` names this nonce a member,
    then point the ``IMAGINAIRE_DIST_*`` env contract at the agreed
    topology and return the plan (the caller inits through
    ``mesh.maybe_init_distributed_from_env`` exactly like a launch-time
    member, then adopts ``plan.barrier_epochs``)."""
    topo = os.path.join(str(logdir), "elastic", TOPOLOGY_FILE)
    deadline = time.time() + float(timeout_s)
    nonce = str(nonce)
    while True:
        plan = None
        try:
            with open(topo) as f:
                plan = ResizePlan.from_json(f.read())
        except (OSError, ValueError, KeyError):
            plan = None
        if plan is not None:
            my_id = plan.process_id_of(nonce)
            if my_id is not None:
                os.environ["IMAGINAIRE_DIST_COORDINATOR"] = \
                    plan.coordinator
                os.environ["IMAGINAIRE_DIST_NUM_PROCESSES"] = str(
                    plan.world_size)
                os.environ["IMAGINAIRE_DIST_PROCESS_ID"] = str(my_id)
                os.environ["IMAGINAIRE_ELASTIC"] = "1"
                os.environ["IMAGINAIRE_ELASTIC_GENERATION"] = str(
                    plan.generation)
                logger.info("elastic: join granted — process %d of %d, "
                            "generation %d, coordinator %s", my_id,
                            plan.world_size, plan.generation,
                            plan.coordinator)
                return plan
        if time.time() >= deadline:
            raise TimeoutError(
                f"elastic: join request {nonce!r} not granted within "
                f"{timeout_s:g}s (topology: {topo})")
        time.sleep(float(poll_s))
