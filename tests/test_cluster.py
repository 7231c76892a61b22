"""Pod-grade fault tolerance (ISSUE 8): timed collectives, preemption
voting, resume consensus, per-host runstate, distributed chaos, the
--hosts health gate, and the collectives/eval single- vs multi-process
branches.

The cluster protocol logic runs against an in-memory fake of the jax
coordination-service KV client (``cluster.set_client_for_testing``) so
its barrier/vote/consensus semantics — including who gets NAMED on a
timeout — are tested without spawning a real 2-process pod; the dryrun
``spade_pod`` leg covers the real-pod end-to-end path.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.resilience import cluster
from imaginaire_tpu.resilience.cluster import ClusterDesyncError


class FakeBarrierTimeout(Exception):
    pass


class FakeClient:
    """In-memory stand-in for jaxlib's DistributedRuntimeClient KV/
    barrier surface. ``present`` lists the process indices that DO
    arrive at barriers; everyone else is 'stalled'."""

    def __init__(self, n, present=None):
        self.n = n
        self.present = set(range(n)) if present is None else set(present)
        self.kv = {}
        self.barrier_calls = []

    # --- KV surface ---------------------------------------------------
    def key_value_set(self, key, value, allow_overwrite=False):
        if key in self.kv and not allow_overwrite:
            raise RuntimeError(f"key exists: {key}")
        self.kv[key] = value

    def key_value_dir_get(self, prefix):
        return sorted((k, v) for k, v in self.kv.items()
                      if k.startswith(prefix))

    def key_value_delete(self, key):
        self.kv.pop(key, None)

    # --- barrier surface ----------------------------------------------
    def wait_at_barrier(self, barrier_id, timeout_ms, process_ids=None):
        self.barrier_calls.append(barrier_id)
        if self.present != set(range(self.n)):
            raise FakeBarrierTimeout(
                f"DEADLINE_EXCEEDED: Barrier timed out. Id: "
                f"{barrier_id}")


@pytest.fixture
def two_proc_client():
    """Install a 2-process fake topology (this process is p0); always
    uninstalls, so no test leaks a fake pod into the suite."""
    client = FakeClient(2)
    cluster.set_client_for_testing(client, process_index=0,
                                   process_count=2)
    yield client
    cluster.set_client_for_testing(None)


@pytest.fixture(autouse=True)
def _reset_cluster():
    cluster._BARRIER_EPOCH.clear()
    yield
    cluster.set_client_for_testing(None)
    cluster._SETTINGS = None
    cluster._BARRIER_EPOCH.clear()


# ------------------------------------------------------ timed barrier


class TestTimedBarrier:
    def test_single_process_noop(self):
        # no client, one process: must not raise or RPC
        cluster.set_client_for_testing(None)
        cluster.timed_barrier("anything", timeout_s=0.01)

    def test_all_present_passes_and_cleans_arrival(self, two_proc_client):
        cluster.timed_barrier("ckpt_enter", timeout_s=5, tag="t0")
        assert two_proc_client.barrier_calls == [
            "barrier/ckpt_enter:t0"]
        # the arrival key is retired after the rendezvous
        assert not [k for k in two_proc_client.kv
                    if k.startswith("arrive/ckpt_enter:t0/")]

    def test_timeout_names_absent_process(self, two_proc_client):
        two_proc_client.present = {0}  # p1 never arrives
        # simulate p1 having *not* written its arrival key: only ours
        with pytest.raises(ClusterDesyncError) as err:
            cluster.timed_barrier("ckpt_enter", timeout_s=0.05,
                                  tag="t1")
        assert err.value.absent == (1,)
        assert "process(es) [1] absent" in str(err.value)
        assert "'ckpt_enter'" in str(err.value)

    def test_unique_epoch_per_invocation(self, two_proc_client):
        cluster.timed_barrier("sync", timeout_s=5)
        cluster.timed_barrier("sync", timeout_s=5)
        assert len(set(two_proc_client.barrier_calls)) == 2

    def test_desync_emits_telemetry(self, two_proc_client, tmp_path):
        from imaginaire_tpu import telemetry
        from imaginaire_tpu.telemetry.report import load_events

        tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                                 sinks=("jsonl",))
        two_proc_client.present = {0}
        with pytest.raises(ClusterDesyncError):
            cluster.timed_barrier("vote", timeout_s=0.05, tag="t")
        tm.shutdown()
        events = load_events(str(tmp_path / "telemetry.jsonl"))
        metas = [e for e in events
                 if e.get("name") == "resilience/cluster_desync"]
        assert metas and metas[0]["absent"] == [1]
        assert any(e.get("name") == "resilience/cluster_desyncs"
                   for e in events if e.get("kind") == "counter")


# ------------------------------------------------- preemption voting


class TestPreemptionVote:
    def test_single_process_identity(self):
        assert cluster.coordinate_preemption(1, False) is False
        assert cluster.coordinate_preemption(1, True) is True

    def test_peer_flag_propagates(self, two_proc_client):
        # p1 voted 1 at this step before us (the SIGTERM'd host)
        two_proc_client.kv["psync/5/p1"] = "1"
        assert cluster.coordinate_preemption(5, False) is True

    def test_no_flags_no_drain(self, two_proc_client):
        two_proc_client.kv["psync/7/p1"] = "0"
        assert cluster.coordinate_preemption(7, False) is False

    def test_local_flag_published(self, two_proc_client):
        two_proc_client.kv["psync/9/p1"] = "0"
        assert cluster.coordinate_preemption(9, True) is True
        assert two_proc_client.kv["psync/9/p0"] == "1"

    def test_stalled_peer_raises_named(self, two_proc_client):
        two_proc_client.present = {0}
        with pytest.raises(ClusterDesyncError) as err:
            cluster.coordinate_preemption(3, False, timeout_s=0.05)
        assert err.value.absent == (1,)

    def test_old_votes_retired(self, two_proc_client):
        two_proc_client.kv["psync/1/p0"] = "0"
        two_proc_client.kv["psync/3/p1"] = "0"
        cluster.coordinate_preemption(3, False)
        assert "psync/1/p0" not in two_proc_client.kv


# ---------------------------------------------------- resume consensus


class TestResumeConsensus:
    def test_single_process_identity(self):
        consensus, votes = cluster.agree_min("resume", 7, extra="ck7")
        assert consensus == 7
        assert votes == {0: (7, "ck7")}

    def test_min_over_verified_wins(self, two_proc_client):
        # p1 only verified iteration 4 (its copy of 6 failed integrity)
        def seed_peer(prefix):
            for k in list(two_proc_client.kv):
                pass
        # peer's vote appears under the epoch the call will use (0)
        two_proc_client.kv["agree/resume/0/p1"] = json.dumps(
            {"v": 4, "x": "ck4"})
        consensus, votes = cluster.agree_min("resume", 6, extra="ck6")
        assert consensus == 4
        assert votes[1] == (4, "ck4")
        assert votes[0] == (6, "ck6")

    def test_nothing_local_follows_peers(self, two_proc_client):
        two_proc_client.kv["agree/resume/0/p1"] = json.dumps(
            {"v": 2, "x": "ck2"})
        consensus, votes = cluster.agree_min("resume", -1, extra=None)
        assert consensus == 2

    def test_nobody_has_anything(self, two_proc_client):
        two_proc_client.kv["agree/resume/0/p1"] = json.dumps(
            {"v": -1, "x": None})
        consensus, _ = cluster.agree_min("resume", -1)
        assert consensus == -1


# --------------------------------------------------------- heartbeats


class TestHeartbeats:
    def test_peer_status_single_process_none(self):
        assert cluster.peer_status() is None
        assert cluster.stalled_peers() == []

    def test_stalled_peer_named(self, two_proc_client):
        import time

        now = time.time()
        two_proc_client.kv["hb/p0"] = json.dumps({"t": now, "step": 9})
        two_proc_client.kv["hb/p1"] = json.dumps({"t": now - 300,
                                                  "step": 4})
        status = cluster.peer_status(stale_after_s=60)
        assert status[0]["stalled"] is False
        assert status[1]["stalled"] is True
        assert cluster.stalled_peers(stale_after_s=60) == [1]

    def test_missing_heartbeat_is_stalled(self, two_proc_client):
        import time

        two_proc_client.kv["hb/p0"] = json.dumps({"t": time.time(),
                                                  "step": 1})
        status = cluster.peer_status(stale_after_s=60)
        assert status[1]["t"] is None and status[1]["stalled"] is True

    def test_watchdog_dump_names_stalled_peer(self, two_proc_client,
                                              capsys):
        import time

        from imaginaire_tpu import telemetry

        two_proc_client.kv["hb/p0"] = json.dumps({"t": time.time(),
                                                  "step": 3})
        two_proc_client.kv["hb/p1"] = json.dumps({"t": time.time() - 99,
                                                  "step": 1})
        cluster.configure({"resilience": {"cluster": {
            "enabled": True, "heartbeat_timeout_s": 10}}})
        tm = telemetry.Telemetry(enabled=True)
        tm.dump_stacks("test stall")
        err = capsys.readouterr().err
        assert "peer heartbeats" in err
        assert "likely stalled process(es): [1]" in err

    def test_dump_header_carries_process_identity(self, capsys):
        from imaginaire_tpu import telemetry

        tm = telemetry.Telemetry(enabled=True)
        tm.dump_stacks("header test")
        assert "[p0/1]" in capsys.readouterr().err


# -------------------------------------------------- distributed chaos


class TestDistributedChaos:
    def _monkey(self, settings):
        from imaginaire_tpu.resilience.chaos import ChaosMonkey, \
            chaos_settings

        base = chaos_settings({"chaos": dict({"enabled": True},
                                             **settings)})
        return ChaosMonkey(base)

    def test_settings_parse(self):
        from imaginaire_tpu.resilience.chaos import chaos_settings

        s = chaos_settings({"chaos": {"enabled": True, "kill_at_step": 2,
                                      "kill_process_index": 1,
                                      "stall_at_step": 3,
                                      "stall_process_index": 1,
                                      "stall_duration_s": 0.01}})
        assert s["kill_at_step"] == 2 and s["kill_process_index"] == 1
        assert s["stall_at_step"] == 3 and s["stall_duration_s"] == 0.01

    def test_kill_only_fires_on_matching_process(self, monkeypatch):
        monkey = self._monkey({"kill_at_step": 2,
                               "kill_process_index": 1})
        killed = []
        monkeypatch.setattr(os, "kill",
                            lambda pid, sig: killed.append(sig))
        monkey.maybe_kill(2)  # this process is index 0, target is 1
        assert killed == []
        monkeypatch.setattr(type(monkey), "_my_process_index",
                            staticmethod(lambda: 1))
        monkey.maybe_kill(2)
        assert len(killed) == 1

    def test_stall_sleeps_matching_process_once(self, monkeypatch):
        import time as time_mod

        monkey = self._monkey({"stall_at_step": 3,
                               "stall_process_index": 0,
                               "stall_duration_s": 123.0})
        slept = []
        monkeypatch.setattr(time_mod, "sleep",
                            lambda s: slept.append(s))
        monkey.maybe_stall(2)
        assert slept == []
        monkey.maybe_stall(3)
        assert slept == [123.0]
        monkey.maybe_stall(3)  # one-shot
        assert slept == [123.0]

    def test_null_chaos_has_new_hooks(self):
        from imaginaire_tpu.resilience import chaos as chaos_mod

        null = chaos_mod._NullChaos()
        null.maybe_kill(1)
        null.maybe_stall(1)


# ----------------------------------------------- per-host runstate


class TestPerHostRunstate:
    def test_paths(self):
        from imaginaire_tpu.resilience.runstate import runstate_path

        assert runstate_path("/x/ck") == "/x/ck.runstate.json"
        assert runstate_path("/x/ck", 3) == "/x/ck.runstate.p3.json"

    def test_nonzero_process_writes_own_sidecar(self, tmp_path,
                                                monkeypatch):
        from imaginaire_tpu.parallel import mesh
        from imaginaire_tpu.resilience import runstate

        monkeypatch.setattr(mesh, "get_rank", lambda: 2)
        ck = str(tmp_path / "ck")
        rs = runstate.build_runstate(1, 5, 2, monitor={"m": 1})
        path = runstate.write_runstate(ck, rs)
        assert path.endswith(".runstate.p2.json")
        got = runstate.read_runstate(ck, process_index=2)
        assert got["iteration"] == 5 and got["monitor"] == {"m": 1}

    def test_missing_per_host_falls_back_to_master(self, tmp_path):
        from imaginaire_tpu.resilience import runstate

        ck = str(tmp_path / "ck")
        rs = runstate.build_runstate(0, 3, 1)
        with open(ck + ".runstate.json", "w") as f:
            json.dump(rs, f)
        got = runstate.read_runstate(ck, process_index=4)
        assert got["iteration"] == 3

    def test_quarantine_moves_per_host_sidecars(self, tmp_path,
                                                monkeypatch):
        from imaginaire_tpu.parallel import mesh
        from imaginaire_tpu.resilience.integrity import (
            quarantine_checkpoint,
            sidecar_files,
        )

        # a live 3-process world: p1/p2 sidecars travel with the
        # quarantine, but a p5 sidecar is an elastic-shrink orphan
        # (ISSUE 11) and must be left behind for GC
        monkeypatch.setattr(mesh, "get_world_size", lambda: 3)
        ck = tmp_path / "epoch_00000_iteration_000000002_checkpoint"
        ck.mkdir()
        (ck / "data").write_bytes(b"x" * 64)
        for suffix in (".runstate.json", ".runstate.p1.json",
                       ".runstate.p2.json", ".runstate.p5.json",
                       ".integrity.json"):
            (tmp_path / (ck.name + suffix)).write_text("{}")
        assert len(sidecar_files(str(ck))) == 5
        target = quarantine_checkpoint(str(ck), reason="test")
        assert target and target.endswith(".corrupt")
        assert os.path.exists(target + ".runstate.p1.json")
        assert os.path.exists(target + ".runstate.p2.json")
        assert not os.path.exists(str(ck) + ".runstate.p1.json")
        # the orphan stayed put and did NOT follow the rename
        assert os.path.exists(str(ck) + ".runstate.p5.json")
        assert not os.path.exists(target + ".runstate.p5.json")


# ------------------------------- collectives: single vs multi-process


class TestCollectivesBranches:
    def test_single_process_host_all_gather_identity(self):
        from imaginaire_tpu.parallel import collectives

        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        assert collectives.host_all_gather(x) is x
        assert float(collectives.host_psum(np.float32(3.0))) == 3.0
        collectives.barrier("noop")  # single-process: no-op, no raise

    def test_multi_process_barrier_routes_through_cluster(
            self, two_proc_client, monkeypatch):
        from imaginaire_tpu.parallel import collectives

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        collectives.barrier("gather", timeout_s=5)
        assert any(b.startswith("barrier/gather")
                   for b in two_proc_client.barrier_calls)

    def test_multi_process_gather_timeout_names_process(
            self, two_proc_client, monkeypatch):
        from imaginaire_tpu.parallel import collectives

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        two_proc_client.present = {0}
        with pytest.raises(ClusterDesyncError) as err:
            collectives.host_all_gather(np.zeros(2), timeout_s=0.05)
        assert err.value.absent == (1,)

    def test_pmean_psum_in_graph(self):
        # the in-graph collectives stay pure XLA (no host rendezvous)
        from imaginaire_tpu.parallel import collectives, shard_map
        from jax.sharding import PartitionSpec as P

        from imaginaire_tpu.parallel.mesh import create_mesh

        mesh = create_mesh(("data",), devices=jax.devices("cpu")[:4])
        x = jnp.arange(8, dtype=jnp.float32)
        f = shard_map(lambda v: collectives.psum(jnp.sum(v)),
                      mesh=mesh, in_specs=(P("data"),), out_specs=P())
        assert float(jax.jit(f)(x)) == float(jnp.sum(x))


# -------------------------- multi-process batch assembly (data path)


class TestProcessLocalBatch:
    def test_assembles_committed_global_batch(self):
        # single-process call of the multi-process assembly helper:
        # local data IS the global batch, so it must equal the
        # device_put path bit for bit while landing committed on 'data'
        from imaginaire_tpu.parallel.mesh import create_mesh
        from imaginaire_tpu.parallel.sharding import (
            place_process_local_batch,
        )

        mesh = create_mesh(("data",), devices=jax.devices("cpu")[:4])
        batch = {"images": np.random.RandomState(0)
                 .rand(8, 4, 4, 3).astype(np.float32),
                 "scalar": np.float32(3.0)}
        placed = place_process_local_batch(batch, mesh)
        assert placed["images"].sharding.spec[0] == "data"
        assert placed["images"].committed
        np.testing.assert_array_equal(np.asarray(placed["images"]),
                                      batch["images"])
        # indivisible/scalar leaves replicate
        assert placed["scalar"].sharding.spec == ()

    def test_indivisible_leading_dim_replicates(self):
        from imaginaire_tpu.parallel.mesh import create_mesh
        from imaginaire_tpu.parallel.sharding import (
            place_process_local_batch,
        )

        mesh = create_mesh(("data",), devices=jax.devices("cpu")[:4])
        batch = {"odd": np.ones((3, 2), np.float32)}
        placed = place_process_local_batch(batch, mesh)
        assert placed["odd"].sharding.spec == ()


# --------------------------------- eval process-strided index split


class _FakeVideoDataset:
    def __init__(self, n):
        self.n = n
        self.selected = []

    def num_inference_sequences(self):
        return self.n

    def set_inference_sequence_idx(self, idx):
        self.selected.append(idx)


class _FakeVideoLoader:
    def __init__(self, dataset):
        self.dataset = dataset

    def __iter__(self):
        return iter(())  # no batches: only the index split is under test


class TestVideoEvalSharding:
    def _run(self, monkeypatch, n_seq, rank, world, sample_size=None):
        from imaginaire_tpu.evaluation.common import (
            get_video_activations,
        )

        monkeypatch.setattr(jax, "process_index", lambda: rank)
        monkeypatch.setattr(jax, "process_count", lambda: world)
        dataset = _FakeVideoDataset(n_seq)
        get_video_activations(_FakeVideoLoader(dataset), "images",
                              "fake_images", trainer=None,
                              extractor=None, sample_size=sample_size)
        return dataset.selected

    def test_single_process_sees_all(self, monkeypatch):
        assert self._run(monkeypatch, 5, 0, 1) == [0, 1, 2, 3, 4]

    def test_strided_split_across_processes(self, monkeypatch):
        assert self._run(monkeypatch, 10, 1, 4) == [1, 5, 9]
        assert self._run(monkeypatch, 10, 3, 4) == [3, 7]

    def test_sample_size_caps_total_before_sharding(self, monkeypatch):
        # 4 sequences over 2 processes: each evaluates 2, not 4
        assert self._run(monkeypatch, 10, 0, 2, sample_size=4) == [0, 2]
        assert self._run(monkeypatch, 10, 1, 2, sample_size=4) == [1, 3]


# ----------------------------------------- check_run_health --hosts


_EVENT = {"kind": "counter", "name": "perf/imgs_per_sec", "value": 1.0,
          "step": 1, "t": 0.0}
_BAD = {"kind": "meta", "name": "nonfinite", "step": 3, "t": 1.0,
        "update": "G", "culprit_terms": ["gan"],
        "culprit_modules": ["head"]}


def _write_jsonl(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


class TestHostsGate:
    def _gate(self, rundir, *extra):
        script = os.path.join(os.path.dirname(__file__), "..",
                              "scripts", "check_run_health.py")
        return subprocess.run(
            [sys.executable, script, str(rundir), "--hosts", *extra],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def test_all_healthy_passes(self, tmp_path):
        _write_jsonl(tmp_path / "telemetry.jsonl.p0", [_EVENT])
        _write_jsonl(tmp_path / "telemetry.jsonl.p1", [_EVENT])
        r = self._gate(tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "all 2 process file(s) healthy" in r.stdout

    def test_any_process_failing_fails_pod(self, tmp_path):
        _write_jsonl(tmp_path / "telemetry.jsonl.p0", [_EVENT])
        _write_jsonl(tmp_path / "telemetry.jsonl.p1", [_EVENT, _BAD])
        r = self._gate(tmp_path)
        assert r.returncode == 1
        assert "[p1]: FAIL" in r.stdout
        assert "non-finite" in r.stdout

    def test_desync_event_fails_gate(self, tmp_path):
        desync = {"kind": "meta", "name": "resilience/cluster_desync",
                  "barrier": "psync:3", "absent": [1], "arrived": [0],
                  "process": 0, "t": 2.0}
        _write_jsonl(tmp_path / "telemetry.jsonl.p0", [_EVENT, desync])
        _write_jsonl(tmp_path / "telemetry.jsonl.p1", [_EVENT])
        r = self._gate(tmp_path)
        assert r.returncode == 1
        assert "desync" in r.stdout

    def test_expect_hosts_catches_missing_log(self, tmp_path):
        _write_jsonl(tmp_path / "telemetry.jsonl.p0", [_EVENT])
        r = self._gate(tmp_path, "--expect-hosts", "2")
        assert r.returncode == 1
        assert "expected >= 2" in r.stdout

    def test_json_mode(self, tmp_path):
        _write_jsonl(tmp_path / "telemetry.jsonl.p0", [_EVENT])
        _write_jsonl(tmp_path / "telemetry.jsonl.p1", [_EVENT, _BAD])
        r = self._gate(tmp_path, "--json")
        verdict = json.loads(r.stdout)
        assert verdict["healthy"] is False
        assert verdict["hosts"]["p1"]["healthy"] is False


# ------------------------------------- loader: equal per-host epochs


class TestLoaderEqualShards:
    def test_odd_dataset_truncates_to_common_floor(self, monkeypatch):
        from imaginaire_tpu.data.loader import DataLoader
        from imaginaire_tpu.parallel import mesh

        class _DS:
            def __len__(self):
                return 5

            def __getitem__(self, i):
                return {"x": np.full((2,), i, np.float32)}

        lengths = {}
        for rank in (0, 1):
            monkeypatch.setattr(mesh, "get_rank", lambda r=rank: r)
            monkeypatch.setattr(mesh, "get_world_size", lambda: 2)
            import imaginaire_tpu.data.loader as loader_mod

            monkeypatch.setattr(loader_mod, "get_rank", lambda r=rank: r)
            monkeypatch.setattr(loader_mod, "get_world_size", lambda: 2)
            dl = DataLoader(_DS(), batch_size=1, shuffle=False)
            batches = list(dl)
            lengths[rank] = len(batches)
        # 5 items over 2 hosts: both MUST see 2 batches — a one-batch
        # difference deadlocks a pod at the epoch boundary
        assert lengths == {0: 2, 1: 2}

    def test_strided_union_covers_prefix(self, monkeypatch):
        import imaginaire_tpu.data.loader as loader_mod
        from imaginaire_tpu.data.loader import DataLoader

        class _DS:
            def __len__(self):
                return 5

            def __getitem__(self, i):
                return {"x": np.full((1,), i, np.float32)}

        seen = []
        for rank in (0, 1):
            monkeypatch.setattr(loader_mod, "get_rank", lambda r=rank: r)
            monkeypatch.setattr(loader_mod, "get_world_size", lambda: 2)
            dl = DataLoader(_DS(), batch_size=1, shuffle=False)
            seen.extend(int(b["x"][0, 0]) for b in dl)
        assert sorted(seen) == [0, 1, 2, 3]  # item 4 dropped evenly


# -------------------------------------------- heartbeat epoch scoping


class TestHeartbeatEpochScoping:
    """ISSUE 13 satellite: after a resize the membership changes, and a
    departed host's final stamp must not report it as stalled forever —
    heartbeat keys are scoped to the membership epoch."""

    @pytest.fixture(autouse=True)
    def _epoch_reset(self):
        yield
        cluster.set_membership_epoch(None)

    def test_heartbeat_key_forms(self):
        assert cluster.heartbeat_key(2, epoch=0) == "hb/p2"
        assert cluster.heartbeat_key(2, epoch=3) == "hb/e3/p2"
        cluster.set_membership_epoch(1)
        assert cluster.heartbeat_key(0) == "hb/e1/p0"
        cluster.set_membership_epoch(None)
        assert cluster.heartbeat_key(0) == "hb/p0"

    def test_epoch_from_env(self, monkeypatch):
        monkeypatch.setenv("IMAGINAIRE_ELASTIC_GENERATION", "2")
        cluster.set_membership_epoch(None)
        assert cluster.membership_epoch() == 2
        assert cluster.heartbeat_key(1) == "hb/e2/p1"

    def test_departed_hosts_stale_stamp_ignored(self, two_proc_client):
        import time

        now = time.time()
        # the dead host's LAST stamp, written before the shrink under
        # the old membership — without scoping it reads stalled forever
        two_proc_client.kv["hb/p1"] = json.dumps({"t": now - 9999,
                                                  "step": 4})
        two_proc_client.kv["hb/e1/p0"] = json.dumps({"t": now,
                                                     "step": 9})
        two_proc_client.kv["hb/e1/p1"] = json.dumps({"t": now,
                                                     "step": 9})
        cluster.set_membership_epoch(1)
        status = cluster.peer_status(stale_after_s=60)
        assert status[0]["stalled"] is False
        assert status[1]["stalled"] is False
        assert cluster.stalled_peers(stale_after_s=60) == []

    def test_epoch_entries_invisible_at_epoch_zero(self,
                                                   two_proc_client):
        import time

        now = time.time()
        two_proc_client.kv["hb/p0"] = json.dumps({"t": now, "step": 1})
        # a fresh stamp under a future epoch is NOT this membership's
        two_proc_client.kv["hb/e1/p1"] = json.dumps({"t": now,
                                                     "step": 1})
        status = cluster.peer_status(stale_after_s=60)
        assert status[1]["t"] is None and status[1]["stalled"] is True

    def test_old_epoch_invisible_at_new_epoch(self, two_proc_client):
        import time

        now = time.time()
        two_proc_client.kv["hb/e1/p0"] = json.dumps({"t": now,
                                                     "step": 2})
        two_proc_client.kv["hb/e1/p1"] = json.dumps({"t": now,
                                                     "step": 2})
        cluster.set_membership_epoch(2)
        status = cluster.peer_status(stale_after_s=60)
        assert status[0]["t"] is None and status[1]["t"] is None


class TestRankCacheSurvivesTeardown:
    def test_get_rank_falls_back_in_teardown_window(self, monkeypatch):
        from imaginaire_tpu.parallel import mesh

        assert mesh.get_rank() == 0  # primes the caches
        assert mesh.get_world_size() == 1

        def _boom():
            raise RuntimeError("Unable to initialize backend 'cpu'")

        monkeypatch.setattr(mesh.jax, "process_index", _boom)
        monkeypatch.setattr(mesh.jax, "process_count", _boom)
        # the elastic teardown window (ISSUE 13): the backend cannot
        # rebuild, but master-gated prints must still resolve identity
        assert mesh.get_rank() == 0
        assert mesh.is_master() is True
        assert mesh.get_world_size() >= 1
