"""Fault-tolerance layer (ISSUE 7, ``imaginaire_tpu/resilience/``):
bounded retries, checkpoint integrity + quarantine + last-good
fallback, retention GC, preemption guard, chaos injection, and the
bit-exact resume contract (straight-through N steps vs kill-at-k +
resume must produce identical params/opt/EMA)."""

import json
import os
import signal

import jax
import numpy as np
import pytest

import __graft_entry__ as ge
from imaginaire_tpu import resilience, telemetry
from imaginaire_tpu.resilience import chaos as chaos_mod
from imaginaire_tpu.resilience.integrity import (
    CheckpointIntegrityError,
    tree_checksums,
    verify_tree,
)
from imaginaire_tpu.utils import checkpoint as ckpt_lib


# ------------------------------------------------------------------ retry


class TestRetry:
    def test_recovers_after_transient_failures(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert resilience.retry_call(flaky, label="t",
                                     backoff_s=0.0) == "ok"
        assert len(calls) == 3

    def test_exhausted_budget_reraises(self):
        def always():
            raise OSError("down")

        with pytest.raises(OSError, match="down"):
            resilience.retry_call(always, label="t", retries=2,
                                  backoff_s=0.0)

    def test_non_retryable_raises_immediately(self):
        calls = []

        def corrupt():
            calls.append(1)
            raise ValueError("not transient")

        with pytest.raises(ValueError):
            resilience.retry_call(corrupt, label="t", backoff_s=0.0)
        assert len(calls) == 1

    def test_backoff_doubles_and_caps(self):
        sleeps = []

        def always():
            raise OSError("down")

        with pytest.raises(OSError):
            resilience.retry_call(always, label="t", retries=4,
                                  backoff_s=0.1, max_backoff_s=0.25,
                                  _sleep=sleeps.append)
        assert sleeps == [0.1, 0.2, 0.25]

    def test_retries_counted_in_telemetry(self, tmp_path):
        tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                                 sinks=("jsonl",))
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise OSError("transient")

        resilience.retry_call(flaky, label="unit", backoff_s=0.0)
        tm.shutdown()
        events = [json.loads(line) for line in
                  open(tmp_path / "telemetry.jsonl")]
        assert any(e.get("name") == "resilience/retry/unit"
                   for e in events)


# -------------------------------------------------------------- integrity


def _state(iteration=1, scale=1.0):
    return {"state": {"w": np.arange(16.0).reshape(8, 2) * scale,
                      "b": np.ones((3,), np.float32)},
            "meta": {"epoch": 0, "iteration": iteration}}


class TestIntegrity:
    def test_checksum_roundtrip(self):
        s = _state()
        record = tree_checksums(s)
        assert record["n_leaves"] == 4
        assert verify_tree(s, record) is not None

    def test_flipped_byte_detected(self):
        s = _state()
        record = tree_checksums(s)
        s["state"]["w"][3, 1] += 1e-7
        with pytest.raises(CheckpointIntegrityError, match="crc"):
            verify_tree(s, record)

    def test_structural_rename_falls_back_to_multiset(self):
        s = _state()
        record = tree_checksums(s)
        renamed = {"other": {"x": s["state"]["w"], "y": s["state"]["b"]},
                   "meta": s["meta"]}
        assert verify_tree(renamed, record) is not None  # same bytes
        renamed["other"]["x"] = renamed["other"]["x"] + 1.0
        with pytest.raises(CheckpointIntegrityError, match="multiset"):
            verify_tree(renamed, record)

    def test_legacy_without_record_is_noop(self):
        assert verify_tree(_state(), None) is None
        assert verify_tree(_state(), {}) is None

    def test_save_writes_sidecar_and_load_verifies(self, tmp_path):
        s = _state()
        path = ckpt_lib.save_checkpoint(str(tmp_path), s, 0, 1)
        assert os.path.exists(path + ".integrity.json")
        restored = ckpt_lib.load_checkpoint(path, target=s)
        np.testing.assert_array_equal(restored["state"]["w"],
                                      s["state"]["w"])

    def test_corrupt_checkpoint_fails_verification(self, tmp_path):
        s = _state()
        path = ckpt_lib.save_checkpoint(str(tmp_path), s, 0, 1)
        # flip bytes in EVERY data file so the corruption hits array
        # bytes regardless of orbax's on-disk layout
        for dirpath, _, files in os.walk(path):
            for name in files:
                if "METADATA" not in name:
                    chaos_mod.corrupt_checkpoint_bytes(
                        os.path.join(dirpath, name))
        with pytest.raises(Exception) as excinfo:
            ckpt_lib.load_checkpoint(path, target=s)
        # either the restore itself explodes or the crc catches it —
        # both are detection, silence is the only failure
        assert excinfo.value is not None

    def test_file_layer_blocks_deserialization_of_corrupt_bytes(
            self, tmp_path):
        """Corruption must be caught by the raw-file digest pass BEFORE
        orbax/tensorstore decode anything: decompressing corrupt chunks
        is a heap hazard (observed as NaN params + delayed SIGSEGV),
        not just a wrong answer."""
        s = _state()
        path = ckpt_lib.save_checkpoint(str(tmp_path), s, 0, 1)
        integrity = ckpt_lib.read_integrity_sidecar(path)
        assert integrity and integrity.get("files"), \
            "file digests missing from the integrity sidecar"
        chaos_mod.corrupt_checkpoint_bytes(path)
        with pytest.raises(CheckpointIntegrityError,
                           match="refusing to deserialize"):
            ckpt_lib.load_checkpoint(path, target=s)

    def test_quarantine_renames_checkpoint_and_sidecars(self, tmp_path):
        s = _state()
        path = ckpt_lib.save_checkpoint(str(tmp_path), s, 0, 1)
        moved = resilience.quarantine_checkpoint(path)
        assert moved == path + ".corrupt"
        assert not os.path.exists(path)
        assert os.path.exists(moved)
        assert os.path.exists(moved + ".integrity.json")
        # quarantined names never parse as resume candidates
        assert ckpt_lib.scan_checkpoints(str(tmp_path)) == []


# ----------------------------------------------------- fallback + pointer


class TestFallback:
    def test_pointer_to_missing_path_scans_logdir(self, tmp_path):
        s = _state()
        path = ckpt_lib.save_checkpoint(str(tmp_path), s, 0, 1)
        with open(tmp_path / "latest_checkpoint.txt", "w") as f:
            f.write("epoch_00000_iteration_000000099_checkpoint\n")
        assert ckpt_lib.latest_checkpoint_path(str(tmp_path)) == path

    def test_no_pointer_returns_none(self, tmp_path):
        ckpt_lib.save_checkpoint(str(tmp_path), _state(), 0, 1)
        os.remove(tmp_path / "latest_checkpoint.txt")
        assert ckpt_lib.latest_checkpoint_path(str(tmp_path)) is None

    def test_corrupt_pointed_falls_back_to_verifiable(self, tmp_path):
        s1, s2 = _state(1), _state(2, scale=2.0)
        p1 = ckpt_lib.save_checkpoint(str(tmp_path), s1, 0, 1)
        p2 = ckpt_lib.save_checkpoint(str(tmp_path), s2, 0, 2)
        for dirpath, _, files in os.walk(p2):
            for name in files:
                chaos_mod.corrupt_checkpoint_bytes(
                    os.path.join(dirpath, name))
        payload, path, fallbacks = ckpt_lib.load_latest_verified(
            str(tmp_path), target=s1)
        assert path == p1 and fallbacks == 1
        np.testing.assert_array_equal(payload["state"]["w"],
                                      s1["state"]["w"])
        assert any(".corrupt" in n for n in os.listdir(tmp_path))

    def test_all_corrupt_raises_instead_of_fresh_start(self, tmp_path):
        p1 = ckpt_lib.save_checkpoint(str(tmp_path), _state(), 0, 1)
        for dirpath, _, files in os.walk(p1):
            for name in files:
                chaos_mod.corrupt_checkpoint_bytes(
                    os.path.join(dirpath, name))
        with pytest.raises(RuntimeError, match="no verifiable"):
            ckpt_lib.load_latest_verified(str(tmp_path), target=_state())

    def test_fresh_logdir_resumes_nothing(self, tmp_path):
        payload, path, fallbacks = ckpt_lib.load_latest_verified(
            str(tmp_path))
        assert payload is None and path is None and fallbacks == 0

    def test_infra_error_raises_without_quarantine(self, tmp_path,
                                                   monkeypatch):
        # an XlaRuntimeError (gloo context timeout, wedged collective
        # layer — ISSUE 13) says nothing about the checkpoint's bytes:
        # quarantining on it would condemn every candidate in a healthy
        # logdir. It must propagate and leave the directory untouched.
        ckpt_lib.save_checkpoint(str(tmp_path), _state(), 0, 1)

        class XlaRuntimeError(Exception):
            pass

        def _boom(path, target=None, verify=True):
            raise XlaRuntimeError("DEADLINE_EXCEEDED: gloo context")

        monkeypatch.setattr(ckpt_lib, "load_checkpoint", _boom)
        with pytest.raises(XlaRuntimeError):
            ckpt_lib.load_latest_verified(str(tmp_path),
                                          target=_state())
        assert not any(".corrupt" in n for n in os.listdir(tmp_path))

    @pytest.mark.parametrize("fault", [ImportError, AttributeError,
                                       TypeError, NameError])
    def test_program_fault_raises_without_quarantine(self, tmp_path,
                                                     monkeypatch, fault):
        # a moved import or a changed signature inside the restore path
        # is the program's fault and says nothing about the bytes: the
        # scan must not rename healthy checkpoints `.corrupt` over it
        for it in (1, 2):
            ckpt_lib.save_checkpoint(str(tmp_path), _state(it), 0, it)

        def _boom(path, target=None, verify=True):
            raise fault("cannot import name 'checkpointer'")

        monkeypatch.setattr(ckpt_lib, "load_checkpoint", _boom)
        with pytest.raises(fault):
            ckpt_lib.load_latest_verified(str(tmp_path), target=_state())
        assert not any(".corrupt" in n for n in os.listdir(tmp_path))
        assert len(ckpt_lib.scan_checkpoints(str(tmp_path))) == 2

    def test_restore_syncs_with_no_other_process(self, tmp_path,
                                                 monkeypatch):
        # elastic restores are asymmetric (a joiner restores while the
        # survivors re-commit live state): a restore that waited on
        # orbax's untimed end-of-restore all-device sync would hang on
        # peers that never arrive. Pretend to be one of two processes
        # and make the sync fatal — the restore must never reach it.
        from jax.experimental import multihost_utils

        ckpt_lib.save_checkpoint(str(tmp_path), _state(), 0, 1)
        path = ckpt_lib.latest_checkpoint_path(str(tmp_path))

        def _boom(*a, **k):
            raise AssertionError("restore entered a cross-process sync")

        monkeypatch.setattr(jax, "process_count", lambda *a, **k: 2)
        monkeypatch.setattr(multihost_utils, "sync_global_devices", _boom)
        for target in (_state(), None):
            payload = ckpt_lib.load_checkpoint(path, target=target)
            np.testing.assert_array_equal(
                np.asarray(payload["state"]["w"]), _state()["state"]["w"])

    def test_async_save_sets_no_per_process_signal_key(self, tmp_path):
        # orbax's asynchronous directory creation signals peers through
        # KV keys suffixed with a PER-PROCESS operation counter; an
        # elastic joiner has a shorter save history than the survivors
        # and would wait on a key nobody sets. The async save must not
        # depend on any such key, however many saves came before.
        from orbax.checkpoint._src.futures import signaling_client

        client = signaling_client.get_signaling_client()
        keys = []
        orig = type(client).key_value_set

        def _record(self, key, value, **kw):
            keys.append(key)
            return orig(self, key, value, **kw)

        type(client).key_value_set = _record
        try:
            for it in (1, 2):
                ckpt_lib.save_checkpoint(str(tmp_path), _state(it), 0, it,
                                         async_save=True)
                ckpt_lib.wait_for_pending_checkpoint()
        finally:
            type(client).key_value_set = orig
        assert keys == []
        assert len(ckpt_lib.scan_checkpoints(str(tmp_path))) == 2


# ------------------------------------------------------------- retention


class TestRetentionGC:
    def test_max_to_keep_never_deletes_pointer_or_last_verified(
            self, tmp_path):
        for it in range(1, 6):
            ckpt_lib.save_checkpoint(str(tmp_path), _state(it), 0, it,
                                     max_to_keep=2)
        kept = [p for _, _, p in ckpt_lib.scan_checkpoints(str(tmp_path))]
        names = [os.path.basename(p) for p in kept]
        assert len(kept) == 2, names
        assert ckpt_lib.latest_checkpoint_path(str(tmp_path)) == kept[-1]

    def test_gc_protects_last_verifiable_over_window(self, tmp_path):
        p1 = ckpt_lib.save_checkpoint(str(tmp_path), _state(1), 0, 1)
        # later checkpoints saved WITHOUT checksums: p1 stays the only
        # verifiable fallback target and must survive the window
        for it in (2, 3, 4):
            ckpt_lib.save_checkpoint(str(tmp_path), _state(it), 0, it,
                                     max_to_keep=2, checksum=False)
        kept = [p for _, _, p in ckpt_lib.scan_checkpoints(str(tmp_path))]
        assert p1 in kept, [os.path.basename(p) for p in kept]

    def test_gc_event_emitted(self, tmp_path):
        tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                                 sinks=("jsonl",))
        for it in range(1, 5):
            ckpt_lib.save_checkpoint(str(tmp_path), _state(it), 0, it,
                                     max_to_keep=1)
        tm.shutdown()
        events = [json.loads(line) for line in
                  open(tmp_path / "telemetry.jsonl")]
        gc = [e for e in events if e.get("name") == "ckpt/gc"]
        assert gc and gc[-1]["deleted"]


# ------------------------------------------------------------ flow store


class TestFlowStoreQuarantine:
    def test_corrupt_shard_quarantined_once(self, tmp_path):
        from imaginaire_tpu.flow.cache import FlowCacheStore

        store = FlowCacheStore(str(tmp_path))
        flow = np.random.RandomState(0).randn(4, 4, 2).astype(np.float32)
        conf = np.ones((4, 4, 1), np.float32)
        store.put("a" * 40, flow, conf)
        shard = store.path("a" * 40)
        with open(shard, "wb") as f:
            f.write(b"garbage not an npz")
        assert store.get("a" * 40) is None
        assert store.corrupt_shards == 1
        assert os.path.exists(shard + ".corrupt")
        assert not os.path.exists(shard)  # never re-read every epoch
        assert store.get("a" * 40) is None  # plain miss now
        assert store.corrupt_shards == 1
        assert store.stats()["corrupt_shards"] == 1

    def test_transient_io_error_retries_to_hit(self, tmp_path, monkeypatch):
        from imaginaire_tpu.config import AttrDict
        from imaginaire_tpu.flow.cache import FlowCacheStore

        chaos_mod.configure(AttrDict(chaos={
            "enabled": True, "io_error_at_step": 0,
            "io_error_site": "flow_store"}))
        try:
            store = FlowCacheStore(str(tmp_path))
            flow = np.zeros((2, 2, 2), np.float32)
            store.put("b" * 40, flow, np.ones((2, 2, 1), np.float32))
            got = store.get("b" * 40)  # first read raises, retry lands
            assert got is not None
            assert store.hits == 1 and store.corrupt_shards == 0
        finally:
            chaos_mod.configure(None)


# ----------------------------------------------------------- chaos units


class TestChaos:
    def test_disabled_singleton_is_inert(self):
        chaos_mod.configure(None)
        monkey = chaos_mod.get()
        assert not monkey.enabled
        batch = {"images": np.zeros((1, 4, 4, 3), np.float32)}
        assert monkey.maybe_nan_batch(batch, 0) is batch
        monkey.maybe_io_error("flow_store")  # no raise

    def test_nan_batch_fires_once_at_step(self):
        from imaginaire_tpu.config import AttrDict

        chaos_mod.configure(AttrDict(chaos={"enabled": True,
                                            "nan_batch_at_step": 3}))
        try:
            monkey = chaos_mod.get()
            batch = {"images": np.zeros((1, 4, 4, 3), np.float32),
                     "label": np.ones((1, 4, 4, 2), np.float32)}
            assert monkey.maybe_nan_batch(batch, 2) is batch
            poisoned = monkey.maybe_nan_batch(batch, 3)
            assert np.isnan(np.asarray(poisoned["images"])).all()
            np.testing.assert_array_equal(poisoned["label"],
                                          batch["label"])
            # one-shot: a second visit to the same step passes through
            assert monkey.maybe_nan_batch(batch, 3) is batch
        finally:
            chaos_mod.configure(None)

    def test_corrupt_checkpoint_bytes_flips_largest_file(self, tmp_path):
        small = tmp_path / "a.bin"
        big = tmp_path / "b.bin"
        small.write_bytes(b"\x00" * 10)
        big.write_bytes(b"\x00" * 1000)
        hit = chaos_mod.corrupt_checkpoint_bytes(str(tmp_path))
        assert hit == str(big)
        assert big.read_bytes() != b"\x00" * 1000
        assert small.read_bytes() == b"\x00" * 10

    def test_sigterm_sets_guard_flag(self):
        guard = resilience.PreemptionGuard(deadline_s=0.0).install()
        try:
            assert not guard.triggered
            os.kill(os.getpid(), signal.SIGTERM)
            assert guard.triggered
            assert guard.signum == signal.SIGTERM
        finally:
            guard.uninstall()

    def test_deadline_timer_fires_without_exit(self):
        fired = []
        guard = resilience.PreemptionGuard(deadline_s=0.01,
                                           exit_on_deadline=False)
        guard._deadline_expired = lambda: fired.append(1)
        guard.install()
        try:
            os.kill(os.getpid(), signal.SIGTERM)
            import time

            time.sleep(0.1)
            assert guard.triggered
        finally:
            guard.uninstall()


# -------------------------------------------------------------- runstate


class TestRunstate:
    def test_roundtrip(self, tmp_path):
        path = str(tmp_path / "ckpt")
        rs = resilience.build_runstate(
            2, 17, 5, monitor={"dg_ratio_ewma": 1.5},
            telemetry_state={"ring": [0.1, 0.2], "ewma": 0.15,
                             "last_step": 17})
        assert resilience.write_runstate(path, rs)
        back = resilience.read_runstate(path)
        assert back["iteration"] == 17 and back["batch_in_epoch"] == 5
        assert back["monitor"]["dg_ratio_ewma"] == 1.5

    def test_missing_and_garbage_return_none(self, tmp_path):
        path = str(tmp_path / "ckpt")
        assert resilience.read_runstate(path) is None
        with open(path + ".runstate.json", "w") as f:
            f.write("{not json")
        assert resilience.read_runstate(path) is None

    def test_monitor_state_dict_roundtrip(self):
        from imaginaire_tpu.config import Config
        from imaginaire_tpu.diagnostics import HealthMonitor

        cfg = Config()
        a = HealthMonitor(cfg)
        a.dg_ratio_ewma = 2.5
        a.dg_breaches = 3
        a.skip_count = 1
        a.nonfinite_events = 2
        a._last_gan = {"G": 1.0, "D": 2.0}
        a.history.append({"step": 10, "kind": "G", "finite": True,
                          "health": {"x": 1.0}, "losses": {}})
        b = HealthMonitor(cfg)
        b.load_state_dict(a.state_dict())
        assert b.dg_ratio_ewma == 2.5 and b.dg_breaches == 3
        assert b.skip_count == 1 and b.nonfinite_events == 2
        assert list(b.history) == list(a.history)

    def test_telemetry_state_dict_roundtrip(self, tmp_path):
        tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                                 sinks=())
        tm.record_step(0.25, items=2, step=7)
        tm.record_step(0.35, items=2, step=8)
        state = tm.state_dict()
        assert state["ring"] == [0.25, 0.35] and state["last_step"] == 8
        tm2 = telemetry.configure(logdir=str(tmp_path), enabled=True,
                                  sinks=())
        tm2.load_state_dict(state)
        assert list(tm2._ring) == [0.25, 0.35]
        assert tm2.last_step == 8
        tm2.shutdown()


# -------------------------------------------------------- loader resume


class _IdxDataset:
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, idx):
        return {"x": np.asarray([idx], np.int64)}


class TestLoaderFastForward:
    @pytest.mark.parametrize("num_workers", [0, 2])
    def test_skips_exact_prefix(self, num_workers):
        from imaginaire_tpu.data.loader import DataLoader

        loader = DataLoader(_IdxDataset(12), batch_size=2, shuffle=True,
                            seed=3, num_workers=num_workers)
        loader.set_epoch(1)
        full = [b["x"].ravel().tolist() for b in loader]
        loader.fast_forward(2)
        skipped = [b["x"].ravel().tolist() for b in loader]
        assert skipped == full[2:]
        # one-shot: the next pass is full again
        assert len(list(loader)) == len(full)

    def test_prefetcher_delegates(self):
        from imaginaire_tpu.data.device_prefetch import DevicePrefetcher
        from imaginaire_tpu.data.loader import DataLoader

        loader = DataLoader(_IdxDataset(8), batch_size=2, shuffle=False,
                            num_workers=0)
        feed = DevicePrefetcher(loader)
        full = [np.asarray(b["x"]).ravel().tolist() for b in feed]
        feed.fast_forward(1)
        skipped = [np.asarray(b["x"]).ravel().tolist() for b in feed]
        assert skipped == full[1:]

    def test_fast_forward_past_epoch_yields_empty(self):
        from imaginaire_tpu.data.loader import DataLoader

        loader = DataLoader(_IdxDataset(4), batch_size=2, shuffle=False,
                            num_workers=0)
        loader.fast_forward(99)
        assert list(loader) == []


# ------------------------------------------------------------- the gate


class TestHealthGate:
    @staticmethod
    def _gate(events, **kwargs):
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "scripts"))
        from check_run_health import check_health

        from imaginaire_tpu.telemetry.report import summarize

        return check_health(summarize(events), **kwargs)

    def test_fallbacks_gated(self):
        events = [{"kind": "counter", "name": "resilience/ckpt_fallbacks",
                   "value": 1, "t": 0.0},
                  {"kind": "meta", "name": "ckpt/fallback", "t": 0.0,
                   "skipped": "x", "fallbacks": 1, "error": "crc"}]
        assert any("fallback" in f for f in self._gate(events))
        assert self._gate(events, max_fallbacks=1) == []

    def test_resume_divergence_always_fails(self):
        events = [{"kind": "meta",
                   "name": "resilience/resume_divergence", "t": 0.0,
                   "checkpoint_iteration": 6, "runstate_iteration": 4}]
        failures = self._gate(events, max_fallbacks=99)
        assert any("divergence" in f for f in failures)

    def test_retry_exhausted_fails(self):
        events = [{"kind": "meta", "name": "resilience/retry_exhausted",
                   "t": 0.0, "label": "flow_store", "attempts": 3}]
        assert any("exhausted" in f for f in self._gate(events))

    def test_clean_run_passes(self):
        events = [{"kind": "counter", "name": "resilience/retry/loader",
                   "value": 1, "t": 0.0},
                  {"kind": "meta", "name": "resilience/resume", "t": 0.0,
                   "runstate": True, "iteration": 4}]
        assert self._gate(events) == []

    def test_report_renders_resilience_section(self):
        from imaginaire_tpu.telemetry.report import render_report

        events = [{"kind": "counter", "name": "resilience/ckpt_fallbacks",
                   "value": 1, "t": 0.0, "step": 1},
                  {"kind": "meta", "name": "ckpt/fallback", "t": 0.0,
                   "skipped": "x", "fallbacks": 1, "error": "crc"}]
        report = render_report(events)
        assert "## resilience" in report and "fallback" in report


# ------------------------------------------------- resume equivalence


def _spade_trainer(tmp_path, logdir_name="log"):
    from imaginaire_tpu.registry import resolve

    cfg = ge._tiny_cfg()
    cfg.logdir = os.path.join(str(tmp_path), logdir_name)
    os.makedirs(cfg.logdir, exist_ok=True)
    cfg.trainer.model_average = True
    cfg.trainer.model_average_start_iteration = 1
    cfg.diagnostics.dg_ratio_warn_low = 0.0
    cfg.diagnostics.dg_ratio_warn_high = 1e9
    return resolve(cfg.trainer.type, "Trainer")(cfg), cfg


def _run_iters(trainer, batch, start, n):
    for i in range(start, start + n):
        data = trainer.start_of_iteration(batch, i)
        trainer.dis_update(data)
        trainer.gen_update(data)
        trainer.current_iteration = i + 1
    trainer.diag.drain(trainer)


def _assert_states_bit_identical(a, b, keys=("vars_G", "vars_D",
                                             "opt_G", "opt_D", "ema_G",
                                             "num_ema_updates", "step",
                                             "step_D")):
    for key in keys:
        sub_a = jax.device_get(a[key])
        sub_b = jax.device_get(b[key])
        flat_a = jax.tree_util.tree_flatten_with_path(sub_a)[0]
        flat_b = jax.tree_util.tree_flatten_with_path(sub_b)[0]
        assert len(flat_a) == len(flat_b), key
        for (path_a, leaf_a), (_, leaf_b) in zip(flat_a, flat_b):
            assert np.array_equal(np.asarray(leaf_a),
                                  np.asarray(leaf_b), equal_nan=True), \
                f"{key}{jax.tree_util.keystr(path_a)} diverged"


class TestResumeEquivalence:
    def test_spade_kill_at_k_resume_bit_identical(self, tmp_path):
        batch = jax.tree_util.tree_map(np.asarray,
                                       ge._tiny_batch(1, h=64, w=64))
        key = jax.random.PRNGKey(0)

        straight, _ = _spade_trainer(tmp_path, "straight")
        straight.init_state(key, batch)
        _run_iters(straight, batch, 0, 4)

        killed, _ = _spade_trainer(tmp_path, "killed")
        killed.init_state(key, batch)
        _run_iters(killed, batch, 0, 2)
        killed.save_checkpoint(0, 2)

        resumed, _ = _spade_trainer(tmp_path, "killed")
        resumed.init_state(jax.random.PRNGKey(99), batch)  # overwritten
        assert resumed.load_checkpoint()  # pointer discovery = resume
        assert resumed.current_iteration == 2
        _run_iters(resumed, batch, 2, 2)

        _assert_states_bit_identical(straight.state, resumed.state)

    def test_restored_state_is_device_committed(self, tmp_path):
        """Regression (pre-existing SIGSEGV the chaos leg surfaced):
        orbax restore hands back host numpy; the step programs DONATE
        their state argument, and donating a zero-copy numpy alias on
        the CPU backend is a use-after-free. load_checkpoint must hand
        the trainer device arrays, never raw numpy."""
        batch = jax.tree_util.tree_map(np.asarray,
                                       ge._tiny_batch(1, h=64, w=64))
        trainer, _ = _spade_trainer(tmp_path)
        trainer.init_state(jax.random.PRNGKey(0), batch)
        trainer.save_checkpoint(0, 1)
        fresh, _ = _spade_trainer(tmp_path)
        fresh.init_state(jax.random.PRNGKey(1), batch)
        assert fresh.load_checkpoint()
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                fresh.state)[0]:
            assert isinstance(leaf, jax.Array), \
                f"host-numpy leaf after restore: " \
                f"{jax.tree_util.keystr(path)} ({type(leaf)})"

    def test_runstate_sidecar_restores_monitor_and_offset(self, tmp_path):
        batch = jax.tree_util.tree_map(np.asarray,
                                       ge._tiny_batch(1, h=64, w=64))
        trainer, _ = _spade_trainer(tmp_path)
        trainer.init_state(jax.random.PRNGKey(0), batch)
        trainer.start_of_epoch(0)
        _run_iters(trainer, batch, 0, 2)
        trainer.diag.dg_ratio_ewma = 3.25
        path = trainer.save_checkpoint(0, 2)
        assert os.path.exists(path + ".runstate.json")

        fresh, _ = _spade_trainer(tmp_path)
        fresh.init_state(jax.random.PRNGKey(1), batch)
        assert fresh.load_checkpoint()
        assert fresh.resume_batch_in_epoch == 2
        assert fresh.diag.dg_ratio_ewma == 3.25
        # start_of_epoch consumes the one-shot offset
        fresh.current_iteration = 2
        fresh.start_of_epoch(0)
        assert fresh._epoch_start_iteration == 0
        assert fresh.resume_batch_in_epoch == 0

    def test_divergent_runstate_flagged_and_ignored(self, tmp_path):
        batch = jax.tree_util.tree_map(np.asarray,
                                       ge._tiny_batch(1, h=64, w=64))
        trainer, _ = _spade_trainer(tmp_path)
        trainer.init_state(jax.random.PRNGKey(0), batch)
        path = trainer.save_checkpoint(0, 2)
        # cross-wire the sidecar: iteration disagrees with the ckpt
        with open(path + ".runstate.json") as f:
            rs = json.load(f)
        rs["iteration"] = 7
        with open(path + ".runstate.json", "w") as f:
            json.dump(rs, f)

        tdir = str(tmp_path / "tm")
        tm = telemetry.configure(logdir=tdir, enabled=True,
                                 sinks=("jsonl",))
        fresh, _ = _spade_trainer(tmp_path)
        fresh.init_state(jax.random.PRNGKey(1), batch)
        assert fresh.load_checkpoint()
        assert fresh.resume_batch_in_epoch == 0  # sidecar ignored
        tm.shutdown()
        events = [json.loads(line) for line in
                  open(os.path.join(tdir, "telemetry.jsonl"))]
        assert any(e.get("name") == "resilience/resume_divergence"
                   for e in events)

    @pytest.mark.slow
    def test_vid2vid_kill_at_k_resume_bit_identical(self, tmp_path):
        """The rollout family: per-frame D/G updates + temporal state —
        resume must restore the full rollout RNG/step chain too."""
        from imaginaire_tpu.config import Config
        from imaginaire_tpu.registry import resolve
        from imaginaire_tpu.utils.data import (
            get_paired_input_label_channel_number,
        )

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

        def make_trainer(logdir):
            cfg = Config(os.path.join(here, "configs", "unit_test",
                                      "vid2vid_street.yaml"))
            cfg.logdir = os.path.join(str(tmp_path), logdir)
            os.makedirs(cfg.logdir, exist_ok=True)
            cfg.trainer.perceptual_loss.layers = ["relu_1_1", "relu_2_1"]
            cfg.trainer.perceptual_loss.weights = [0.5, 1.0]
            cfg.dis.image.num_discriminators = 1
            cfg.diagnostics.dg_ratio_warn_low = 0.0
            cfg.diagnostics.dg_ratio_warn_high = 1e9
            return resolve(cfg.trainer.type, "Trainer")(cfg), cfg

        trainer, cfg = make_trainer("straight")
        n_lab = get_paired_input_label_channel_number(cfg.data)
        rng = np.random.RandomState(2)
        batch = {
            "images": (rng.rand(1, 3, 64, 64, 3).astype(np.float32)
                       * 2 - 1),
            "label": (rng.rand(1, 3, 64, 64, n_lab) > 0.9
                      ).astype(np.float32),
        }

        def run(t, start, n):
            for i in range(start, start + n):
                data = t.start_of_iteration(batch, i)
                t.gen_update(data)  # D updates ride inside the rollout
                t.current_iteration = i + 1
            t.diag.drain(t)

        trainer.init_state(jax.random.PRNGKey(3), batch)
        run(trainer, 0, 2)

        killed, _ = make_trainer("killed")
        killed.init_state(jax.random.PRNGKey(3), batch)
        run(killed, 0, 1)
        killed.save_checkpoint(0, 1)

        resumed, _ = make_trainer("killed")
        resumed.init_state(jax.random.PRNGKey(77), batch)
        assert resumed.load_checkpoint()
        run(resumed, 1, 1)
        _assert_states_bit_identical(
            trainer.state, resumed.state,
            keys=("vars_G", "vars_D", "opt_G", "opt_D", "step",
                  "step_D"))
