"""Host-side health monitor: consumes the in-jit health summaries the
step programs return, emits telemetry counters at the audit cadence,
tracks GAN balance, and drives the non-finite response policy.

Sync discipline (the PR 2 contract — no per-step device fences): each
``observe`` call only *stores* the freshly dispatched step's outputs and
polls the PREVIOUS entry's finite/audited flags. By the time the poll
runs, the next program is already queued behind the previous one, so the
two-scalar ``device_get`` never stalls the dispatch pipeline; it merely
caps host run-ahead at one program. Full health summaries (and the loss
breakdown) are fetched only for entries whose in-graph cadence predicate
fired.

Non-finite policy (``diagnostics.on_nonfinite``):

- ``halt``     — triage, write the report, raise ``NonFiniteLossError``.
- ``skip``     — triage once, count the event, keep running. The step
  programs guard updates in-graph whenever diagnostics are enabled, so
  the skipped step's params/opt/mutables are bit-identical to the last
  finite state — no host-side restore needed.
- ``rollback`` — like skip, but additionally restores the trainer state
  from the last audited-finite snapshot (a device copy taken every
  ``every_n_steps``; costs one extra state-sized buffer — use for runs
  where optimizer moments degrade before the loss goes non-finite).
"""

from __future__ import annotations

import logging
from collections import deque

import jax
import jax.numpy as jnp

from imaginaire_tpu.config import cfg_get

logger = logging.getLogger(__name__)

_POLICIES = ("halt", "skip", "rollback")
# health keys that are per-step control flags, not audit metrics
_CONTROL_KEYS = ("finite", "audited", "rng_step")


class NonFiniteLossError(RuntimeError):
    """Raised by ``on_nonfinite: halt`` after the triage report lands."""


def diagnostics_settings(cfg):
    """Parse the ``diagnostics`` config section (see config.py defaults)."""
    dcfg = cfg_get(cfg or {}, "diagnostics", None) or {}
    policy = str(cfg_get(dcfg, "on_nonfinite", "halt")).lower()
    if policy not in _POLICIES:
        logger.warning("unknown diagnostics.on_nonfinite=%r; using 'halt' "
                       "(supported: %s)", policy, "/".join(_POLICIES))
        policy = "halt"
    return {
        "enabled": bool(cfg_get(dcfg, "enabled", True)),
        "every_n_steps": max(int(cfg_get(dcfg, "every_n_steps", 10)), 1),
        "on_nonfinite": policy,
        "history": max(int(cfg_get(dcfg, "history", 64)), 1),
        "dg_ratio_beta": float(cfg_get(dcfg, "dg_ratio_beta", 0.9)),
        "dg_ratio_warn_low": float(cfg_get(dcfg, "dg_ratio_warn_low", 0.1)),
        "dg_ratio_warn_high": float(cfg_get(dcfg, "dg_ratio_warn_high",
                                            10.0)),
        "max_triage_terms": int(cfg_get(dcfg, "max_triage_terms", 16)),
    }


class HealthMonitor:
    def __init__(self, cfg):
        self.cfg = cfg
        s = diagnostics_settings(cfg)
        self.enabled = s["enabled"]
        self.every_n = s["every_n_steps"]
        self.on_nonfinite = s["on_nonfinite"]
        self.dg_beta = s["dg_ratio_beta"]
        self.warn_low = s["dg_ratio_warn_low"]
        self.warn_high = s["dg_ratio_warn_high"]
        self.max_triage_terms = s["max_triage_terms"]
        self.history = deque(maxlen=s["history"])
        self.dg_ratio_ewma = None
        self.dg_breaches = 0
        self._in_breach = False
        self.skip_count = 0
        self.nonfinite_events = 0
        self.last_report_path = None
        self._prev = None
        self._last_gan = {}
        self._snapshot = None
        self._snapshot_step = None
        self._triaged = False

    # --------------------------------------------------------- run state

    def state_dict(self):
        """JSON-serializable monitor state for the checkpoint's runstate
        sidecar (resilience/, ISSUE 7): a resumed run keeps its GAN
        balance EWMA, breach counts and health history instead of
        silently restarting them."""
        return {
            "dg_ratio_ewma": self.dg_ratio_ewma,
            "dg_breaches": int(self.dg_breaches),
            "in_breach": bool(self._in_breach),
            "skip_count": int(self.skip_count),
            "nonfinite_events": int(self.nonfinite_events),
            "last_gan": dict(self._last_gan),
            "history": list(self.history),
        }

    def load_state_dict(self, state):
        """Restore ``state_dict`` output (missing keys keep defaults —
        old sidecars stay loadable)."""
        if not state:
            return
        if state.get("dg_ratio_ewma") is not None:
            self.dg_ratio_ewma = float(state["dg_ratio_ewma"])
        self.dg_breaches = int(state.get("dg_breaches",
                                         self.dg_breaches))
        self._in_breach = bool(state.get("in_breach", self._in_breach))
        self.skip_count = int(state.get("skip_count", self.skip_count))
        self.nonfinite_events = int(state.get("nonfinite_events",
                                              self.nonfinite_events))
        self._last_gan = {str(k): float(v) for k, v in
                          (state.get("last_gan") or {}).items()}
        history = state.get("history")
        if history:
            self.history.clear()
            self.history.extend(history)

    # ------------------------------------------------------------ intake

    def observe(self, trainer, kind, losses, health, data, step):
        """Record one dispatched step ('G' or 'D') and poll the previous
        one. ``health`` is the step program's summary dict ({} when
        diagnostics are off — then this is a no-op)."""
        if not self.enabled or not health:
            return
        prev, self._prev = self._prev, {
            "kind": kind, "step": step, "losses": losses,
            "health": health, "data": data,
        }
        if prev is not None:
            self._check(trainer, prev)

    def drain(self, trainer):
        """Process the final pending entry (end of epoch / end of run /
        tests) — blocks on that step's completion, so never call it from
        the per-step hot path."""
        if self._prev is None:
            return
        prev, self._prev = self._prev, None
        self._check(trainer, prev)

    # --------------------------------------------------------- processing

    def _check(self, trainer, entry):
        from imaginaire_tpu import telemetry

        h = entry["health"]
        # the one place the loop thread blocks on the device: the span's
        # step is the iteration that polls, the flags the program's
        # before it
        with telemetry.span("health_poll", step=trainer.current_iteration):
            # lint: allow(host-sync) -- reads the PREVIOUS step's flags, one step behind the dispatch frontier
            finite, audited = (bool(x) for x in jax.device_get(
                (h["finite"], h["audited"])))
        if audited:
            self._ingest(entry, finite=finite)
            if finite and self.on_nonfinite == "rollback":
                self._take_snapshot(trainer, entry["step"])
        if not finite:
            self._handle_nonfinite(trainer, entry)
        entry["data"] = None  # release the batch reference

    def _ingest(self, entry, finite=True):
        """Fetch and emit one audited entry's health + loss breakdown.
        Both programs have completed by now, so the ``device_get`` is a
        pure transfer."""
        from imaginaire_tpu import telemetry

        kind, step = entry["kind"], entry["step"]
        metrics = {k: v for k, v in entry["health"].items()
                   if k not in _CONTROL_KEYS}
        health = {k: float(v) for k, v in
                  # lint: allow(host-sync) -- completed-step transfer
                  jax.device_get(metrics).items()}
        lvals = {k: float(v) for k, v in
                 # lint: allow(host-sync) -- completed-step transfer
                 jax.device_get(dict(entry["losses"])).items()}
        tm = telemetry.get()
        for name, value in health.items():
            tm.counter(f"health/{kind}/{name}", value, step=step)
        if kind == "D":
            for key, ctr in (("D_real_acc", "health/D/real_acc"),
                             ("D_fake_acc", "health/D/fake_acc")):
                if key in lvals:
                    tm.counter(ctr, lvals[key], step=step)
        self.history.append({"step": step, "kind": kind, "finite": finite,
                             "health": health, "losses": lvals})
        # pod divergence sentinel intake (podview.py, ISSUE 17): these
        # are already host floats — podview adds no device syncs
        from imaginaire_tpu.telemetry import podview

        podview.get().note_losses(step, kind, lvals)
        self._update_balance(kind, step, lvals)

    def _update_balance(self, kind, step, lvals):
        """D/G GAN-loss ratio EWMA + threshold warnings."""
        from imaginaire_tpu import telemetry

        gan = lvals.get("GAN", lvals.get("gan", lvals.get("total")))
        if gan is None:
            return
        self._last_gan[kind] = gan
        if "G" not in self._last_gan or "D" not in self._last_gan:
            return
        d, g = self._last_gan["D"], self._last_gan["G"]
        ratio = abs(d) / (abs(g) + 1e-12)
        self.dg_ratio_ewma = (ratio if self.dg_ratio_ewma is None
                              else self.dg_beta * self.dg_ratio_ewma
                              + (1.0 - self.dg_beta) * ratio)
        tm = telemetry.get()
        tm.counter("health/dg_loss_ratio", ratio, step=step)
        tm.counter("health/dg_loss_ratio_ewma", self.dg_ratio_ewma,
                   step=step)
        breached = not (self.warn_low <= self.dg_ratio_ewma
                        <= self.warn_high)
        if breached:
            self.dg_breaches += 1
            tm.counter("health/dg_ratio_breach", self.dg_ratio_ewma,
                       step=step)
            if not self._in_breach:
                # warn once per excursion, not once per audit step —
                # the breach counter still counts every audited breach
                tm.meta("dg_ratio_breach", step=step,
                        ewma=self.dg_ratio_ewma, low=self.warn_low,
                        high=self.warn_high)
                logger.warning(
                    "D/G loss-ratio EWMA %.4g outside [%g, %g] at step "
                    "%s — the discriminator/generator balance is off "
                    "(diagnostics.dg_ratio_warn_{low,high})",
                    self.dg_ratio_ewma, self.warn_low, self.warn_high,
                    step)
        self._in_breach = breached

    def _take_snapshot(self, trainer, step):
        if trainer.state is None:
            return
        self._snapshot = jax.tree_util.tree_map(jnp.copy, trainer.state)
        self._snapshot_step = step

    # -------------------------------------------------------- non-finite

    def _handle_nonfinite(self, trainer, entry):
        from imaginaire_tpu import telemetry

        kind, step = entry["kind"], entry["step"]
        tm = telemetry.get()
        self.nonfinite_events += 1
        tm.counter("health/nonfinite_events", self.nonfinite_events,
                   step=step)
        if self.on_nonfinite in ("skip", "rollback"):
            self.skip_count += 1
            tm.counter("health/nonfinite_skipped", self.skip_count,
                       step=step)
        report = None
        if not self._triaged:
            # one-shot eager triage: localize the term/module, dump the
            # report. Later events only bump the counters (the first
            # report already names the provenance; re-running an eager
            # backward per event would stall the run it's meant to save).
            self._triaged = True
            from imaginaire_tpu.diagnostics.triage import (
                run_triage,
                write_report,
            )

            try:
                report = run_triage(trainer, self, entry)
                self.last_report_path = write_report(
                    cfg_get(self.cfg, "logdir", "."), report)
            except Exception:  # noqa: BLE001 — triage must not mask the event
                logger.exception("non-finite triage pass failed")
            tm.meta("nonfinite", step=step, update=kind,
                    report=self.last_report_path,
                    culprit_terms=(report or {}).get("culprit_terms"),
                    culprit_modules=(report or {}).get("culprit_modules"),
                    action=self.on_nonfinite)
            logger.error(
                "non-finite %s update at step %s — culprit terms %s, "
                "modules %s; report: %s; action=%s", kind, step,
                (report or {}).get("culprit_terms"),
                (report or {}).get("culprit_modules"),
                self.last_report_path, self.on_nonfinite)
        if self.on_nonfinite == "halt":
            raise NonFiniteLossError(
                f"non-finite {kind} update at step {step} "
                f"(culprit terms {(report or {}).get('culprit_terms')}, "
                f"modules {(report or {}).get('culprit_modules')}); "
                f"report: {self.last_report_path}. Set "
                "diagnostics.on_nonfinite: skip|rollback to keep running, "
                "or retry under `train.py --debug-nans` on CPU to trap "
                "the op.")
        if self.on_nonfinite == "rollback" and self._snapshot is not None:
            # restore a COPY: the restored buffers get donated to the
            # next step, which would otherwise invalidate the snapshot
            trainer.state = jax.tree_util.tree_map(jnp.copy,
                                                   self._snapshot)
            logger.warning(
                "rolled back trainer state to the last audited-finite "
                "snapshot (step %s)", self._snapshot_step)
