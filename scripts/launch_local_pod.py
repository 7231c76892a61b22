#!/usr/bin/env python
"""Localhost CPU pod harness (ISSUE 8): spawn N ``jax.distributed``
processes of an imaginaire-tpu entry point on this machine.

This is the zero-hardware proof of the multi-process stack: each child
gets its own virtual CPU device(s) and joins one coordination service
on 127.0.0.1, so the pod runs REAL cross-process collectives (gloo),
real collective orbax checkpointing, real timed barriers — everything a
TPU pod runs except the ICI. The dryrun ``spade_pod`` leg and the
chaos/resilience tests drive it; operators can use it to rehearse pod
procedures (kill/restart drills, consensus resume) before burning pod
hours.

Usage:
    python scripts/launch_local_pod.py --num-processes 2 -- \
        train.py --config cfg.yaml --logdir logs/pod --seed 0

Everything after ``--`` is the per-process command line (executed with
this interpreter). The harness:
  - picks a free coordinator port and exports the ``IMAGINAIRE_DIST_*``
    env contract (``parallel/mesh.maybe_init_distributed_from_env``);
  - forces ``JAX_PLATFORMS=cpu`` and one virtual CPU device per process
    (``--devices-per-process`` to change);
  - relays each child's output under a ``[p<i>]`` prefix, live;
  - enforces ``--timeout`` by killing the whole pod (exit 124) — a
    hung pod must fail loudly, hangs are the failure mode under test;
  - exits 0 only when EVERY process exits ``--expect-exit`` (default
    0). ``--expect-exit 75`` asserts a coordinated preemption drain.
    ``--expect-exit-map 0:75,1:0`` (ISSUE 13) asserts PER-PROCESS
    codes instead — unlisted ranks keep the ``--expect-exit`` default
    (in elastic mode: the drill's built-in verdict);
  - ``--child-log-dir DIR`` tees each child's full output to
    ``DIR/p<i>.log`` (joiners: ``p<i>.rejoin-<n>.log``) — the drill
    post-mortem evidence a truncated harness capture loses. Elastic
    mode defaults it to ``<logdir>/pod-logs``.

``--elastic`` (ISSUE 11) runs the N -> N-1 -> N chaos drill instead:
every child starts with ``IMAGINAIRE_ELASTIC=1`` (the resilient raw
runtime), one child (``--kill-rank``) is expected to leave — either the
launcher SIGTERMs it after ``--kill-after-s``, or the workload's chaos
config kills it at an exact step — and must exit 75 after the
coordinated drain while the survivors reshape IN-PROCESS and keep
training. ``--respawn-after-s`` later the harness respawns it as a
JOINER (``IMAGINAIRE_ELASTIC_JOIN=<logdir>``); the pod grows back and
every process must finish 0. Requires ``--logdir`` (the join
rendezvous lives under ``<logdir>/elastic/``). ``--relaunch`` (ISSUE
13) extends the drill's grow-back hook to mid-run restarts: ANY rank
that exits ``EXIT_ELASTIC_RESTART`` (76 — a resize that could not
complete in-process) is respawned once as a joiner into the same pod
instead of failing the drill.
"""

from __future__ import annotations

import argparse
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time


def free_port():
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="spawn an N-process localhost CPU pod")
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--devices-per-process", type=int, default=1,
                    help="virtual CPU devices per process (the pod "
                         "mesh has N*this devices on 'data')")
    ap.add_argument("--timeout", type=float, default=1800.0,
                    help="seconds before the whole pod is killed "
                         "(exit 124) — a hung pod must fail loudly")
    ap.add_argument("--expect-exit", type=int, default=0,
                    help="required exit code of EVERY process (75 for "
                         "a coordinated preemption drain)")
    ap.add_argument("--expect-exit-map", default=None,
                    help="per-process exit expectations as "
                         "'rank:code,rank:code' (e.g. '0:75,1:0'); "
                         "unlisted ranks fall back to --expect-exit "
                         "(elastic mode: the drill's built-in verdict)")
    ap.add_argument("--expect-failure", action="store_true",
                    help="success = every process exited NONZERO "
                         "(desync drills: the exact code depends on "
                         "whether the coordination service aborted the "
                         "process before its traceback exit)")
    ap.add_argument("--coordinator-port", type=int, default=None)
    ap.add_argument("--elastic", action="store_true",
                    help="run the N -> N-1 -> N elastic chaos drill "
                         "(ISSUE 11): one child leaves with exit 75, "
                         "survivors reshape in-process, the harness "
                         "respawns it as a joiner and everyone must "
                         "finish 0")
    ap.add_argument("--logdir", default=None,
                    help="the run's --logdir (elastic mode only: the "
                         "join rendezvous lives under <logdir>/elastic/)")
    ap.add_argument("--kill-rank", type=int, default=None,
                    help="which process leaves the pod (default: the "
                         "last one)")
    ap.add_argument("--kill-after-s", type=float, default=None,
                    help="SIGTERM --kill-rank this many seconds in; "
                         "omit when the workload's chaos config kills "
                         "itself at an exact step")
    ap.add_argument("--respawn-after-s", type=float, default=2.0,
                    help="delay between the drain exit and the joiner "
                         "respawn")
    ap.add_argument("--relaunch", action="store_true",
                    help="elastic mode: respawn (once per rank) any "
                         "process that exits 76 (EXIT_ELASTIC_RESTART) "
                         "as a joiner into the same pod — the grow-back "
                         "hook for a rank whose in-process resize "
                         "failed")
    ap.add_argument("--child-log-dir", default=None,
                    help="tee each child's full output to "
                         "<dir>/p<i>.log (elastic mode default: "
                         "<logdir>/pod-logs)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="entry point + args, after '--' (e.g. "
                         "train.py --config ...)")
    args = ap.parse_args(argv)
    cmd = list(args.command)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given (everything after '--')")
    if args.elastic and not args.logdir:
        ap.error("--elastic requires --logdir (join rendezvous dir)")
    args.command = cmd
    args.expect_exit_map = parse_exit_map(args.expect_exit_map, ap)
    if args.child_log_dir is None and args.elastic and args.logdir:
        args.child_log_dir = os.path.join(args.logdir, "pod-logs")
    return args


def parse_exit_map(spec, ap=None):
    """'0:75,1:0' -> {0: 75, 1: 0}; None/'' -> {}."""
    if not spec:
        return {}
    out = {}
    for item in str(spec).split(","):
        item = item.strip()
        if not item:
            continue
        try:
            rank, code = item.split(":")
            out[int(rank)] = int(code)
        except ValueError:
            msg = (f"--expect-exit-map entry {item!r} is not "
                   f"'rank:code'")
            if ap is not None:
                ap.error(msg)
            raise ValueError(msg) from None
    return out


def _relay_factory(write_lock, log_dir=None):
    """A relay function that prefixes each child line onto stdout and —
    when ``log_dir`` is set — tees the child's FULL output to
    ``<log_dir>/<tag>.log`` (the post-mortem record a truncated
    harness capture loses, ISSUE 13)."""
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)

    def relay(tag, pipe):
        logf = None
        if log_dir:
            try:
                logf = open(os.path.join(log_dir, f"{tag}.log"), "w")
            except OSError:
                logf = None
        for line in pipe:
            if logf is not None:
                logf.write(line)
                logf.flush()
            with write_lock:
                sys.stdout.write(f"[{tag}] {line}")
                sys.stdout.flush()
        pipe.close()
        if logf is not None:
            logf.close()

    return relay


def launch_pod(command, num_processes=2, devices_per_process=1,
               timeout=1800.0, coordinator_port=None, extra_env=None,
               prefix_output=True, cwd=None, log_dir=None):
    """Spawn the pod; returns ``(exit_codes, wall_s)`` with one exit
    code per process (None replaced by -9 when the timeout killed it).
    """
    port = coordinator_port or free_port()
    here = cwd or os.getcwd()
    procs = []
    readers = []
    write_lock = threading.Lock()
    relay = _relay_factory(write_lock, log_dir)

    for idx in range(num_processes):
        env = dict(os.environ, **(extra_env or {}))
        env["IMAGINAIRE_DIST_COORDINATOR"] = f"127.0.0.1:{port}"
        env["IMAGINAIRE_DIST_NUM_PROCESSES"] = str(num_processes)
        env["IMAGINAIRE_DIST_PROCESS_ID"] = str(idx)
        env["JAX_PLATFORMS"] = "cpu"
        # --devices-per-process always wins: an inherited device-count
        # flag (e.g. the dryrun parent's 8-device virtual mesh) would
        # silently change the pod's topology — and a per-host batch
        # that no longer divides the per-host device count corrupts
        # the global batch assembly
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", env.get("XLA_FLAGS", "")).strip()
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
                    f"{devices_per_process}").strip()
        proc = subprocess.Popen(
            [sys.executable, "-u"] + list(command), cwd=here, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        procs.append(proc)
        if prefix_output:
            reader = threading.Thread(target=relay,
                                      args=(f"p{idx}", proc.stdout),
                                      daemon=True)
            reader.start()
            readers.append(reader)

    t0 = time.monotonic()
    deadline = t0 + timeout
    codes = [None] * num_processes
    while time.monotonic() < deadline and any(c is None for c in codes):
        for i, proc in enumerate(procs):
            if codes[i] is None:
                codes[i] = proc.poll()
        time.sleep(0.2)
    timed_out = any(c is None for c in codes)
    if timed_out:
        sys.stderr.write(
            f"launch_local_pod: TIMEOUT after {timeout:.0f}s — killing "
            f"{sum(c is None for c in codes)} hung process(es) "
            f"(exit codes so far: {codes})\n")
        for i, proc in enumerate(procs):
            if codes[i] is None:
                proc.kill()
        for i, proc in enumerate(procs):
            if codes[i] is None:
                proc.wait()
                codes[i] = -9
    for reader in readers:
        reader.join(timeout=10)
    return codes, time.monotonic() - t0, timed_out


def _pod_env(port, devices_per_process, extra_env=None):
    """Child env shared by every elastic incarnation: CPU platform, the
    exact virtual device count, and the elastic base coordinator (the
    per-generation service ports are derived from it)."""
    env = dict(os.environ, **(extra_env or {}))
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                   "", env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count="
                f"{devices_per_process}").strip()
    env["IMAGINAIRE_ELASTIC"] = "1"
    env["IMAGINAIRE_ELASTIC_BASE_COORDINATOR"] = f"127.0.0.1:{port}"
    # stale inherited membership would let a joiner skip the rendezvous
    for key in ("IMAGINAIRE_DIST_COORDINATOR",
                "IMAGINAIRE_DIST_NUM_PROCESSES",
                "IMAGINAIRE_DIST_PROCESS_ID",
                "IMAGINAIRE_ELASTIC_JOIN",
                "IMAGINAIRE_ELASTIC_JOIN_NONCE"):
        env.pop(key, None)
    return env


def launch_elastic_pod(command, logdir, num_processes=3,
                       devices_per_process=1, timeout=1800.0,
                       coordinator_port=None, kill_rank=None,
                       kill_after_s=None, respawn_after_s=2.0,
                       extra_env=None, prefix_output=True, cwd=None,
                       log_dir=None, relaunch=False):
    """The N -> N-1 -> N elastic chaos drill (ISSUE 11).

    Spawns ``num_processes`` elastic children; ``kill_rank`` leaves the
    pod (SIGTERM from here after ``kill_after_s``, or the workload's
    own chaos config at an exact step) and must exit 75 after the
    coordinated drain. The survivors reshape IN-PROCESS — they do not
    exit. ``respawn_after_s`` after the drain exit the same rank is
    respawned as a joiner (``IMAGINAIRE_ELASTIC_JOIN``, no
    ``IMAGINAIRE_DIST_*``: the published topology assigns those) and
    the pod grows back. With ``relaunch=True`` (ISSUE 13) any OTHER
    rank that exits 76 (``EXIT_ELASTIC_RESTART``) is also respawned —
    once per rank — as a joiner, and its final code replaces its
    first-incarnation 76 in the verdict.

    Returns ``(first_codes, rejoin_code, wall_s, timed_out)`` —
    ``first_codes[kill_rank]`` should be 75, every other entry and
    ``rejoin_code`` should be 0 (relaunched ranks report their SECOND
    incarnation's code).
    """
    port = coordinator_port or free_port()
    here = cwd or os.getcwd()
    if kill_rank is None:
        kill_rank = num_processes - 1
    write_lock = threading.Lock()
    readers = []
    relay = _relay_factory(write_lock, log_dir)

    def spawn(tag, env):
        proc = subprocess.Popen(
            [sys.executable, "-u"] + list(command), cwd=here, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        if prefix_output:
            reader = threading.Thread(target=relay,
                                      args=(tag, proc.stdout),
                                      daemon=True)
            reader.start()
            readers.append(reader)
        return proc

    procs = []
    for idx in range(num_processes):
        env = _pod_env(port, devices_per_process, extra_env)
        env["IMAGINAIRE_DIST_COORDINATOR"] = f"127.0.0.1:{port}"
        env["IMAGINAIRE_DIST_NUM_PROCESSES"] = str(num_processes)
        env["IMAGINAIRE_DIST_PROCESS_ID"] = str(idx)
        procs.append(spawn(f"p{idx}", env))

    def spawn_joiner(rank, suffix="rejoin"):
        env = _pod_env(port, devices_per_process, extra_env)
        env["IMAGINAIRE_ELASTIC_JOIN"] = str(logdir)
        env["IMAGINAIRE_ELASTIC_JOIN_NONCE"] = f"{suffix}-p{rank}"
        with write_lock:
            sys.stdout.write(
                f"launch_local_pod: respawning p{rank} as joiner "
                f"(nonce {suffix}-p{rank})\n")
            sys.stdout.flush()
        return spawn(f"p{rank}.{suffix}", env)

    t0 = time.monotonic()
    deadline = t0 + timeout
    first_codes = [None] * num_processes
    rejoin_proc = None
    rejoin_code = None
    respawn_at = None
    term_sent = False
    # --relaunch bookkeeping: rank -> second-incarnation proc/code for
    # ranks that exited 76 (EXIT_ELASTIC_RESTART) and were respawned
    relaunched = {}
    relaunch_codes = {}
    while time.monotonic() < deadline:
        for i, proc in enumerate(procs):
            if first_codes[i] is None:
                first_codes[i] = proc.poll()
        if rejoin_proc is not None and rejoin_code is None:
            rejoin_code = rejoin_proc.poll()
        for rank, proc in relaunched.items():
            if relaunch_codes.get(rank) is None:
                relaunch_codes[rank] = proc.poll()
        if (kill_after_s is not None and not term_sent
                and time.monotonic() - t0 >= kill_after_s
                and first_codes[kill_rank] is None):
            with write_lock:
                sys.stdout.write(
                    f"launch_local_pod: SIGTERM -> p{kill_rank} "
                    f"(elastic drill)\n")
                sys.stdout.flush()
            procs[kill_rank].send_signal(signal.SIGTERM)
            term_sent = True
        if first_codes[kill_rank] is not None and respawn_at is None:
            respawn_at = time.monotonic() + respawn_after_s
        if (respawn_at is not None and rejoin_proc is None
                and time.monotonic() >= respawn_at):
            rejoin_proc = spawn_joiner(kill_rank)
        if relaunch:
            for i in range(num_processes):
                if (i != kill_rank and i not in relaunched
                        and first_codes[i] == 76):
                    relaunched[i] = spawn_joiner(i, suffix="relaunch")
        done = (all(c is not None for c in first_codes)
                and rejoin_proc is not None and rejoin_code is not None
                and all(relaunch_codes.get(r) is not None
                        for r in relaunched))
        if done:
            break
        time.sleep(0.2)

    pending_relaunch = [r for r in relaunched
                        if relaunch_codes.get(r) is None]
    timed_out = (any(c is None for c in first_codes)
                 or rejoin_code is None or bool(pending_relaunch))
    if timed_out:
        hung = [p for i, p in enumerate(procs) if first_codes[i] is None]
        if rejoin_proc is not None and rejoin_code is None:
            hung.append(rejoin_proc)
        hung.extend(relaunched[r] for r in pending_relaunch)
        sys.stderr.write(
            f"launch_local_pod: elastic drill TIMEOUT after "
            f"{timeout:.0f}s — killing {len(hung)} hung process(es) "
            f"(first incarnation codes: {first_codes}, "
            f"rejoin: {rejoin_code})\n")
        for proc in hung:
            proc.kill()
        for proc in hung:
            proc.wait()
        first_codes = [(-9 if c is None else c) for c in first_codes]
        if rejoin_proc is not None and rejoin_code is None:
            rejoin_code = -9
        for r in pending_relaunch:
            relaunch_codes[r] = -9
    # a relaunched rank's verdict is its SECOND incarnation: the 76 did
    # its job (the supervisor hook fired), the rejoined run must finish
    for rank, code in relaunch_codes.items():
        with write_lock:
            sys.stdout.write(
                f"launch_local_pod: p{rank} relaunched after 76 — "
                f"final code {code}\n")
            sys.stdout.flush()
        first_codes[rank] = code
    for reader in readers:
        reader.join(timeout=10)
    return first_codes, rejoin_code, time.monotonic() - t0, timed_out


def main(argv=None):
    args = parse_args(argv)
    if args.elastic:
        first, rejoin, wall, timed_out = launch_elastic_pod(
            args.command, args.logdir,
            num_processes=args.num_processes,
            devices_per_process=args.devices_per_process,
            timeout=args.timeout,
            coordinator_port=args.coordinator_port,
            kill_rank=args.kill_rank, kill_after_s=args.kill_after_s,
            respawn_after_s=args.respawn_after_s,
            log_dir=args.child_log_dir, relaunch=args.relaunch)
        kill_rank = (args.num_processes - 1 if args.kill_rank is None
                     else args.kill_rank)
        # the drill's built-in verdict (kill_rank -> 75, everyone else
        # + joiner -> 0), overridable per rank via --expect-exit-map
        expected = {i: (75 if i == kill_rank else 0)
                    for i in range(args.num_processes)}
        expected.update(args.expect_exit_map)
        print(f"launch_local_pod: elastic drill first codes {first}, "
              f"rejoin {rejoin} in {wall:.1f}s (expected: "
              f"{ {f'p{i}': c for i, c in sorted(expected.items())} } "
              f"+ joiner -> 0)")
        if timed_out:
            return 124
        ok = (rejoin == 0
              and all(first[i] == expected.get(i, 0)
                      for i in range(args.num_processes)))
        return 0 if ok else 1
    codes, wall, timed_out = launch_pod(
        args.command, num_processes=args.num_processes,
        devices_per_process=args.devices_per_process,
        timeout=args.timeout, coordinator_port=args.coordinator_port,
        log_dir=args.child_log_dir)
    expected = {i: args.expect_exit_map.get(i, args.expect_exit)
                for i in range(args.num_processes)}
    want = ("nonzero" if args.expect_failure
            else (str(args.expect_exit) if not args.expect_exit_map
                  else str({f"p{i}": c
                            for i, c in sorted(expected.items())})))
    print(f"launch_local_pod: exit codes {codes} in {wall:.1f}s "
          f"(expected {want} from all {args.num_processes})")
    if timed_out:
        return 124
    if args.expect_failure:
        return 0 if all(c != 0 for c in codes) else 1
    return 0 if all(codes[i] == expected[i]
                    for i in range(args.num_processes)) else 1


if __name__ == "__main__":
    raise SystemExit(main())
