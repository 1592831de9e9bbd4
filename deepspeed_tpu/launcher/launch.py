"""Node-local launcher — sets up the JAX distributed env and execs the script.

Counterpart of the reference's ``deepspeed/launcher/launch.py`` (main:216),
which forks one OS process per GPU and sets RANK/LOCAL_RANK/WORLD_SIZE.
On TPU there is exactly ONE process per host (the JAX single-controller
runtime owns all local chips), so this program:

1. decodes the world description (host → chip list) from the runner,
2. exports ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
   ``JAX_PROCESS_ID`` so ``jax.distributed.initialize()`` can rendezvous
   (plus RANK/WORLD_SIZE/LOCAL_RANK for scripts written against the
   reference's env contract),
3. execs the user script (optionally tee-ing output per host),
4. supervises it: polls child liveness and — when ``--heartbeat_file`` is
   given — the heartbeat file the engine's ``watchdog`` block touches each
   step. A heartbeat gone stale for ``--heartbeat_timeout`` seconds means
   the child is wedged past anything its own watchdog could deliver (every
   Python thread stuck under a C call); the whole process group is killed
   with a logged reason instead of ``proc.wait()`` blocking forever.

Signal handling mirrors the reference's kill-the-tree behavior (:426): we run
the child in its own process group and forward SIGINT/SIGTERM.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import signal
import subprocess
import sys
import time

from deepspeed_tpu.utils.logging import logger

# exit code for a supervisor kill (distinct from any child exit so restart
# policy can tell "wedged, killed by us" from "crashed on its own")
HEARTBEAT_KILL_EXIT_CODE = 86
# exit code a serving child (bin/ds_serve / ServingFrontEnd) uses for a
# GRACEFUL drain after SIGTERM/preemption: admission stopped, in-flight
# requests finished or deadline-capped, partials flushed. Distinct from 86
# (wedged, killed by us) and from 0 (work complete) so a supervision loop
# can reschedule the drained server without treating it as a crash.
DRAIN_EXIT_CODE = 87


def parse_args(args=None):
    parser = argparse.ArgumentParser(description="node-local TPU launcher")
    parser.add_argument("--world_info", type=str, required=True,
                        help="base64 json {host: [chip indices]}")
    parser.add_argument("--node_rank", type=int, default=0)
    parser.add_argument("--num_nodes", type=int, default=0,
                        help="override process count (Cloud TPU: one world_info "
                             "entry fans out to N workers)")
    parser.add_argument("--master_addr", type=str, default="127.0.0.1")
    parser.add_argument("--master_port", type=int, default=8476)
    parser.add_argument("--log_dir", type=str, default=None)
    parser.add_argument("--heartbeat_file", type=str, default=None,
                        help="supervise this heartbeat file (exported to the "
                             "child as DS_TPU_HEARTBEAT_FILE; the engine's "
                             "watchdog block touches it each step)")
    parser.add_argument("--heartbeat_timeout", type=float, default=0.0,
                        help="seconds without a heartbeat touch before the "
                             "child process group is killed (0 = liveness "
                             "polling only)")
    parser.add_argument("--poll_interval", type=float, default=2.0,
                        help="supervision poll cadence (s)")
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def decode_world_info(encoded: str) -> dict:
    return json.loads(base64.urlsafe_b64decode(encoded.encode()).decode())


def build_env(world_info: dict, node_rank: int, master_addr: str, master_port: int,
              base_env=None, num_nodes: int = 0) -> dict:
    """Env block for the user process — both JAX rendezvous vars and the
    reference's RANK/WORLD_SIZE contract (one "rank" per host here).

    ``num_nodes`` overrides the process count when one world_info entry fans
    out to several workers (Cloud TPU: the pool has one TPU name, node_rank
    comes from TPU_WORKER_ID and num_nodes from the worker-hostname list).
    """
    env = dict(base_env if base_env is not None else os.environ)
    hosts = list(world_info)
    num_hosts = num_nodes if num_nodes > 0 else len(hosts)
    if node_rank >= num_hosts:
        raise ValueError(f"node_rank {node_rank} out of range for {num_hosts} nodes")
    env["JAX_COORDINATOR_ADDRESS"] = f"{master_addr}:{master_port}"
    env["JAX_NUM_PROCESSES"] = str(num_hosts)
    env["JAX_PROCESS_ID"] = str(node_rank)
    # reference-compatible names (launch.py:216 contract), host-granular:
    env["RANK"] = str(node_rank)
    env["LOCAL_RANK"] = "0"
    env["WORLD_SIZE"] = str(num_hosts)
    env["MASTER_ADDR"] = master_addr
    env["MASTER_PORT"] = str(master_port)
    return env


def kill_process_tree(proc, grace_s: float = 10.0,
                      sleep=time.sleep) -> None:
    """SIGTERM the child's process group, escalate to SIGKILL after
    ``grace_s`` if it did not die (a wedged process often ignores TERM —
    that is why it is wedged)."""
    try:
        pgid = os.getpgid(proc.pid)
    except ProcessLookupError:
        return
    try:
        os.killpg(pgid, signal.SIGTERM)
    except ProcessLookupError:
        return
    deadline = time.monotonic() + grace_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            return
        sleep(0.1)
    try:
        os.killpg(pgid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def supervise(proc, heartbeat_file=None, heartbeat_timeout: float = 0.0,
              poll_interval: float = 2.0, kill_grace: float = 10.0,
              clock=time.time, sleep=time.sleep):
    """Supervision loop replacing a bare ``proc.wait()``: poll child
    liveness every ``poll_interval``; with a heartbeat configured, kill the
    process group once the file's mtime goes stale past
    ``heartbeat_timeout``. A heartbeat file that was NEVER created does not
    trip the check (the job may not enable the watchdog block) — only a
    heartbeat that existed and then stopped advancing is evidence of a
    wedge. Returns ``(exit_code, reason)``.
    """
    while True:
        rc = proc.poll()
        if rc is not None:
            return rc, "exited"
        if heartbeat_file and heartbeat_timeout > 0:
            try:
                age = clock() - os.path.getmtime(heartbeat_file)
            except OSError:
                age = None      # not created yet: liveness polling only
            if age is not None and age > heartbeat_timeout:
                reason = (f"heartbeat stale: {heartbeat_file} last touched "
                          f"{age:.0f}s ago (> {heartbeat_timeout:.0f}s) — "
                          "killing the wedged process group")
                logger.error(f"launcher: {reason}")
                from deepspeed_tpu import telemetry

                telemetry.get_registry().counter("resilience/heartbeat_stale").inc()
                kill_process_tree(proc, grace_s=kill_grace, sleep=sleep)
                return HEARTBEAT_KILL_EXIT_CODE, reason
        sleep(poll_interval)


def main(args=None):
    args = parse_args(args)
    world_info = decode_world_info(args.world_info)
    env = build_env(world_info, args.node_rank, args.master_addr, args.master_port,
                    num_nodes=args.num_nodes)
    if args.heartbeat_file:
        # the engine's watchdog block reads this env var when the config
        # does not name a heartbeat file itself
        env["DS_TPU_HEARTBEAT_FILE"] = args.heartbeat_file
        try:
            # a leftover file from a previous run is already stale — it would
            # kill the new child before its first touch; any file present
            # after this point was created by THIS run
            os.remove(args.heartbeat_file)
        except OSError:
            pass
    cmd = [sys.executable, "-u", args.user_script] + args.user_args

    stdout = None
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
        stdout = open(os.path.join(args.log_dir, f"host_{args.node_rank}.log"), "ab")

    logger.info(f"launching node_rank={args.node_rank}/{len(world_info)}: {cmd}")
    proc = subprocess.Popen(cmd, env=env, stdout=stdout,
                            stderr=subprocess.STDOUT if stdout else None,
                            start_new_session=True)

    def forward(sig, _frame):
        try:
            os.killpg(os.getpgid(proc.pid), sig)
        except ProcessLookupError:
            pass

    signal.signal(signal.SIGINT, forward)
    signal.signal(signal.SIGTERM, forward)
    code, reason = supervise(proc, heartbeat_file=args.heartbeat_file,
                             heartbeat_timeout=args.heartbeat_timeout,
                             poll_interval=args.poll_interval)
    if reason != "exited":
        logger.error(f"launcher: child terminated by supervisor ({reason})")
    elif code == DRAIN_EXIT_CODE:
        # not a crash: the serving child drained cleanly after SIGTERM/
        # preemption — restart policy should reschedule, not back off
        logger.info("launcher: child exited via graceful drain "
                    f"(exit {DRAIN_EXIT_CODE})")
    sys.exit(code)


if __name__ == "__main__":
    main()
