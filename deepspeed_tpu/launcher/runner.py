"""deepspeed_tpu CLI runner — resource parsing + multi-host job launch.

Counterpart of the reference's ``deepspeed/launcher/runner.py`` (main:377,
fetch_hostfile:189, include/exclude filtering, ssh reachability check,
single-node exec path :475-486). Same resource-description surface
(``--hostfile`` with ``hostname slots=N`` lines, ``--include``/``--exclude``
filters, ``--num_nodes``/``--num_gpus``), TPU-native launch semantics:

* one worker process per HOST (JAX single-controller per host), so "slots"
  counts chips for topology math but does not multiply processes;
* rendezvous = ``jax.distributed.initialize(coordinator, num_processes,
  process_id)`` wired through env vars by ``launch.py`` — no NCCL store;
* multinode transport backends (ssh/pdsh/slurm/gcloud) live in
  ``multinode_runner.py``.
"""

from __future__ import annotations

import argparse
import os
import re
import shlex
import subprocess
import sys
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger

DLTS_HOSTFILE = "/job/hostfile"
EXPORT_ENVS = ("PYTHONPATH", "XLA_FLAGS", "LIBTPU_INIT_ARGS", "TPU_", "JAX_")
COORD_PORT_DEFAULT = 8476


def parse_args(args=None):
    parser = argparse.ArgumentParser(
        description="deepspeed_tpu launcher: run a training script across TPU hosts")
    parser.add_argument("-H", "--hostfile", type=str, default=DLTS_HOSTFILE,
                        help="hostfile with lines '<hostname> slots=<n_chips>'")
    parser.add_argument("-i", "--include", type=str, default="",
                        help="subset of hosts/chips, e.g. 'host1@host2:0,2'")
    parser.add_argument("-e", "--exclude", type=str, default="",
                        help="hosts/chips to drop, same syntax as --include")
    parser.add_argument("--num_nodes", type=int, default=-1,
                        help="cap on number of hosts (first N of the hostfile)")
    parser.add_argument("--num_gpus", "--num_chips", dest="num_gpus", type=int, default=-1,
                        help="chips per host to use (topology math only)")
    parser.add_argument("--master_addr", type=str, default=None,
                        help="coordinator address; default = first host")
    parser.add_argument("--master_port", type=int, default=COORD_PORT_DEFAULT,
                        help="coordinator port for jax.distributed")
    parser.add_argument("--launcher", type=str, default="ssh",
                        choices=["ssh", "pdsh", "slurm", "gcloud", "local"],
                        help="multinode transport backend")
    parser.add_argument("--launcher_args", type=str, default="",
                        help="extra flags passed to the transport (e.g. ssh options)")
    parser.add_argument("--force_multi", action="store_true",
                        help="treat as multinode even with one host")
    parser.add_argument("--no_ssh_check", action="store_true",
                        help="skip host reachability probe")
    parser.add_argument("--elastic_training", action="store_true",
                        help="validate elastic config before launching")
    parser.add_argument("--enable_each_rank_log", type=str, default=None,
                        help="directory for per-host log files")
    parser.add_argument("user_script", type=str, help="training script to run")
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def fetch_hostfile(hostfile_path: str) -> "OrderedDict[str, int]":
    """Parse ``hostname slots=N`` lines → ordered {host: slots}.

    Reference: runner.py fetch_hostfile:189. Blank lines and ``#`` comments
    are skipped; duplicate hosts or malformed lines are errors.
    """
    if not os.path.isfile(hostfile_path):
        return OrderedDict()
    resource_pool: "OrderedDict[str, int]" = OrderedDict()
    with open(hostfile_path) as fd:
        for lineno, line in enumerate(fd, 1):
            line = line.split("#")[0].strip()
            if not line:
                continue
            m = re.match(r"^(\S+)\s+slots=(\d+)\s*$", line)
            if m is None:
                raise ValueError(f"{hostfile_path}:{lineno}: malformed line {line!r} "
                                 "(expected '<hostname> slots=<int>')")
            host, slots = m.group(1), int(m.group(2))
            if host in resource_pool:
                raise ValueError(f"{hostfile_path}:{lineno}: duplicate host {host!r}")
            resource_pool[host] = slots
    return resource_pool


def _parse_filter(spec: str) -> "OrderedDict[str, Optional[List[int]]]":
    """'host1@host2:0,2' → {host1: None, host2: [0, 2]} (None = all slots)."""
    out: "OrderedDict[str, Optional[List[int]]]" = OrderedDict()
    if not spec:
        return out
    for part in spec.split("@"):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            host, slot_str = part.split(":", 1)
            slots = []
            for tok in slot_str.split(","):
                tok = tok.strip()
                if "-" in tok:
                    lo, hi = tok.split("-")
                    slots.extend(range(int(lo), int(hi) + 1))
                else:
                    slots.append(int(tok))
            if host in out and out[host] is not None:
                out[host].extend(s for s in slots if s not in out[host])
            else:
                out[host] = slots
        else:
            out[part] = None
    return out


def parse_inclusion_exclusion(resource_pool: Dict[str, int],
                              inclusion: str,
                              exclusion: str) -> "OrderedDict[str, List[int]]":
    """Apply --include / --exclude to the hostfile pool.

    Reference: runner.py parse_resource_filter (same @-separated host[:slots]
    grammar). Returns ordered {host: [chip indices]}.
    """
    active: "OrderedDict[str, List[int]]" = OrderedDict(
        (host, list(range(slots))) for host, slots in resource_pool.items())

    inc = _parse_filter(inclusion)
    exc = _parse_filter(exclusion)
    if inc and exc:
        raise ValueError("--include and --exclude are mutually exclusive")

    if inc:
        picked: "OrderedDict[str, List[int]]" = OrderedDict()
        for host, slots in inc.items():
            if host not in active:
                raise ValueError(f"--include host {host!r} not in hostfile")
            avail = active[host]
            use = avail if slots is None else slots
            bad = [s for s in use if s not in avail]
            if bad:
                raise ValueError(f"--include slots {bad} not available on {host}")
            picked[host] = sorted(use)
        return picked

    for host, slots in exc.items():
        if host not in active:
            raise ValueError(f"--exclude host {host!r} not in hostfile")
        if slots is None:
            del active[host]
        else:
            remaining = [s for s in active[host] if s not in slots]
            if remaining:
                active[host] = remaining
            else:
                del active[host]
    return active


def build_resource_pool(args) -> "OrderedDict[str, List[int]]":
    """hostfile + filters + --num_nodes/--num_gpus → final {host: chips}.

    No hostfile: a localhost-only job whose one worker process owns every
    local chip. The launcher does NOT count them — counting means
    initialising a jax backend, and a parent that holds the chip starves
    the child it is about to spawn — so the chip list is ``--num_gpus``
    when given and empty ("whatever the child finds") otherwise."""
    pool = fetch_hostfile(args.hostfile)
    if not pool:
        return OrderedDict([("localhost", list(range(max(args.num_gpus, 0))))])
    active = parse_inclusion_exclusion(pool, args.include, args.exclude)
    if args.num_nodes > 0:
        active = OrderedDict(list(active.items())[:args.num_nodes])
    if args.num_gpus > 0:
        active = OrderedDict((h, chips[:args.num_gpus]) for h, chips in active.items())
    if not active:
        raise ValueError("no hosts left after filtering")
    return active


def _ssh_reachable(host: str) -> bool:
    if host in ("localhost", "127.0.0.1"):
        return True
    try:
        r = subprocess.run(["ssh", "-o", "PasswordAuthentication=no",
                            "-o", "ConnectTimeout=5", host, "hostname"],
                           capture_output=True, timeout=15)
        return r.returncode == 0
    except (subprocess.TimeoutExpired, FileNotFoundError):
        return False


def encode_world_info(active: "OrderedDict[str, List[int]]") -> str:
    """Compact world description passed to launch.py (base64 json, mirroring
    the reference's encoded world_info argument)."""
    import base64
    import json

    return base64.urlsafe_b64encode(json.dumps(active).encode()).decode()


def main(args=None):
    args = parse_args(args)
    active = build_resource_pool(args)
    hosts = list(active)
    multi_node = args.force_multi or len(hosts) > 1

    if args.elastic_training:
        from deepspeed_tpu.elasticity import validate_elastic_config_from_script_args

        validate_elastic_config_from_script_args(args)

    if multi_node and not args.no_ssh_check and args.launcher in ("ssh", "pdsh"):
        unreachable = [h for h in hosts if not _ssh_reachable(h)]
        if unreachable:
            raise RuntimeError(f"hosts unreachable over ssh: {unreachable}")

    master_addr = args.master_addr or hosts[0]
    env = os.environ.copy()
    # the workers import the deepspeed_tpu this launcher runs from, whether
    # or not it is pip-installed (a checkout launched from another cwd)
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [pkg_root, env.get("PYTHONPATH")]))

    if not multi_node:
        # single host: exec through launch.py in-place (reference :475-486)
        cmd = [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
               f"--world_info={encode_world_info(active)}",
               f"--master_addr={master_addr}", f"--master_port={args.master_port}",
               "--node_rank=0", args.user_script] + args.user_args
        logger.info(f"cmd = {' '.join(map(shlex.quote, cmd))}")
        result = subprocess.run(cmd, env=env)
        sys.exit(result.returncode)

    from deepspeed_tpu.launcher.multinode_runner import get_runner

    runner = get_runner(args.launcher, args, active, master_addr)
    cmd = runner.get_cmd(env, active)
    logger.info(f"cmd = {' '.join(map(shlex.quote, cmd))}")
    result = subprocess.run(cmd, env=runner.export_env(env))
    sys.exit(result.returncode)


if __name__ == "__main__":
    main()
