"""``dstpu`` launcher CLI.

Parity target: ``deepspeed/launcher/runner.py:main`` (:436) + ``launch.py`` per-rank
spawn (:237). On TPU pods one process per **host** (not per chip) runs the script and
``jax.distributed.initialize`` handles rendezvous — so the launcher's job collapses
to: parse a hostfile, pick a fan-out transport (ssh, or local for single host /
testing), export the rendezvous env (``DSTPU_COORDINATOR/RANK/WORLD_SIZE``, consumed
by ``comm.init_distributed``), spawn, and propagate failures by killing the cohort
(``sigkill_handler`` runner.py:633 parity).

Usage:
    dstpu --hostfile hosts.txt train.py --args...
    dstpu --num_procs 4 train.py ...     # local multi-process (CPU mesh testing)
    dstpu train.py ...                   # single host
"""

from __future__ import annotations

import argparse
import os
import shlex
import signal
import subprocess
import sys
from typing import Dict, List, Optional, Tuple


def parse_hostfile(path: str) -> Dict[str, int]:
    """``host slots=N`` lines → {host: slots} (runner.py:230 ``fetch_hostfile``)."""
    hosts: Dict[str, int] = {}
    with open(path) as f:
        for line in f:
            line = line.split("#")[0].strip()
            if not line:
                continue
            parts = line.split()
            host = parts[0]
            slots = 1
            for p in parts[1:]:
                if p.startswith("slots="):
                    slots = int(p.split("=", 1)[1])
            if host in hosts:
                raise ValueError(f"duplicate host {host} in {path}")
            hosts[host] = slots
    if not hosts:
        raise ValueError(f"hostfile {path} is empty")
    return hosts


def filter_hosts(hosts: Dict[str, int], include: str = "", exclude: str = ""
                 ) -> Dict[str, int]:
    """``--include``/``--exclude`` host filters (runner.py:310 parity; host-level —
    per-chip slot filtering has no TPU meaning)."""
    out = dict(hosts)
    if include:
        keep = {h.strip() for h in include.split(",") if h.strip()}
        out = {h: s for h, s in out.items() if h in keep}
    if exclude:
        drop = {h.strip() for h in exclude.split(",") if h.strip()}
        out = {h: s for h, s in out.items() if h not in drop}
    if not out:
        raise ValueError("host filters removed every host")
    return out


def _host_has_tpu() -> bool:
    """TPU device nodes on this host, asked without touching JAX (a launcher
    that initialised the backend would hold the chip its child needs)."""
    import glob

    return os.environ.get("JAX_PLATFORMS") != "cpu" and bool(
        glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _spawn_local(args, env_base) -> int:
    """Single-host / multi-process local launch (launch.py:237 spawn loop)."""
    nprocs = max(args.num_procs, 1)
    if nprocs > 1 and _host_has_tpu():
        raise SystemExit(
            "--num_procs > 1 on a TPU host: a chip belongs to one process, "
            "and one process drives all local chips — launch one process "
            "per host (--num_procs is for CPU-backend development)")
    procs: List[subprocess.Popen] = []
    coordinator = f"127.0.0.1:{args.master_port}"

    def killall(signum=None, frame=None):
        for p in procs:
            if p.poll() is None:
                p.kill()

    signal.signal(signal.SIGINT, killall)
    signal.signal(signal.SIGTERM, killall)

    for rank in range(nprocs):
        env = dict(env_base)
        if nprocs > 1:
            env.update({"DSTPU_COORDINATOR": coordinator,
                        "DSTPU_RANK": str(rank),
                        "DSTPU_WORLD_SIZE": str(nprocs)})
        cmd = [sys.executable, args.script] + args.script_args
        procs.append(subprocess.Popen(cmd, env=env))

    code = 0
    try:
        for p in procs:
            rc = p.wait()
            if rc != 0:
                code = rc
                killall()  # one rank failed -> kill the cohort
    finally:
        killall()
    return code


def _spawn_ssh(args, hosts: Dict[str, int], env_base) -> int:
    """Multi-host ssh fan-out (multinode_runner.py PDSH-equivalent over plain ssh)."""
    from deepspeed_tpu.launcher.multinode_runner import (EXPORT_PREFIXES,
                                                         remote_shell_line)

    ordered = list(hosts)
    world = len(ordered)
    master = ordered[0]
    coordinator = f"{master}:{args.master_port}"
    exports = {k: v for k, v in env_base.items()
               if k.startswith(EXPORT_PREFIXES)}
    procs = []
    for rank, host in enumerate(ordered):
        remote = remote_shell_line(args, {
            **exports,
            "DSTPU_COORDINATOR": coordinator,
            "DSTPU_RANK": str(rank),
            "DSTPU_WORLD_SIZE": str(world),
        })
        procs.append(subprocess.Popen(["ssh", "-o", "StrictHostKeyChecking=no",
                                       host, remote]))
    code = 0
    for p in procs:
        rc = p.wait()
        if rc != 0:
            code = rc
            for q in procs:
                if q.poll() is None:
                    q.kill()
    return code


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="dstpu", description=__doc__)
    parser.add_argument("--hostfile", default="", help="host slots=N lines")
    parser.add_argument("--include", default="", help="comma-separated hosts to keep")
    parser.add_argument("--exclude", default="", help="comma-separated hosts to drop")
    parser.add_argument("--num_procs", type=int, default=1,
                        help="local processes (CPU-mesh testing)")
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("--force_multi", action="store_true")
    parser.add_argument("--launcher", default="ssh",
                        choices=["ssh", "pdsh", "openmpi", "slurm", "mpich",
                                 "impi"],
                        help="multi-node transport (multinode_runner.py "
                             "parity); ssh = built-in fan-out")
    parser.add_argument("--slurm_comment", default="")
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    env = dict(os.environ)
    if args.launcher != "ssh" and not args.hostfile:
        raise ValueError(
            f"--launcher {args.launcher} requires --hostfile (the transport "
            "fans the script out to the hostfile's hosts); without it the "
            "script would silently run locally")
    if args.hostfile:
        hosts = filter_hosts(parse_hostfile(args.hostfile), args.include, args.exclude)
        if len(hosts) > 1 or args.force_multi:
            if args.launcher != "ssh":
                from deepspeed_tpu.launcher.multinode_runner import RUNNERS

                runner = RUNNERS[args.launcher](args)
                if not runner.backend_exists():
                    raise RuntimeError(
                        f"--launcher {args.launcher}: transport binary not "
                        "found on this host")
                cmd = runner.get_cmd(env, hosts)
                return subprocess.call(cmd, env=runner.get_env(env, hosts))
            return _spawn_ssh(args, hosts, env)
        if args.launcher != "ssh":
            raise ValueError(
                f"--launcher {args.launcher} given but the (filtered) "
                "hostfile has a single host and --force_multi is unset — "
                "the script would silently run locally; add --force_multi "
                "to fan out to that one host")
    return _spawn_local(args, env)


if __name__ == "__main__":
    sys.exit(main())
