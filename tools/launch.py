#!/usr/bin/env python
"""Multi-host job launcher (parity: `tools/launch.py:72-109` of the
reference — dmlc_tracker's `local|ssh|mpi` launchers).

TPU-native mapping: there are no parameter servers; each launched worker is
a JAX process in a multi-controller job. The launcher exports the
environment `jax.distributed.initialize` reads:

    MXTPU_COORDINATOR   (≈ DMLC_PS_ROOT_URI:PORT)
    MXTPU_NUM_PROCESSES (≈ DMLC_NUM_WORKER)
    MXTPU_PROCESS_ID    (rank)

- `--launcher local` spawns N copies of the command on this machine (the
  reference's single-machine multi-process test trick,
  `tests/nightly/test_distributed_training-gpu.sh:25-38`).  It is the
  CPU-TEST launcher (`JAX_PLATFORMS=cpu`): on a TPU host every one of the
  N processes would try to claim every chip, and a chip belongs to one
  process — there ONE process drives all chips of the host (a mesh over
  `jax.devices()`), and multi-host jobs run one process per host (`ssh`).
- `--launcher ssh -H hostfile` prints/execs ssh commands per host.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys


def launch_local(n, command, port=29500):
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env["MXTPU_COORDINATOR"] = f"127.0.0.1:{port}"
        env["MXTPU_NUM_PROCESSES"] = str(n)
        env["MXTPU_PROCESS_ID"] = str(rank)
        # legacy names so reference scripts keep working
        env["DMLC_ROLE"] = "worker"
        env["DMLC_NUM_WORKER"] = str(n)
        env["DMLC_WORKER_ID"] = str(rank)
        procs.append(subprocess.Popen(command, shell=True, env=env))
    # a failed worker must not leave siblings wedged in a collective: kill
    # the remaining workers as soon as any worker exits nonzero
    import time
    rc = 0
    pending = set(procs)
    while pending:
        for p in list(pending):
            code = p.poll()
            if code is None:
                continue
            pending.discard(p)
            rc |= code
            if code != 0:
                for q in pending:
                    q.terminate()
        time.sleep(0.1)
    return rc


def launch_ssh(hosts, n, command, port=29500, dry_run=False):
    coordinator = f"{hosts[0]}:{port}"
    procs = []
    for rank in range(n):
        host = hosts[rank % len(hosts)]
        env = (f"MXTPU_COORDINATOR={coordinator} "
               f"MXTPU_NUM_PROCESSES={n} MXTPU_PROCESS_ID={rank}")
        cmd = f"ssh -o StrictHostKeyChecking=no {host} '{env} {command}'"
        if dry_run:
            print(cmd)
        else:
            procs.append(subprocess.Popen(cmd, shell=True))
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("-p", "--port", type=int, default=29500)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    command = " ".join(args.command)
    if not command:
        ap.error("no command given")
    if args.launcher == "local":
        sys.exit(launch_local(args.num_workers, command, args.port))
    hosts = [l.strip() for l in open(args.hostfile) if l.strip()]
    sys.exit(launch_ssh(hosts, args.num_workers, command, args.port,
                        args.dry_run))


if __name__ == "__main__":
    main()
