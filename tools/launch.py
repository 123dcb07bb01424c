#!/usr/bin/env python
"""Multi-process training launcher.

Reference: tools/launch.py over dmlc-tracker (ssh/mpi/sge/yarn/local submit,
launch.py:101-116) — starts scheduler/server/worker processes for the
parameter-server kvstore.

TPU-native: there are no server/scheduler roles — every process is a worker
participating in jax.distributed collectives.  ``--launcher local`` spawns N
worker processes on localhost (the reference's multi-node simulator used by
tests/nightly/dist_sync_kvstore.py); ``--launcher ssh`` runs one process per
host from a hostfile.  Each worker gets MX_KV_RANK / MX_KV_NUM_WORKERS /
MX_KV_ROOT_URI (DMLC_* names also set for reference-script compatibility).

Chips: a process that reaches the TPU claims every chip of its host, and a
chip belongs to one process at a time.  Local mode assigns no chip to any
worker, so it refuses more than one local worker unless the workers are held
to the CPU (``JAX_PLATFORMS=cpu``, the simulator).  On a host with chips, run
one process and let it drive all of them through a mesh; across hosts, use
``--launcher ssh`` with one process per host.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys


def launch_local(num_workers, command, env_base):
    if num_workers > 1 and env_base.get("JAX_PLATFORMS") != "cpu":
        sys.exit("launch.py: %d local workers would each claim every chip "
                 "of this host; set JAX_PLATFORMS=cpu to simulate them on "
                 "the CPU, or run one process over a mesh" % num_workers)
    procs = []
    for rank in range(num_workers):
        env = dict(env_base)
        env.update({
            "MX_KV_RANK": str(rank),
            "MX_KV_NUM_WORKERS": str(num_workers),
            "MX_KV_ROOT_URI": "127.0.0.1",
            "MX_KV_ROOT_PORT": env_base.get("MX_KV_ROOT_PORT", "9876"),
            # reference-compatible names
            "DMLC_WORKER_ID": str(rank),
            "DMLC_NUM_WORKER": str(num_workers),
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_ROLE": "worker",
        })
        procs.append(subprocess.Popen(command, shell=True, env=env))
    code = 0
    try:
        for p in procs:
            p.wait()
            code = code or p.returncode
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGINT)
        for p in procs:
            p.wait()
        code = 1
    return code


def launch_ssh(hostfile, num_workers, command, env_base):
    with open(hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    assert len(hosts) >= num_workers, "hostfile has fewer hosts than -n"
    root = hosts[0]
    procs = []
    for rank in range(num_workers):
        envs = " ".join("%s=%s" % (k, v) for k, v in {
            "MX_KV_RANK": rank, "MX_KV_NUM_WORKERS": num_workers,
            "MX_KV_ROOT_URI": root,
            "MX_KV_ROOT_PORT": env_base.get("MX_KV_ROOT_PORT", "9876"),
        }.items())
        remote = "cd %s && %s %s" % (os.getcwd(), envs, command)
        procs.append(subprocess.Popen(["ssh", hosts[rank], remote]))
    code = 0
    for p in procs:
        p.wait()
        code = code or p.returncode
    return code


def main():
    parser = argparse.ArgumentParser(description="Launch distributed training")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("--launcher", choices=["local", "ssh"], default="local")
    parser.add_argument("-H", "--hostfile", default=None)
    parser.add_argument("--env-server-port", default="9876")
    # REMAINDER: everything after the launcher's own options belongs to the
    # worker command verbatim, including its dashed flags — so launcher
    # options must come BEFORE the command
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no worker command given")
    if args.command[0].startswith("-"):
        parser.error("launcher options must precede the worker command "
                     "(got %r first)" % args.command[0])
    cmd = " ".join(args.command)
    env = dict(os.environ)
    env["MX_KV_ROOT_PORT"] = args.env_server_port
    if args.launcher == "local":
        sys.exit(launch_local(args.num_workers, cmd, env))
    sys.exit(launch_ssh(args.hostfile, args.num_workers, cmd, env))


if __name__ == "__main__":
    main()
