#!/usr/bin/env python
"""Distributed job launcher (reference: tools/launch.py:29,71 — the
dmlc-core tracker driving ssh/mpi/yarn/sge/local process groups).

TPU-native: workers connect to each other through jax.distributed (a
gRPC coordinator on worker 0) instead of a ps-lite scheduler, so the
launcher only has to start N processes with the right DMLC_* env vars —
the same contract the reference bootstraps from
(docs distributed_training.md:262-276).

Modes:

  local  (reference `--launcher local`, used by CI to test dist_sync
          without a cluster, ci/docker/runtime_functions.sh:1367-1374)

      python tools/launch.py -n 4 python train.py ...

  ssh    (reference `--launcher ssh -H hostfile`): one worker per
          hostfile line, launched over ssh with the DMLC_* env inlined;
          worker 0's host is the jax.distributed coordinator.

      python tools/launch.py -n 4 --launcher ssh -H hosts.txt \\
          python train.py ...

  mpi    (reference `--launcher mpi`): delegates process placement to
          mpirun; ranks read OMPI_COMM_WORLD_RANK/PMI_RANK for their
          DMLC_WORKER_ID.

--cpu forces the workers onto the CPU backend with a virtual device
each — the way to exercise multi-worker semantics on one host (the
driver's 8-device CPU mesh pattern).

One process per chip: a TPU chip belongs to the first process that
opens it.  The local launcher assigns no chip to a worker, so on a host
with TPU chips it refuses to start more than one worker unless --cpu
(or JAX_PLATFORMS=cpu) keeps them off the chips — the second worker
would otherwise die on the device lock.  A worker per chip is
ROADMAP R7.

Failure handling (reference floor: kvstore get_num_dead_node,
include/mxnet/kvstore.h:380):

  * ``DistKVStore.num_dead_node(timeout_sec=...)`` reports workers
    whose parameter-server heartbeat went stale — poll it from rank 0
    to detect hung/dead peers.
  * ``--max-restarts K`` (local mode) relaunches a worker that exits
    nonzero, up to K times per rank.  This suits IDEMPOTENT worker
    scripts that re-initialize their own state (resume from a
    checkpoint, re-run a data shard).  It does NOT transparently
    resume an in-flight kvstore job: a crashed worker takes its
    parameter-server key shard's memory with it, and bulk-sync
    collectives cannot survive a lost member (jax.distributed tears
    the group down) — for training, recovery is a whole-job restart
    from the last checkpoint (Module.save_checkpoint / Trainer state
    files), the reference's recovery story too.  Use
    ``num_dead_node`` to DETECT the failure promptly; use
    checkpoints to recover.
"""
from __future__ import annotations

import argparse
import glob
import os
import shlex
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(args, rank, root_uri, port):
    env = {
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": root_uri,
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": "0",
    }
    if rank is not None:
        env["DMLC_WORKER_ID"] = str(rank)
    if args.cpu:
        env["JAX_PLATFORMS"] = "cpu"
    for kv in args.env:
        k, _, v = kv.partition("=")
        env[k] = v
    return env


def _local_tpu_chips():
    """TPU chips attached to this host, counted from their device
    files.  The launcher must not ask jax: a parent that has touched
    jax holds the chip its workers need."""
    return len(glob.glob("/dev/accel[0-9]*")) \
        or len(glob.glob("/dev/vfio/[0-9]*"))


def _launch_local(args):
    port = _free_port()
    worker_platforms = {**os.environ, **_worker_env(
        args, 0, "127.0.0.1", port)}.get("JAX_PLATFORMS", "")
    chips = _local_tpu_chips()
    if args.num_workers > 1 and chips and worker_platforms != "cpu":
        raise SystemExit(
            f"launch.py: this host has {chips} TPU chip(s) and the "
            f"local launcher assigns none to a worker: all "
            f"{args.num_workers} workers would open the same chip(s) "
            "and all but the first would die on the device lock.  "
            "Pass --cpu to run the workers on the CPU backend, or "
            "start one worker per host (--launcher ssh/mpi).  A "
            "worker per chip is ROADMAP R7.")

    def spawn(rank):
        env = dict(os.environ)
        env.update(_worker_env(args, rank, "127.0.0.1", port))
        return subprocess.Popen(args.command, env=env)

    procs = [spawn(r) for r in range(args.num_workers)]
    if not args.max_restarts:
        return procs
    # supervise: relaunch nonzero-exit workers up to --max-restarts
    # times per rank (see module docstring for the dist_sync caveat)
    budget = [args.max_restarts] * args.num_workers
    while True:
        live = [p for p in procs if p.poll() is None]
        done = [(r, p) for r, p in enumerate(procs)
                if p.poll() is not None]
        restarted = False
        for r, p in done:
            if p.returncode and budget[r] > 0:
                budget[r] -= 1
                sys.stderr.write(
                    f"[launch] worker {r} exited rc={p.returncode}; "
                    f"restarting ({budget[r]} retries left)\n")
                procs[r] = spawn(r)
                restarted = True
        if not live and not restarted:
            return procs
        import time as _time

        _time.sleep(0.5)


def _launch_ssh(args):
    """Reference ssh_submit (dmlc_tracker/ssh.py): one worker per
    hostfile line; env is inlined into the remote command."""
    if not args.hostfile:
        raise SystemExit("--launcher ssh requires -H/--hostfile")
    with open(args.hostfile) as f:
        hosts = [h for h in (ln.strip() for ln in f)
                 if h and not h.startswith("#")]
    if len(hosts) < args.num_workers:
        raise SystemExit(
            f"hostfile has {len(hosts)} hosts < -n {args.num_workers}")
    root_uri = hosts[0].split(":")[0]
    port = args.port or 9099
    procs = []
    for rank in range(args.num_workers):
        host, _, ssh_port = hosts[rank].partition(":")
        env = _worker_env(args, rank, root_uri, port)
        env_str = " ".join(f"{k}={shlex.quote(v)}"
                           for k, v in env.items())
        remote = (f"cd {shlex.quote(args.workdir or '.')} && "
                  f"env {env_str} "
                  + " ".join(shlex.quote(c) for c in args.command))
        ssh_cmd = [args.ssh_cmd, "-o", "StrictHostKeyChecking=no"]
        if ssh_port:
            ssh_cmd += ["-p", ssh_port]
        procs.append(subprocess.Popen(ssh_cmd + [host, remote]))
    return procs


def _launch_mpi(args):
    """Reference mpi_submit: mpirun owns placement; each rank derives
    DMLC_WORKER_ID from its MPI rank env — kvstore.init_distributed
    falls back to OMPI_COMM_WORLD_RANK/PMI_RANK when DMLC_WORKER_ID is
    absent, so no per-rank env is needed here."""
    root_uri = args.root_uri or "127.0.0.1"
    port = args.port or 9099
    env = _worker_env(args, None, root_uri, port)
    flags = []
    for k, v in env.items():
        flags += ["-x", f"{k}={v}"]
    cmd = ([args.mpirun_cmd, "-n", str(args.num_workers)] + flags
           + ["--allow-run-as-root"] + list(args.command))
    return [subprocess.Popen(cmd)]


def main():
    ap = argparse.ArgumentParser(
        description="launch a multi-worker mxnet_tpu job")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", default="local",
                    choices=["local", "ssh", "mpi"])
    ap.add_argument("-H", "--hostfile", default=None,
                    help="ssh mode: one host[:port] per line")
    ap.add_argument("--ssh-cmd", default="ssh",
                    help="ssh binary (tests substitute a shim)")
    ap.add_argument("--mpirun-cmd", default="mpirun")
    ap.add_argument("--root-uri", default=None,
                    help="coordinator address override")
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--workdir", default=None,
                    help="ssh mode: remote working directory")
    ap.add_argument("--cpu", action="store_true",
                    help="force workers onto the CPU backend (local "
                         "multi-process testing)")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for workers")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="local mode: relaunch a nonzero-exit worker "
                         "up to K times (for idempotent/checkpoint-"
                         "resuming scripts; see docstring)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    launcher = {"local": _launch_local, "ssh": _launch_ssh,
                "mpi": _launch_mpi}[args.launcher]
    procs = launcher(args)
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    sys.exit(rc)


if __name__ == "__main__":
    main()
