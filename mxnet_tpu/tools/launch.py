"""Launch a distributed job (reference: ``tools/launch.py:66-105``).

The reference's local launcher forks scheduler + servers + workers as
processes on one host with ``DMLC_*`` role env vars.  The TPU-native
equivalent forks N identical SPMD workers wired to one ``jax.distributed``
coordination service: worker 0 hosts the coordinator, every worker runs the
same script (single-program, multi-data — there are no server/scheduler
roles).

Usage (CLI mirrors the reference)::

    python -m mxnet_tpu.tools.launch -n 4 [--launcher local] \
        [--platform cpu] [--local-devices 2] -- python train.py ...

``--platform cpu`` runs the CPU-emulation harness (gloo collectives, for
tests/CI on one machine — the analogue of the reference's
``--launcher local`` ps-lite testing trick, tests/nightly/dist_sync_*).
It is the only way to run more than one local worker: a chip belongs to
one process at a time and no worker is told which chip is its own, so on a
TPU host ONE process drives all local chips.  On a real TPU pod each host
launches its own worker and the TPU runtime discovers the coordinator
itself; this launcher is then only needed to fan out ssh commands, which
is out of scope (use gcloud / xpk).
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

__all__ = ["launch_local", "main"]


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def launch_local(num_workers, command, platform=None, local_devices=None,
                 env=None, port=None):
    """Fork ``num_workers`` local worker processes running ``command`` and
    wait for them.  Returns the list of exit codes.

    Each worker gets MXNET_TPU_COORDINATOR/NUM_WORKERS/WORKER_ID (consumed
    by ``mxnet_tpu._dist.init_from_env`` at import), so any script that
    does ``import mxnet_tpu`` becomes a distributed worker unmodified —
    the reference's "launch.py wraps an ordinary training script" contract.

    More than one worker needs the workers on the CPU platform
    (``platform="cpu"``, or ``JAX_PLATFORMS=cpu`` in their environment):
    otherwise each would initialise JAX on the same local accelerators.
    """
    worker_env = {**os.environ, **(env or {})}
    on_cpu = (platform or worker_env.get("JAX_PLATFORMS", "")) == "cpu"
    if num_workers > 1 and not on_cpu:
        raise ValueError(
            "%d local workers would each initialise JAX on the same local "
            "accelerator(s), and a chip belongs to one process at a time: "
            "one process drives all local chips. Pass --platform cpu for "
            "the multi-worker CPU emulation harness." % num_workers)
    port = port or _free_port()
    procs = []
    for i in range(num_workers):
        e = dict(worker_env)
        e["MXNET_TPU_COORDINATOR"] = "localhost:%d" % port
        e["MXNET_TPU_NUM_WORKERS"] = str(num_workers)
        e["MXNET_TPU_WORKER_ID"] = str(i)
        if platform:
            e["MXNET_TPU_PLATFORM"] = platform
        if local_devices:
            e["MXNET_TPU_LOCAL_DEVICES"] = str(local_devices)
        procs.append(subprocess.Popen(list(command), env=e))
    return [p.wait() for p in procs]


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="mxnet_tpu.tools.launch", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", default="local",
                    choices=["local", "ssh", "mpi", "sge", "yarn"])
    ap.add_argument("--platform", default=None,
                    help="force worker platform (cpu = emulation harness)")
    ap.add_argument("--local-devices", type=int, default=None,
                    help="virtual devices per worker (cpu platform)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="worker command (prefix with --)")
    args = ap.parse_args(argv)
    if args.launcher != "local":
        raise NotImplementedError(
            "launcher %r: TPU pods are launched per-host by the TPU "
            "runtime (gcloud/xpk); only the local emulation launcher is "
            "provided" % args.launcher)
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        ap.error("no worker command given")
    codes = launch_local(args.num_workers, command,
                         platform=args.platform,
                         local_devices=args.local_devices)
    bad = [(i, c) for i, c in enumerate(codes) if c != 0]
    if bad:
        print("workers failed: %s" % bad, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
