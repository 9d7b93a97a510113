"""Multi-process (multi-host) runtime initialization.

Reference parity: the reference bootstraps its distributed runtime from env
vars at import time — ``DMLC_ROLE``/``DMLC_PS_ROOT_URI`` set by
``tools/launch.py`` decide worker/server/scheduler inside
``python/mxnet/kvstore_server.py:28-77``.

TPU-native redesign: there are no parameter-server roles.  Every process is
an SPMD worker; ``jax.distributed`` provides the coordination service and
XLA provides the collectives (ICI/DCN on real TPU pods, gloo TCP for the
CPU-emulation harness).  ``mxnet_tpu.tools.launch`` sets::

    MXNET_TPU_COORDINATOR = host:port   of worker 0's coordination service
    MXNET_TPU_NUM_WORKERS = N
    MXNET_TPU_WORKER_ID   = 0..N-1
    MXNET_TPU_PLATFORM    = cpu|tpu     (optional; cpu = emulation harness)
    MXNET_TPU_LOCAL_DEVICES = k         (optional; virtual devices/process)

and ``import mxnet_tpu`` in the worker calls :func:`init_from_env` before
any JAX backend is created — after that ``jax.devices()`` is the global
device set across all workers and kvstore ``dist_*`` collectives are real.
"""
from __future__ import annotations

import os

_initialized = False


def init_from_env():
    """Initialize ``jax.distributed`` from MXNET_TPU_* env vars (no-op when
    they are absent or this process was already initialized)."""
    global _initialized
    coord = os.environ.get("MXNET_TPU_COORDINATOR")
    nproc = int(os.environ.get("MXNET_TPU_NUM_WORKERS", "1"))
    if _initialized or not coord or nproc <= 1:
        return False

    platform = os.environ.get("MXNET_TPU_PLATFORM")
    import jax
    if platform == "cpu":
        # no backend exists yet, so the platform can still be chosen here;
        # the env var is for processes this worker starts
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        jax.config.update("jax_platforms", "cpu")
        # cross-process CPU collectives ride gloo TCP
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.config.update(
            "jax_num_cpu_devices",
            int(os.environ.get("MXNET_TPU_LOCAL_DEVICES", "1")))
        # jax_num_cpu_devices conflicts with an inherited
        # --xla_force_host_platform_device_count (e.g. from test envs)
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" in flags:
            os.environ["XLA_FLAGS"] = " ".join(
                f for f in flags.split()
                if "host_platform_device_count" not in f)
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=nproc,
        process_id=int(os.environ["MXNET_TPU_WORKER_ID"]))
    _initialized = True
    # Scrub the worker env so descendant processes (data-loader workers,
    # subprocess helpers) don't try to re-join the coordination service
    # with a duplicate worker id — they run as plain single-process JAX.
    for var in ("MXNET_TPU_COORDINATOR", "MXNET_TPU_NUM_WORKERS",
                "MXNET_TPU_WORKER_ID", "MXNET_TPU_PLATFORM",
                "MXNET_TPU_LOCAL_DEVICES"):
        os.environ.pop(var, None)
    return True
