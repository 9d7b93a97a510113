"""Runtime feature detection (reference: ``python/mxnet/runtime.py`` over
``src/libinfo.cc`` — enumerate compile/runtime capabilities).

The reference's features are compile flags (CUDA, CUDNN, MKLDNN, …); here
they are runtime probes of the JAX environment (platform, pallas, dtypes,
IO deps), served through the same ``Features``/``feature_list`` API.
"""
from __future__ import annotations

import os

__all__ = ["Feature", "Features", "feature_list", "init_compile_cache",
           "compile_cache_dir", "DEVICE_PEAKS", "device_peaks"]

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  The one
# place a utilization (MFU, HBM share, roofline share) may take its
# denominator from; a device that is not listed has no such number.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 393 TOP/s int8, 819 GB/s HBM per chip).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def device_peaks(device=None):
    """The :data:`DEVICE_PEAKS` row of ``device`` (default: the first
    device of the default backend), or None when its ``device_kind`` is
    not in the table — callers then publish no utilization at all."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return DEVICE_PEAKS.get(device.device_kind)

# Where the persistent XLA compile cache lives when the environment does not
# place it: one fixed path inside the checkout.  The path is part of JAX's
# cache key, so it must not vary between processes or runs.
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".xla_cache")


def init_compile_cache():
    """Make sure JAX's persistent compilation cache has a directory, so
    jitted modules survive process restarts.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps its cache
    there and nothing is set in code.  Where it is not, the cache goes to
    ``.xla_cache/`` at the root of the checkout.  JAX reads the directory at
    compile time, so ``import mxnet_tpu`` calls this before anything can
    compile.  Returns the directory in force."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)
    return compile_cache_dir()


def compile_cache_dir():
    """The persistent-cache directory in force, as JAX reports it."""
    import jax

    return jax.config.jax_compilation_cache_dir


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        return "[%s: %s]" % ("✔" if self.enabled else "✖", self.name)


def _probe():
    import jax

    feats = {}
    try:
        platforms = {d.platform for d in jax.local_devices()}
    except Exception:
        platforms = set()
    feats["TPU"] = "tpu" in platforms
    feats["CPU"] = True
    feats["GPU"] = "gpu" in platforms or "cuda" in platforms
    try:
        import jax.experimental.pallas  # noqa: F401
        feats["PALLAS"] = True
    except Exception:
        feats["PALLAS"] = False
    feats["BF16"] = True  # native on TPU; emulated on host CPU
    feats["INT8"] = True  # int8 dot/conv with int32 accumulation
    feats["F16C"] = False
    feats["INT64_TENSOR_SIZE"] = bool(jax.config.jax_enable_x64)
    feats["COMPILE_CACHE"] = bool(compile_cache_dir())
    feats["DIST_KVSTORE"] = True  # jax.distributed + gloo/ICI collectives
    feats["PROFILER"] = True
    # resilience layer (mxnet_tpu.elastic): background checksummed
    # checkpoint writes, and SIGTERM→checkpoint-at-step-boundary drain
    feats["ASYNC_CHECKPOINT"] = True
    try:
        import signal
        feats["PREEMPTION_DRAIN"] = hasattr(signal, "SIGTERM")
    except Exception:
        feats["PREEMPTION_DRAIN"] = False
    try:
        import cv2  # noqa: F401
        feats["OPENCV"] = True
    except Exception:
        feats["OPENCV"] = False
    try:
        import graphviz  # noqa: F401
        feats["GRAPHVIZ"] = True
    except Exception:
        feats["GRAPHVIZ"] = False
    # reference compile-flags with no TPU analogue: permanently off
    for off in ("CUDA", "CUDNN", "NCCL", "TENSORRT", "MKLDNN", "OPENMP"):
        feats[off] = False
    return feats


class Features(dict):
    """Mapping name -> Feature (reference runtime.Features)."""

    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _probe().items()})

    def is_enabled(self, name):
        return self[name.upper()].enabled

    def __repr__(self):
        return "[%s]" % ", ".join(repr(v) for v in self.values())


def feature_list():
    """List of runtime features (reference runtime.feature_list)."""
    return list(Features().values())
