"""Shared helpers for the Pallas kernel layer.

Besides the numeric helpers this module holds the one rule for which
implementation of a kernel runs (docs/KERNELS.md): every kernel's public
entry point asks :func:`kernel_impl` when its caller forces nothing, and
dispatches among its own private implementations.  :func:`kernel_unit`
wraps a kernel entry in a memoized, labeled ``TrackedJit`` so the recompile
flight recorder and the per-leg cost/MFU attribution see each kernel as its
own unit.
"""
from __future__ import annotations

import threading

import jax

_NEG = -1e30  # masked-logit filler: finite (NaN-safe) but exp() == 0 in f32


def _round_up(x, m):
    return -(-x // m) * m


def kernel_impl(name, sharded=False, per_device=False, count=True):
    """Which implementation of kernel ``name`` runs here: ``'pallas'`` (the
    kernel, on a single-device TPU), ``'interpret'`` (the kernel through the
    Pallas interpreter: any backend, parity testing), ``'sharded'`` (the
    kernel's own ``shard_map`` wrapper; only for a kernel that says it has
    one) or ``'fallback'`` (the same mathematics in lax, which GSPMD shards
    freely).  By the validated ``MXTPU_PALLAS`` knob
    (``dispatch.pallas_mode``):

    ==========  ==================  ===============================
    mode        no mesh             a mesh is active
    ==========  ==================  ===============================
    auto, TPU   pallas              sharded if ``sharded`` else fallback
    auto, else  fallback            fallback
    off         fallback            fallback
    interpret   interpret           fallback
    ==========  ==================  ===============================

    GSPMD cannot partition a Pallas custom call, hence the right column.
    A caller already inside a ``shard_map`` body holds its own device's
    shard: it passes ``per_device`` and is answered by the left column.

    Runs at trace time.  Each answer bumps the ``pallas.select.<name>.<impl>``
    telemetry counter, except a ``per_device`` one (whoever made the body
    made the selection) and one asked with ``count=False`` (a caller that
    may overrule the answer counts its own).
    """
    from ...dispatch import pallas_mode
    from ...parallel.mesh import current_mesh
    mode = pallas_mode()
    meshed = not per_device and current_mesh() is not None
    if mode == "off":
        impl = "fallback"
    elif mode == "interpret":
        impl = "fallback" if meshed else "interpret"
    elif jax.default_backend() != "tpu":
        impl = "fallback"
    elif meshed:
        impl = "sharded" if sharded else "fallback"
    else:
        impl = "pallas"
    if count and not per_device:
        from ... import telemetry as _telemetry
        _telemetry.registry().counter(
            "pallas.select.%s.%s" % (name, impl)).inc()
    return impl


_UNITS = {}
_UNITS_LOCK = threading.Lock()


def kernel_unit(name, fn=None, static_argnums=()):
    """Memoized ``TrackedJit`` wrapper for a kernel entry, labeled
    ``kernel.<name>`` so retraces land in the recompile flight recorder and
    ``.cost_analysis()`` attributes FLOPs/bytes to this kernel alone (the
    bench `kernels` leg and docs/KERNELS.md read these).  The first call
    binds ``fn``; later calls with the same name return the same unit.
    """
    with _UNITS_LOCK:
        unit = _UNITS.get(name)
        if unit is None:
            if fn is None:
                raise KeyError("kernel_unit(%r): not yet bound" % name)
            from ...dispatch import TrackedJit
            unit = _UNITS[name] = TrackedJit(
                fn, static_argnums=static_argnums, label="kernel." + name)
        return unit


def kernel_units():
    """Snapshot of the live kernel units: ``{name: TrackedJit}``."""
    with _UNITS_LOCK:
        return dict(_UNITS)
