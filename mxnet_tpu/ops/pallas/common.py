"""Shared helpers for the Pallas kernel layer.

Besides the numeric helpers this module is the kernel library's front
door (docs/KERNELS.md): every kernel registers its implementations with
:func:`register_impl` and callers resolve them with :func:`select_impl`,
which honors the validated ``MXTPU_PALLAS=auto|off|interpret`` knob
(``dispatch.pallas_mode``).  :func:`kernel_unit` wraps a kernel entry in a
memoized, labeled ``TrackedJit`` so the recompile flight recorder and the
per-leg cost/MFU attribution see each kernel as its own unit.
"""
from __future__ import annotations

import functools
import threading

_NEG = -1e30  # masked-logit filler: finite (NaN-safe) but exp() == 0 in f32


def _round_up(x, m):
    return -(-x // m) * m


def _mesh_active():
    """True when a device mesh is active — GSPMD cannot partition a Pallas
    custom call, so kernels must route to their lax fallbacks (or shard_map
    wrappers) in that case."""
    from ...parallel.mesh import current_mesh
    return current_mesh() is not None


# ---------------------------------------------------------------------------
# kernel-selection registry
# ---------------------------------------------------------------------------

_REGISTRY = {}
_UNITS = {}
_UNITS_LOCK = threading.Lock()


def register_impl(name, *, pallas, fallback, sharded=None):
    """Register kernel ``name``'s implementations.

    ``pallas`` is the single-device Pallas entry point and must accept an
    ``interpret=`` keyword (interpret mode partials it in); ``fallback`` is
    the pure-lax path (identical math, GSPMD-shardable); ``sharded`` is an
    optional mesh-aware wrapper (e.g. a shard_map entry) used under 'auto'
    on TPU when a mesh is active.
    """
    _REGISTRY[name] = {"pallas": pallas, "fallback": fallback,
                       "sharded": sharded}


def _ensure_registered():
    # Kernel modules register at import; pull them in on first lookup so
    # importing only `common` (e.g. from models.transformer) still works.
    from . import (flash_attention, int8_matmul, layers,  # noqa: F401
                   selective_scan)


def select_impl(name):
    """Resolve kernel ``name`` to ``(callable, impl)``.

    ``impl`` is one of ``'pallas'`` (real kernel, single-device TPU),
    ``'sharded'`` (mesh-aware wrapper), ``'interpret'`` (real kernel through
    the Pallas interpreter — any backend, parity testing), or ``'fallback'``
    (pure-lax path).  Selection honors ``MXTPU_PALLAS``:

    * ``auto`` (default): pallas on TPU without a mesh; the sharded wrapper
      (when registered) on TPU under a mesh; lax fallback elsewhere.
    * ``off``: always the lax fallback.
    * ``interpret``: the real kernels via the interpreter, except under an
      active mesh (GSPMD cannot partition the custom call) where the
      fallback keeps semantics identical.

    Runs at trace time; each resolution bumps the
    ``pallas.select.<name>.<impl>`` telemetry counter so kernel routing is
    visible in the registry snapshot.
    """
    if name not in _REGISTRY:
        _ensure_registered()
    entry = _REGISTRY[name]
    from ...dispatch import pallas_mode
    mode = pallas_mode()
    if mode == "interpret" and not _mesh_active():
        fn, impl = functools.partial(entry["pallas"], interpret=True), \
            "interpret"
    elif mode == "off":
        fn, impl = entry["fallback"], "fallback"
    else:
        import jax
        if jax.default_backend() != "tpu":
            fn, impl = entry["fallback"], "fallback"
        elif _mesh_active():
            if entry["sharded"] is not None:
                fn, impl = entry["sharded"], "sharded"
            else:
                fn, impl = entry["fallback"], "fallback"
        else:
            fn, impl = entry["pallas"], "pallas"
    from ... import telemetry as _telemetry
    _telemetry.registry().counter(
        "pallas.select.%s.%s" % (name, impl)).inc()
    return fn, impl


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
