"""Device context management.

Capability parity with the reference's ``Context`` (``python/mxnet/context.py``,
``include/mxnet/base.h`` Context struct): a (dev_type, dev_id) pair with a
thread-local default and a ``with`` scope.  TPU-native design: a Context maps to a
``jax.Device`` (or, for sharded execution, a position in a ``jax.sharding.Mesh``);
there is no per-device stream/thread state here because XLA owns scheduling.
"""
from __future__ import annotations

import threading

import jax

_DEVTYPE_TO_ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
_ID_TO_DEVTYPE = {v: k for k, v in _DEVTYPE_TO_ID.items()}


class Context:
    """A device context, usable as a ``with`` scope to set the default device.

    Unlike the reference (CUDA device + stream), a TPU Context resolves lazily to a
    ``jax.Device``; ``gpu`` is accepted as an alias for the local accelerator so
    reference scripts run unmodified.
    """

    _default_ctx = threading.local()
    devtype2id = _DEVTYPE_TO_ID
    devid2type = _ID_TO_DEVTYPE

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type = device_type.device_type
            self.device_id = device_type.device_id
        else:
            if device_type not in _DEVTYPE_TO_ID:
                raise ValueError("unknown device type %r" % (device_type,))
            self.device_type = device_type
            self.device_id = device_id

    @property
    def device_typeid(self):
        return _DEVTYPE_TO_ID[self.device_type]

    # -- jax bridge ---------------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        ``tpu``/``gpu`` both mean "the local accelerator".  An explicit
        accelerator context never resolves to something else: with no
        accelerator present, or a ``device_id`` past the local device
        count, this raises — code that wants "whatever is here" uses
        ``current_context()``, whose default follows the backend.
        """
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                return jax.local_devices(backend="cpu")[self.device_id]
            except RuntimeError:
                return jax.local_devices()[0]
        accel = [d for d in jax.local_devices() if d.platform != "cpu"]
        if not 0 <= self.device_id < len(accel):
            raise ValueError(
                "%r: this process has %d accelerator device(s) (jax backend "
                "%r)" % (self, len(accel), jax.default_backend()))
        return accel[self.device_id]

    # -- python protocol ----------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls):
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _global_default()


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias for the local accelerator (parity shim: reference scripts say gpu)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the native accelerator of this framework."""
    return Context("tpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def num_gpus():
    """Number of local accelerator devices (TPU chips here)."""
    return len([d for d in jax.local_devices() if d.platform != "cpu"])


def num_tpus():
    return num_gpus()


def current_context():
    return Context.default_ctx()


_GLOBAL_DEFAULT = None


def _global_default():
    # Lazy: resolving devices initializes the jax backend, which we defer until
    # first use so that `import mxnet_tpu` stays cheap.
    global _GLOBAL_DEFAULT
    if _GLOBAL_DEFAULT is None:
        _GLOBAL_DEFAULT = Context("tpu", 0) if num_gpus() else Context("cpu", 0)
    return _GLOBAL_DEFAULT

