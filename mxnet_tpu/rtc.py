"""Runtime kernel compilation — user-defined accelerator kernels
(reference: ``python/mxnet/rtc.py:42-101`` ``CudaModule``/``CudaKernel``
over NVRTC, ``src/common/rtc.cc``).

TPU-native: the runtime compiler is Pallas/Mosaic instead of NVRTC.
``PallasModule`` accepts Python source text (the analogue of CUDA source
text) or ready callables written against ``jax.experimental.pallas``;
``get_kernel(...).launch(args, grid, out_shape)`` wraps ``pl.pallas_call``
with the same "compile once, launch many" shape.  On hosts without a TPU
the kernel runs in Pallas interpret mode, so the same user code is
testable everywhere (the CPU-oracle strategy of SURVEY §4).
"""
from __future__ import annotations

import numpy as np

from .ndarray import NDArray, _wrap

__all__ = ["PallasModule", "PallasKernel", "CudaModule"]


def _on_tpu():
    import jax

    return jax.default_backend() == "tpu"


class PallasModule:
    """Compile user Pallas kernels at runtime (reference CudaModule).

    Parameters
    ----------
    source : str or dict or callable
        Python source text defining one or more kernel functions written
        with ``pl``/``jnp`` primitives (both names are pre-imported into
        the compilation namespace, like NVRTC's implicit headers), or a
        single callable, or a dict name -> callable.
    exports : list of str
        Kernel names exported from source text (reference parity; ignored
        for callables, which export themselves).
    """

    def __init__(self, source, options=(), exports=()):
        self._kernels = {}
        if callable(source):
            self._kernels[source.__name__] = source
        elif isinstance(source, dict):
            self._kernels.update(source)
        elif isinstance(source, str):
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            ns = {"pl": pl, "jnp": jnp, "jax": jax, "np": np}
            exec(compile(source, "<rtc.PallasModule>", "exec"), ns)
            names = exports or [k for k, v in ns.items()
                                if callable(v) and getattr(
                                    v, "__module__", None) is None]
            for name in names:
                if name not in ns:
                    raise ValueError("export %r not found in source" % name)
                self._kernels[name] = ns[name]
        else:
            raise TypeError("source must be str, dict, or callable")

    def get_kernel(self, name, signature=None):
        """Fetch a compiled kernel handle (reference CudaModule.get_kernel;
        ``signature`` is accepted for API parity and unused — shapes/dtypes
        are taken from the launch arguments)."""
        if name not in self._kernels:
            raise ValueError("kernel %r not in module (have: %s)"
                             % (name, sorted(self._kernels)))
        return PallasKernel(name, self._kernels[name])


class PallasKernel:
    """A launchable kernel (reference CudaKernel.launch)."""

    def __init__(self, name, fn):
        self.name = name
        self._fn = fn
        self._compiled = {}

    def launch(self, args, ctx=None, grid=None, out_shape=None,
               out_dtype="float32", **pallas_kwargs):
        """Run the kernel.

        ``args``: list of NDArrays (inputs).  ``grid``: pallas grid tuple
        (the analogue of CUDA grid_dims).  ``out_shape``: output shape
        (defaults to the first input's).  Extra ``pallas_kwargs`` (e.g.
        ``in_specs``/``out_specs``) pass through to ``pl.pallas_call``.
        """
        import jax
        from jax.experimental import pallas as pl

        datas = [a.data if isinstance(a, NDArray) else a for a in args]
        if out_shape is None:
            out_shape = datas[0].shape
            out_dtype = datas[0].dtype
        key = (tuple((d.shape, str(d.dtype)) for d in datas),
               tuple(grid) if grid else None, tuple(out_shape),
               str(out_dtype),
               tuple(sorted((k, repr(v))
                            for k, v in pallas_kwargs.items())))
        call = self._compiled.get(key)
        if call is None:
            kw = dict(pallas_kwargs)
            if grid is not None:
                kw["grid"] = tuple(grid)
            call = jax.jit(pl.pallas_call(
                self._fn,
                out_shape=jax.ShapeDtypeStruct(tuple(out_shape),
                                               np.dtype(out_dtype)),
                interpret=not _on_tpu(), **kw))
            self._compiled[key] = call
        return _wrap(call(*datas))


class CudaModule:
    """Reference-name stub: CUDA runtime compilation has no TPU analogue;
    use :class:`PallasModule` (same get_kernel/launch surface)."""

    def __init__(self, *a, **kw):
        raise NotImplementedError(
            "CudaModule requires NVRTC/CUDA. On TPU builds use "
            "mx.rtc.PallasModule — same get_kernel/launch API over "
            "Pallas kernels.")
