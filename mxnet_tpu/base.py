"""Shared plumbing: dtypes, shapes, errors, string/int name registry.

Reference parity: ``python/mxnet/base.py`` (ctypes plumbing) — here there is no C
ABI to marshal through (JAX *is* the runtime), so this module only keeps the
dtype/shape conventions and the error type.
"""
from __future__ import annotations

import numpy as np

__all__ = ["MXNetError", "DTYPE_MAP", "np_dtype", "string_types",
           "encode_rng_state", "decode_rng_state"]

string_types = (str,)


class MXNetError(RuntimeError):
    """Framework error type (reference: dmlc error -> MXNetError)."""


# Reference dtype enum (mshadow/base.h TypeFlag order) — kept so that saved-model
# metadata and operator dtype attributes use the same integer codes.
DTYPE_MAP = {
    0: np.float32,
    1: np.float64,
    2: np.float16,
    3: np.uint8,
    4: np.int32,
    5: np.int8,
    6: np.int64,
    # TPU-native additions (no reference equivalent):
    7: np.dtype("bfloat16") if hasattr(np, "bfloat16") else "bfloat16",
    8: np.bool_,
}
_DTYPE_TO_CODE = {}
for _code, _dt in DTYPE_MAP.items():
    try:
        _DTYPE_TO_CODE[np.dtype(_dt)] = _code
    except TypeError:
        pass


def np_dtype(dtype):
    """Normalize int code / str / np.dtype to np.dtype."""
    if isinstance(dtype, int):
        return np.dtype(DTYPE_MAP[dtype])
    if dtype is None:
        return np.dtype(np.float32)
    try:
        return np.dtype(dtype)
    except TypeError:
        # bfloat16 via ml_dtypes
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, str(dtype)))


def dtype_code(dtype):
    return _DTYPE_TO_CODE[np.dtype(dtype)]


try:  # private (jax has no public spelling); resolved at import so a
    # relocation fails LOUDLY here instead of silently disabling every
    # tracer-poisoning guard built on in_user_trace()
    from jax._src.core import trace_state_clean as _trace_state_clean
except ImportError as _e:  # pragma: no cover
    raise ImportError(
        "jax._src.core.trace_state_clean moved; "
        "update mxnet_tpu.base.in_user_trace for the new location "
        "(the trace-escape guards in registry/random/SymbolBlock "
        "depend on it): %s" % _e)


def in_user_trace():
    """True when user-level jax is tracing (jit/scan/grad over framework
    calls).  Imperative caching/mutation must not capture tracers then."""
    return not _trace_state_clean()


def encode_rng_state(rng):
    """JSON-able snapshot of a ``np.random.RandomState`` (checkpointed by
    the data-iterator ``state_dict`` protocol so shuffle order of FUTURE
    epochs survives a mid-epoch resume)."""
    kind, keys, pos, has_gauss, cached = rng.get_state()
    return [kind, [int(k) for k in keys], int(pos), int(has_gauss),
            float(cached)]


def decode_rng_state(state):
    """Inverse of :func:`encode_rng_state` (a set_state-compatible tuple)."""
    kind, keys, pos, has_gauss, cached = state
    return (kind, np.asarray(keys, dtype=np.uint32), int(pos),
            int(has_gauss), float(cached))
