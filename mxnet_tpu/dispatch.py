"""Donation-aware dispatch layer.

Reference parity target: ``src/imperative/cached_op.cc`` — the CachedOp's
``static_alloc``/``static_shape`` flags pre-plan in-place memory so a step
writes parameters and optimizer state where they already live instead of
allocating fresh outputs, and its shape-keyed executable cache avoids
re-planning.  On TPU the analogous machinery is XLA input/output aliasing
(``jax.jit(..., donate_argnums=...)``), a persistent compilation cache, and
shape bucketing so ragged batches hit an existing executable.

This module centralises the three policies so the executor, ``_CachedOp``,
the fused train step, and the optimizer update path all make the same
decision:

* :func:`donation_active` / :func:`donation_scope` — whether mutated input
  buffers may be donated right now (config knob + thread-local override;
  callers additionally skip donation under autograd recording or when the
  inputs are tracers).
* :func:`bucket_size` / :func:`pad_batch` — leading-dim shape bucketing.
* :class:`TrackedJit` — ``jax.jit`` plus the profiler's dispatch counters
  (cache hits/misses, recompiles, donated bytes).
"""
from __future__ import annotations

import collections
import os
import threading
import time

import numpy as np

__all__ = ["donation_active", "donation_scope", "no_donation",
           "bucket_size", "bucket_spec", "pow2_chain", "pad_batch",
           "TrackedJit",
           "TraceGuardError", "trace_scope", "in_framework_trace",
           "trace_guard_mode", "guard_host_sync", "pallas_mode",
           "RecompileError", "explain_recompiles_mode", "recompile_ring",
           "clear_recompile_ring", "explain_recompiles",
           "first_cost_failure", "note_cost_failure"]

_tls = threading.local()


# -- runtime trace guard ----------------------------------------------------
class TraceGuardError(RuntimeError):
    """A host sync executed inside a traced region while
    ``MXNET_TRACE_GUARD=raise`` (see docs/STATIC_ANALYSIS.md)."""


class trace_scope:
    """Marks this thread as inside a framework trace (``TrackedJit`` /
    ``_CachedOp``) so :func:`guard_host_sync` can attribute violations to
    the jitted function by name.  Re-entrant."""

    __slots__ = ("_label",)

    def __init__(self, label):
        self._label = label

    def __enter__(self):
        stack = getattr(_tls, "trace_stack", None)
        if stack is None:
            stack = _tls.trace_stack = []
        stack.append(self._label)
        return self

    def __exit__(self, *exc):
        _tls.trace_stack.pop()
        return False


def in_framework_trace():
    """Label of the innermost live framework trace on this thread (a
    ``TrackedJit``-compiled function mid-trace), or None."""
    stack = getattr(_tls, "trace_stack", None)
    return stack[-1] if stack else None


def trace_guard_mode():
    """'', 'warn', or 'raise' — the MXNET_TRACE_GUARD knob, validated."""
    from .config import config

    mode = (config.trace_guard or "").strip().lower()
    if mode in ("", "0", "off", "false"):
        return ""
    if mode not in ("warn", "raise"):
        raise ValueError(
            "MXNET_TRACE_GUARD must be '', 'warn' or 'raise'; got %r"
            % mode)
    return mode


def pallas_mode():
    """'auto', 'off', or 'interpret' — the MXTPU_PALLAS knob, validated.

    Read by ``ops.pallas.common.kernel_impl`` (docs/KERNELS.md), which has
    the table: 'auto' is the Pallas kernel on single-device TPU and the lax
    form elsewhere; 'off' the lax form everywhere; 'interpret' the real
    kernels through the Pallas interpreter on any backend (the CPU
    parity-testing mode)."""
    from .config import config

    mode = (config.pallas or "").strip().lower()
    if mode in ("", "1", "on", "true"):
        return "auto"
    if mode in ("0", "false", "no"):
        return "off"
    if mode not in ("auto", "off", "interpret"):
        raise ValueError(
            "MXTPU_PALLAS must be 'auto', 'off' or 'interpret'; got %r"
            % mode)
    return mode


def _offending_frame():
    """(filename, lineno, func, line) of the nearest stack frame outside
    the framework itself — the user code that triggered the sync."""
    import traceback

    pkg_root = os.path.dirname(os.path.abspath(__file__))
    for fr in reversed(traceback.extract_stack()):
        fn = os.path.abspath(fr.filename)
        if not fn.startswith(pkg_root):
            return fr
    return None


def guard_host_sync(kind):
    """Called from every device->host sync choke point (``NDArray.
    asnumpy``).  Inside a traced region — a framework :class:`trace_scope`
    or any live jax trace — a sync is a trace-safety violation: it runs
    once at trace time (baking a constant / stale value into the compiled
    program) or raises a ConcretizationError later.  Under
    ``MXNET_TRACE_GUARD=warn`` this warns; ``raise`` makes it a
    :class:`TraceGuardError`.  Off by default (zero overhead beyond one
    env read)."""
    mode = trace_guard_mode()
    if not mode:
        return
    label = in_framework_trace()
    if label is None:
        from . import base as _base

        if not _base.in_user_trace():
            return
        label = "<jax trace>"
    from . import profiler as _prof

    _prof.dispatch_count("trace_guard")
    fr = _offending_frame()
    where = ("%s:%d in %s(): %s" % (fr.filename, fr.lineno, fr.name,
                                    (fr.line or "").strip())
             if fr is not None else "<unknown frame>")
    msg = ("trace guard: %s during trace of %s — a device->host sync "
           "inside a traced region executes at trace time only (baked "
           "constant / stale value in the compiled program). Offending "
           "frame: %s. Move the sync outside the traced code, or "
           "silence with MXNET_TRACE_GUARD=0." % (kind, label, where))
    if mode == "raise":
        raise TraceGuardError(msg)
    import warnings

    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def donation_active():
    """True when compiled calls may donate mutated input buffers: the
    MXNET_DONATE_BUFFERS knob, unless a :func:`donation_scope` override is
    live on this thread, and never under the naive (eager) engine."""
    override = getattr(_tls, "donate", None)
    if override is not None:
        return override
    from .config import config

    return bool(config.donate_buffers) and not config.naive_engine


class donation_scope:
    """Thread-local donation override.  ``donation_scope(None)`` is a
    no-op passthrough so call sites can wrap unconditionally."""

    def __init__(self, enable):
        self._enable = enable
        self._prev = ()

    def __enter__(self):
        if self._enable is not None:
            self._prev = (getattr(_tls, "donate", None),)
            _tls.donate = bool(self._enable)
        return self

    def __exit__(self, *exc):
        if self._prev:
            _tls.donate = self._prev[0]
            self._prev = ()
        return False


def no_donation():
    """Scope under which donation is off (e.g. when a caller must keep
    reading pre-step buffers)."""
    return donation_scope(False)


# -- shape bucketing --------------------------------------------------------
_POW2 = "pow2"
_spec_cache = {}


def bucket_spec():
    """The parsed MXNET_SHAPE_BUCKETS spec: None (off), 'pow2', or a
    sorted tuple of bucket sizes."""
    from .config import config

    raw = (config.shape_buckets or "").strip().lower()
    return _parse_spec(raw)


def _parse_spec(raw):
    if not raw:
        return None
    got = _spec_cache.get(raw)
    if got is None:
        if raw == _POW2:
            got = _POW2
        else:
            got = tuple(sorted({int(t) for t in raw.split(",") if t.strip()}))
            if not got:
                got = None
        _spec_cache[raw] = got
    return got


def bucket_size(n, spec=None):
    """Padded leading-dim size for a batch of ``n`` rows under ``spec``
    (default: the MXNET_SHAPE_BUCKETS knob).  Returns ``n`` unchanged when
    bucketing is off or ``n`` exceeds the largest bucket (those shapes
    compile on their own, like the reference BucketingModule's default
    bucket)."""
    if spec is None:
        spec = bucket_spec()
    elif isinstance(spec, str):
        spec = _parse_spec(spec.strip().lower())
    if spec is None or n <= 0:
        return n
    if spec == _POW2:
        return 1 << (int(n) - 1).bit_length()
    for b in spec:
        if b >= n:
            return b
    return n


def pow2_chain(cap):
    """Full power-of-two bucket chain up to ``cap``: (1, 2, 4, ..., cap),
    with ``cap`` itself always included even when it is not a power of two.
    The warmup-enumeration companion to ``bucket_size(spec='pow2')``: an
    open-ended pow2 spec cannot be pre-compiled, but a capped chain can —
    consumers (serving batch buckets, generation decode-slot buckets)
    compile every member up front so steady state never retraces."""
    cap = int(cap)
    if cap <= 0:
        return ()
    out = []
    b = 1
    while b < cap:
        out.append(b)
        b <<= 1
    out.append(cap)
    return tuple(out)


def pad_batch(data, target):
    """Pad ``data`` (a jax array) along axis 0 up to ``target`` rows by
    wrapping around existing rows — the reference ``NDArrayIter``
    'pad' last-batch semantics, which keeps padded rows statistically
    plausible (vs. zeros skewing e.g. BN batch stats)."""
    n = data.shape[0]
    if target == n:
        return data
    import jax.numpy as jnp

    idx = np.arange(target) % n
    return jnp.take(data, jnp.asarray(idx), axis=0)


# -- recompile flight recorder ----------------------------------------------
# Every TrackedJit retrace captures the call signature (arg shapes /
# dtypes / shardings, static args, donation flags) and diffs it against
# the previous trace of the same function, producing a human-readable
# explanation ("arg 1 `batch` shape (32, 128) -> (48, 128)") kept in a
# capped ring.  The ring is what /debug/recompiles serves, what debug
# bundles embed, and what the zero-recompile test contracts print on
# failure.  Signature work happens ONLY on a retrace, so steady-state
# cache hits pay nothing.
class RecompileError(RuntimeError):
    """A TrackedJit retraced while ``MXTPU_EXPLAIN_RECOMPILES=raise``
    — the enforcement mode for zero-recompile contracts."""


_ring_lock = threading.Lock()
_ring = None                      # deque, sized lazily from the config knob
_retrace_times = collections.deque(maxlen=256)   # monotonic, storm window
_STORM_WINDOW_S = 60.0
_first_cost_failure = None

_MAX_LEAVES = 16                  # leaf descriptors kept per pytree arg
_MAX_REPR = 80


def explain_recompiles_mode():
    """'off', 'record', 'warn', or 'raise' — the MXTPU_EXPLAIN_RECOMPILES
    knob, validated."""
    from .config import config

    mode = (config.explain_recompiles or "").strip().lower()
    if mode in ("", "0", "false", "no", "off"):
        return "off"
    if mode in ("1", "true", "yes", "on"):
        return "record"
    if mode not in ("record", "warn", "raise"):
        raise ValueError(
            "MXTPU_EXPLAIN_RECOMPILES must be off|record|warn|raise; "
            "got %r" % mode)
    return mode


def _short_repr(x):
    r = repr(x)
    return r if len(r) <= _MAX_REPR else r[:_MAX_REPR - 3] + "..."


def _describe_sharding(x):
    try:
        sh = getattr(x, "sharding", None)
        if sh is None:
            return None
        spec = getattr(sh, "spec", None)
        return str(spec) if spec is not None else type(sh).__name__
    except Exception:
        return None


def _leaf_descriptor(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return {"shape": [int(d) for d in x.shape],
                "dtype": str(x.dtype),
                "sharding": _describe_sharding(x)}
    return {"static": _short_repr(x)}


def _arg_descriptor(x):
    """JSON-ready descriptor of one positional argument: a leaf dict for
    plain arrays/scalars, or a pytree summary (structure string + capped
    leaf list) for containers."""
    if hasattr(x, "shape") and hasattr(x, "dtype") \
            or not isinstance(x, (tuple, list, dict)):
        return _leaf_descriptor(x)
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(x)
    return {"tree": _short_repr(treedef),
            "n_leaves": len(leaves),
            "leaves": [_leaf_descriptor(v) for v in leaves[:_MAX_LEAVES]]}


def _fmt_shape(shape):
    return "(" + ", ".join(str(d) for d in shape) + ")"


def _diff_leaf(old, new, label=""):
    """Human-readable field-level differences between two leaf
    descriptors."""
    out = []
    if "static" in old or "static" in new:
        if old != new:
            out.append("%svalue %s -> %s"
                       % (label, old.get("static", _short_repr(old)),
                          new.get("static", _short_repr(new))))
        return out
    if old.get("shape") != new.get("shape"):
        out.append("%sshape %s -> %s" % (label, _fmt_shape(old["shape"]),
                                         _fmt_shape(new["shape"])))
    if old.get("dtype") != new.get("dtype"):
        out.append("%sdtype %s -> %s" % (label, old["dtype"], new["dtype"]))
    if old.get("sharding") != new.get("sharding"):
        out.append("%ssharding %s -> %s"
                   % (label, old.get("sharding"), new.get("sharding")))
    return out


def _diff_arg(old, new):
    if "leaves" in old or "leaves" in new:
        if "leaves" not in old or "leaves" not in new:
            return ["kind changed: %s -> %s"
                    % ("pytree" if "leaves" in old else "leaf",
                       "pytree" if "leaves" in new else "leaf")]
        out = []
        if old["n_leaves"] != new["n_leaves"]:
            out.append("pytree leaf count %d -> %d"
                       % (old["n_leaves"], new["n_leaves"]))
        for i, (lo, ln) in enumerate(zip(old["leaves"], new["leaves"])):
            out.extend(_diff_leaf(lo, ln, "leaf %d " % i))
        if not out and old["tree"] != new["tree"]:
            out.append("pytree structure changed: %s -> %s"
                       % (old["tree"], new["tree"]))
        return out
    return _diff_leaf(old, new)


def _diff_signature(old, new, argnames):
    """Per-argument differences between two call signatures, each line
    naming the argument position and (when known) its name."""
    changes = []
    if len(old) != len(new):
        changes.append("arity %d -> %d positional args"
                       % (len(old), len(new)))
    for i in range(min(len(old), len(new))):
        if old[i] == new[i]:
            continue
        name = argnames[i] if i < len(argnames) else "arg%d" % i
        for c in _diff_arg(old[i], new[i]):
            changes.append("arg %d `%s` %s" % (i, name, c))
    return changes


def _ring_deque():
    global _ring
    if _ring is None:
        from .config import config

        cap = max(1, int(config.recompile_ring))
        _ring = collections.deque(maxlen=cap)
    return _ring


def _record_entry(entry):
    with _ring_lock:
        _ring_deque().append(entry)


def recompile_ring():
    """The recorded recompile explanations, oldest first (each a
    JSON-ready dict: ts_unix, fn, trace, call, kind, why, changes,
    args, donate_argnums, static_argnums)."""
    with _ring_lock:
        return list(_ring) if _ring is not None else []


def clear_recompile_ring():
    """Drop all recorded explanations (tests / measurement windows)."""
    global _ring
    with _ring_lock:
        _ring = None
    _retrace_times.clear()


def explain_recompiles(last=None, kinds=("retrace",)):
    """Human-readable report of the recorded recompile explanations
    (newest ``last``, default all), filtered to ``kinds`` ('retrace'
    and/or 'initial').  The string the zero-recompile assertions print
    on failure."""
    entries = [e for e in recompile_ring() if e["kind"] in kinds]
    if last is not None:
        entries = entries[-int(last):]
    if not entries:
        return ("no recompile explanations recorded "
                "(MXTPU_EXPLAIN_RECOMPILES=%s)" % explain_recompiles_mode())
    lines = ["%d recompile explanation(s), oldest first:" % len(entries)]
    for e in entries:
        lines.append("  %s trace #%d (call %d): %s"
                     % (e["fn"], e["trace"], e["call"], e["why"]))
    return "\n".join(lines)


def _note_retrace_storm():
    """Feed the storm detector; on threshold, ask the debug plane for a
    bundle (never raises — diagnosis must not take down the job)."""
    from .config import config

    threshold = int(config.recompile_storm)
    if threshold <= 0:
        return
    now = time.monotonic()
    _retrace_times.append(now)
    recent = sum(1 for t in _retrace_times if now - t <= _STORM_WINDOW_S)
    if recent < threshold:
        return
    try:
        from . import debug as _debug

        _debug.write_bundle("recompile_storm",
                            extra={"retraces_in_window": recent,
                                   "window_s": _STORM_WINDOW_S})
    except Exception:
        pass


def note_cost_failure(label, stage, exc):
    """Record a cost-analysis failure: bumps the
    ``cost_analysis_failures`` dispatch counter and keeps the FIRST
    failure's reason so the bench's ``mfu_source`` fallback is
    diagnosable (see :func:`first_cost_failure`)."""
    global _first_cost_failure
    from . import profiler as _prof

    _prof.dispatch_count("cost_analysis_failures")
    if _first_cost_failure is None:
        _first_cost_failure = {
            "fn": label, "stage": stage,
            "error": "%s: %s" % (type(exc).__name__, exc)}


def first_cost_failure():
    """{fn, stage, error} for the first cost-analysis failure in this
    process, or None when every capture succeeded."""
    return dict(_first_cost_failure) if _first_cost_failure else None


# -- counted jit ------------------------------------------------------------
def _donated_nbytes(args, positions):
    total = 0
    for i in positions:
        a = args[i]
        if isinstance(a, (tuple, list)):
            for x in a:
                total += getattr(x, "nbytes", 0)
        else:
            total += getattr(a, "nbytes", 0)
    return total


class TrackedJit:
    """``jax.jit`` wrapper that reports into the profiler's dispatch
    counters: every trace bumps ``recompile``, every call bumps
    ``jit_cache_hit`` or ``jit_cache_miss`` (a call that traced is a miss),
    and donated argument bytes accumulate into ``donated_bytes``.  It is
    also where cost-analysis step accounting hooks in:
    :meth:`cost_analysis` captures XLA's FLOPs/bytes estimate for the
    compiled step so telemetry.StepAccountant can publish live MFU and
    HBM-bandwidth gauges with zero device syncs."""

    __slots__ = ("_jitted", "_donate", "_static", "_cost", "_label",
                 "_argnames", "_last_sig", "_traces", "_calls",
                 "_span_name")

    def __init__(self, fn, donate_argnums=(), static_argnums=(), label=None):
        from . import profiler as _prof

        donate = tuple(donate_argnums)
        self._donate = donate
        self._static = tuple(static_argnums)
        self._cost = None
        self._last_sig = None
        self._traces = 0
        self._calls = 0

        name = label or getattr(fn, "__name__", "tracked_fn")
        self._label = name
        self._span_name = "engine.jit.execute:" + name
        try:
            import inspect

            self._argnames = tuple(
                p.name for p in
                inspect.signature(fn).parameters.values()
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD))
        except (TypeError, ValueError):
            self._argnames = ()

        def traced(*a, **k):
            if not getattr(_tls, "cost_probe", False):
                _prof.dispatch_count("recompile")
            with trace_scope(name):
                return fn(*a, **k)

        traced.__name__ = name
        import jax

        kw = {}
        if donate:
            kw["donate_argnums"] = donate
        if static_argnums:
            kw["static_argnums"] = tuple(static_argnums)
        self._jitted = jax.jit(traced, **kw)

    def __call__(self, *args):
        from . import profiler as _prof

        self._calls += 1
        before = _prof.dispatch_value("recompile")
        # sized before the call consumes them
        nbytes = _donated_nbytes(args, self._donate) if self._donate else 0
        # the span bounds the jitted call alone, so that what a caller's
        # own span has left over is this method's bookkeeping
        with _prof.span(self._span_name):
            out = self._jitted(*args)
        if self._donate:
            _prof.dispatch_count("donated_bytes", nbytes)
        retraced = _prof.dispatch_value("recompile") != before
        _prof.dispatch_count("jit_cache_miss" if retraced
                             else "jit_cache_hit")
        if retraced:
            self._note_trace(args)
        return out

    def _note_trace(self, args):
        """Flight-recorder hook, called only when this call (re)traced:
        capture the signature, diff it against the previous trace, and
        record/warn/raise per the MXTPU_EXPLAIN_RECOMPILES mode.  The
        capture reads only metadata (shape/dtype/sharding avals survive
        donation), never buffer contents."""
        mode = explain_recompiles_mode()
        if mode == "off":
            return
        try:
            sig = [{"static": _short_repr(args[i])} if i in self._static
                   else _arg_descriptor(args[i]) for i in range(len(args))]
        except Exception:
            return
        prev, self._last_sig = self._last_sig, sig
        self._traces += 1
        if prev is None:
            kind, why, changes = "initial", "initial trace", []
        else:
            kind = "retrace"
            changes = _diff_signature(prev, sig, self._argnames)
            why = "; ".join(changes) if changes else (
                "no signature difference detected (jit cache eviction, "
                "or a donation/global-context change)")
        entry = {"ts_unix": round(time.time(), 3), "fn": self._label,
                 "trace": self._traces, "call": self._calls, "kind": kind,
                 "why": why, "changes": changes, "args": sig,
                 "donate_argnums": list(self._donate),
                 "static_argnums": list(self._static)}
        _record_entry(entry)
        from . import telemetry as _telemetry

        _telemetry.trace_instant("recompile::" + self._label,
                                 cat="dispatch",
                                 args={"kind": kind, "why": why})
        if kind != "retrace":
            return
        _note_retrace_storm()
        msg = ("recompile: %s trace #%d (call %d): %s"
               % (self._label, self._traces, self._calls, why))
        if mode == "warn":
            import warnings

            warnings.warn(msg, RuntimeWarning, stacklevel=4)
        elif mode == "raise":
            raise RecompileError(msg)

    def lower(self, *args, **kw):
        return self._jitted.lower(*args, **kw)

    def cost_analysis(self, *args, **kw):
        """XLA's per-execution cost estimate for this function at the
        given concrete args: ``{"flops": float, "bytes_accessed": float}``
        (0.0 where the backend doesn't report), or None when
        unavailable.  Cached after the first successful capture, so call
        it with the first step's args and reuse freely.

        Prefers ``lower().cost_analysis()`` (HLO-level, no XLA
        compilation) and falls back to ``lower().compile()
        .cost_analysis()``.  Lowering re-traces the wrapped function;
        the ``cost_probe`` flag keeps that probe trace out of the
        ``recompile`` counter so cache-hit/miss accounting stays exact.
        """
        if self._cost is not None:
            return self._cost
        from . import profiler as _prof

        _tls.cost_probe = True
        try:
            lowered = self._jitted.lower(*args, **kw)
        except Exception as e:
            note_cost_failure(self._label, "lower", e)
            return None
        finally:
            _tls.cost_probe = False
        ca = None
        try:
            ca = lowered.cost_analysis()
        except Exception:
            ca = None             # HLO-level miss: the compile fallback
        if not ca:                # below is the one that counts
            try:
                ca = lowered.compile().cost_analysis()
            except Exception as e:
                note_cost_failure(self._label, "compile.cost_analysis", e)
                return None
        if isinstance(ca, (list, tuple)):      # some backends: one per device
            ca = ca[0] if ca else {}
        if not isinstance(ca, dict):
            note_cost_failure(self._label, "result",
                              TypeError("cost analysis returned %s"
                                        % type(ca).__name__))
            return None
        self._cost = {
            "flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
        }
        _prof.dispatch_count("cost_analyses")
        return self._cost


# -- compilation by phase ---------------------------------------------------
# JAX reports every program it makes in this process (a plain ``jax.jit``
# as much as a TrackedJit) to its monitoring listeners: tracing, lowering
# and the backend's compile as time spans on ``time.time()`` with the
# function's name, a fetch out of the persistent cache as a duration that
# has just ended.  One listener each puts them into the profiler's span
# ring on ``time.perf_counter()``.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "engine.compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "engine.compile.lower",
    "/jax/core/compile/backend_compile_duration": "engine.compile.backend",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_WALL_TO_PERF = time.perf_counter() - time.time()


def _on_compile_time_span(event, start_time, end_time, fun_name="", **_kw):
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    from . import profiler as _prof

    _prof.record_interval("%s:%s" % (name, fun_name),
                          start_time + _WALL_TO_PERF,
                          end_time + _WALL_TO_PERF)


def _on_compile_duration(event, duration_secs, **_kw):
    if event != _CACHE_LOAD_EVENT:
        return
    from . import profiler as _prof

    end = time.perf_counter()
    _prof.record_interval("engine.compile.cache_load", end - duration_secs,
                          end)


import jax.monitoring as _monitoring  # noqa: E402

_monitoring.register_event_time_span_listener(_on_compile_time_span)
_monitoring.register_event_duration_secs_listener(_on_compile_duration)
