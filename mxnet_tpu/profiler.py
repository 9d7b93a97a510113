"""Profiler (reference: ``python/mxnet/profiler.py:33-291`` over
``src/profiler/profiler.{h,cc}``).

Reference mechanism: engine worker threads wrap op execution in
``ProfileOperator`` spans, C-API calls get ``kAPI`` spans, storage hooks
record memory; output is chrome://tracing JSON plus aggregate per-op tables
(``aggregate_stats.cc``).

TPU-native redesign: there is no engine thread to instrument — XLA owns
device scheduling.  Two layers instead:

* **Host spans** — every imperative op dispatch (``ops/registry.invoke``),
  executor forward/backward, and user ProfileTask/Event/Frame objects are
  recorded wall-clock into an in-process buffer and dumped as
  chrome://tracing JSON (identical consumption story: load in
  ``chrome://tracing`` / Perfetto).  Aggregate per-op stats parity via
  :func:`dumps`.
* **Layer spans** — :func:`span` marks the boundaries between the
  runtime's layers (compilation by phase, the fused Gluon step by phase, a
  generation turn by phase; every name is in :data:`SPANS` and starts with
  ``engine.``).  Unlike the per-op spans these record always, session or
  none, into a second bounded ring on ``time.perf_counter()``'s own clock
  (:func:`spans` reads it back by interval), and enter a
  ``jax.profiler.TraceAnnotation`` so that under any ``jax.profiler`` trace
  the same interval lies beside the device's operations.
* **Device traces** — ``set_config(tensorboard_dir=...)`` brackets the run
  with ``jax.profiler.start_trace/stop_trace``; :func:`device_time_by_scope`
  sums that trace by the ``jax.named_scope`` each operation was traced under
  (:data:`DEVICE_SCOPES`) and by pass (``fwd`` / ``bwd`` / ``remat``), and
  :func:`dumps` prints it under the host's table.
"""
from __future__ import annotations

import bisect
import collections
import json
import os
import re
import sys
import threading
import time

__all__ = ["set_config", "set_state", "start", "stop", "pause", "resume",
           "dump", "dumps", "state", "ProfileDomain", "Task", "Event",
           "Counter", "Frame", "Marker", "dispatch_count", "dispatch_stats",
           "dispatch_value", "record_span", "record_event", "now_us",
           "set_max_events", "recent_events", "span", "spans",
           "record_interval", "spans_lost_before", "SPANS", "SPAN_RING",
           "DEVICE_SCOPES", "device_time_by_scope", "device_table", "live_hlo"]

_lock = threading.Lock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": True,
    "aggregate_stats": False,
    "continuous_dump": False,
    "tensorboard_dir": None,
}
_state = "stop"
_paused = False
# chrome trace events — bounded ring (oldest dropped) so a week-long
# serving run with the profiler on cannot grow host memory without bound;
# all mutation goes through _append/_drain under _lock
_events = collections.deque()
_max_events = int(os.environ.get("MXNET_PROFILER_MAX_EVENTS", "1000000"))
_agg = {}          # name -> [count, total_us, min_us, max_us]
_t0 = time.perf_counter()


def _now_us():
    return (time.perf_counter() - _t0) * 1e6


def now_us():
    """Microseconds on the profiler's span timebase (for callers that
    time work themselves and report it via :func:`record_span`)."""
    return _now_us()


def set_max_events(n):
    """Resize the event ring (``MXNET_PROFILER_MAX_EVENTS`` sets the
    import-time default).  Shrinking drops the oldest events."""
    global _max_events
    n = int(n)
    if n < 1:
        raise ValueError("max events must be >= 1, got %d" % n)
    dropped = 0
    with _lock:
        _max_events = n
        while len(_events) > _max_events:
            _events.popleft()
            dropped += 1
    if dropped:
        _count_dropped(dropped)


def _count_dropped(n):
    from . import telemetry

    telemetry.registry().counter("profiler.events_dropped").inc(n)


def _append(evt):
    """Sole writer into the event ring: append under ``_lock`` (a
    concurrent :func:`dump` snapshot-and-clear can never lose or
    double-write events), evicting the oldest beyond the cap."""
    dropped = 0
    with _lock:
        while len(_events) >= _max_events:
            _events.popleft()
            dropped += 1
        _events.append(evt)
    if dropped:
        _count_dropped(dropped)


def recent_events(n=500):
    """Snapshot of the newest ``n`` chrome-trace events in the ring
    (postmortem debug bundles embed this; the ring itself is left
    untouched)."""
    n = max(0, int(n))
    with _lock:
        if n >= len(_events):
            return list(_events)
        return list(_events)[-n:]


def _active(category="imperative"):
    if _state != "run" or _paused:
        return False
    return bool(_config.get("profile_all")
                or _config.get("profile_" + category, True))


def record_span(name, cat, begin_us, dur_us, tid=None, args=None):
    """Append one complete ('X') chrome-trace span (internal hook API).
    No-op unless the profiler is running (so instrumented library code is
    free to leave Task/Frame objects in place)."""
    if _state != "run" or _paused:
        return
    evt = {"name": name, "cat": cat, "ph": "X",
           "ts": begin_us, "dur": dur_us, "pid": os.getpid(),
           "tid": tid if tid is not None
           else threading.get_ident() % 10000}
    if args:
        evt["args"] = args
    _append(evt)
    if _config.get("aggregate_stats"):
        with _lock:
            a = _agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
            a[0] += 1
            a[1] += dur_us
            a[2] = min(a[2], dur_us)
            a[3] = max(a[3], dur_us)


def record_event(evt):
    """Append one raw chrome-trace event (async 'b'/'e' flow events,
    instants, counters — whatever ``ph`` says).  Fills in ``ts``/``pid``/
    ``tid`` when absent; gated on the profiler running like every other
    recorder.  This is the channel mxnet_tpu.telemetry's request-trace
    helpers emit through."""
    if _state != "run" or _paused:
        return
    e = dict(evt)
    e.setdefault("ts", _now_us())
    e.setdefault("pid", os.getpid())
    e.setdefault("tid", threading.get_ident() % 10000)
    _append(e)


# -- layer spans (always on) ------------------------------------------------
# Every name the package opens through span()/record_interval(); a name may
# end in ":<label>" (a function's name, a TrackedJit's label) and this holds
# the part before the colon.  The benchmark's trace reduction keeps a host
# event only if its name starts with "engine.", so every name does.
SPANS = (
    "engine.import",
    "engine.compile.trace", "engine.compile.lower",
    "engine.compile.backend", "engine.compile.cache_load",
    "engine.cachedop.build",
    "engine.fused.step", "engine.fused.health", "engine.fused.build",
    "engine.fused.cost_probe", "engine.fused.host_scalars",
    "engine.fused.gather", "engine.fused.dispatch",
    "engine.fused.writeback",
    "engine.jit.execute",
    "engine.sync",
    "engine.gen.turn", "engine.gen.admit", "engine.gen.pages",
    "engine.gen.prefill", "engine.gen.decode", "engine.gen.sample",
    "engine.gen.settle",
    "engine.prefill.prepare", "engine.prefill.dispatch",
    "engine.prefill.fetch",
    "engine.decode.prepare", "engine.decode.dispatch",
    "engine.decode.fetch",
)
# Tuples the span ring holds: several 40 s benchmark windows of the Gluon
# step's ten spans a step (a few MB).  A constant, not a knob.
SPAN_RING = 65536
# (name, start_s, end_s, thread, parent, args): both times are
# time.perf_counter() readings, parent is the (name, start_s) of the
# enclosing span of the same thread or None, args a dict or None
_spans = collections.deque(maxlen=SPAN_RING)
_spans_lost = None          # start_s of the newest span the ring evicted
_span_tls = threading.local()
_dropped_counter = None


def _annotation(name):
    """``jax.profiler.TraceAnnotation(name)``; the first use imports jax and
    rebinds this name to the class itself."""
    global _annotation
    import jax.profiler

    _annotation = jax.profiler.TraceAnnotation
    return _annotation(name)


def _span_stack():
    try:
        return _span_tls.stack
    except AttributeError:
        stack = _span_tls.stack = []
        return stack


def _ring_put(rec):
    """Sole writer into the span ring.  ``deque.append`` with a ``maxlen``
    is atomic and drops the oldest; the count and the lost mark are
    statistics and need no lock."""
    global _spans_lost, _dropped_counter
    if len(_spans) == _spans.maxlen:
        _spans_lost = _spans[0][1]
        if _dropped_counter is None:
            _dropped_counter = _registry().counter("profiler.events_dropped")
        _dropped_counter.inc()
    _spans.append(rec)


def _parent_key(stack):
    if not stack:
        return None
    top = stack[-1]
    return (top.name, top.begin)


class _Span:
    """The one span of the package.  ``ring`` spans (:func:`span`) record
    always; per-op spans (:func:`op_span`, :func:`symbolic_span`) exist only
    while a session runs and write the session's chrome event alone."""

    __slots__ = ("name", "cat", "args", "ring", "begin", "end", "_ann",
                 "_dropped")

    def __init__(self, name, cat, args=None, ring=False):
        self.name = name
        self.cat = cat
        self.args = args
        self.ring = ring
        self._dropped = False

    def drop(self):
        """Record nothing when this span closes: a polling loop's turn that
        only waited is no span, and would fill the ring while idle."""
        self._dropped = True

    def __enter__(self):
        try:  # the same interval on the XLA device trace's clock
            self._ann = ann = _annotation(self.name)
            ann.__enter__()
        except Exception:
            self._ann = None
        if self.ring:
            _span_stack().append(self)
        self.begin = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = end = time.perf_counter()
        if self.ring:
            stack = _span_stack()
            stack.pop()
            if not self._dropped:
                _ring_put((self.name, self.begin, end,
                           threading.get_ident(), _parent_key(stack),
                           self.args))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if _state == "run" and not _paused and not self._dropped:
            record_span(self.name, self.cat, (self.begin - _t0) * 1e6,
                        (end - self.begin) * 1e6, args=self.args)
        return False


def span(name, **args):
    """A layer-boundary span: recorded on every use into the span ring
    (no session needed), entered as a ``jax.profiler.TraceAnnotation``, and
    written as a chrome event while a session runs.  ``name`` is one of
    :data:`SPANS`, optionally followed by ``:<label>``."""
    return _Span(name, "engine", args or None, ring=True)


def record_interval(name, start_s, end_s, **args):
    """Report a span after the fact (``time.perf_counter()`` seconds at
    both ends) into the same ring; its parent is the span open on this
    thread now."""
    _ring_put((name, start_s, end_s, threading.get_ident(),
               _parent_key(_span_stack()), args or None))
    if _state == "run" and not _paused:
        record_span(name, "engine", (start_s - _t0) * 1e6,
                    (end_s - start_s) * 1e6, args=args or None)


def spans(start_s=None, end_s=None, prefix="", last=None):
    """The ring's spans that lie wholly inside ``[start_s, end_s]`` (an
    open end where ``None``) and whose name starts with ``prefix``, oldest
    first (a parent before its children); only the newest ``last`` of them
    where that is given (none for 0 or less, as ``recent_events``)."""
    out = [s for s in list(_spans)
           if s[0].startswith(prefix)
           and (start_s is None or s[1] >= start_s)
           and (end_s is None or s[2] <= end_s)]
    out.sort(key=lambda s: (s[1], -s[2]))
    if last is not None:
        last = max(0, int(last))
        out = out[-last:] if last else []
    return out


def spans_lost_before():
    """Start time of the newest span the ring has evicted, None while it
    has evicted nothing: a reader of an interval that begins before it
    holds a part only."""
    return _spans_lost


def op_span(name):
    """Hook for ops/registry.invoke: a span when imperative profiling is
    live, else a no-op context."""
    if _active("imperative"):
        return _Span(name, "operator")
    return _NULL


def symbolic_span(name):
    if _active("symbolic"):
        return _Span(name, "symbolic")
    return _NULL


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


# -- dispatch counters (always on, unlike spans) ----------------------------
# The donation-aware dispatch path (executor / _CachedOp / FusedTrainStep /
# ops.registry) reports cache behaviour here: "jit_cache_hit" /
# "jit_cache_miss" count calls that reused vs. (re)built a compiled
# executable at the step/graph level, "recompile" counts step-level traces,
# "op_recompile" counts per-op jit traces, "donated_bytes" accumulates the
# bytes of device buffers handed to XLA for in-place reuse, and
# "bucket_padded_batches" counts ragged batches padded up to a shape bucket.
# These live in the mxnet_tpu.telemetry registry as Counters under the
# "dispatch." prefix (no profiler session required) so CI can print them
# after every tier-1 run and any exporter can scrape them; when a profiler
# session IS running each update also lands as a chrome-trace counter event.
_DISPATCH_KEYS = ("jit_cache_hit", "jit_cache_miss", "recompile",
                  "op_recompile", "donated_bytes", "bucket_padded_batches",
                  "host_sync", "trace_guard",
                  # numerical-health sentinel + chaos harness
                  # (docs/NUMERICAL_HEALTH.md)
                  "nonfinite_steps", "rollbacks", "divergence_checks",
                  "faults_injected", "corrupt_records", "io_retries",
                  # overload-safe serving layer (docs/SERVING.md)
                  "requests_admitted", "requests_shed", "hedges_fired",
                  "breaker_trips", "batches_closed_by_deadline",
                  "requests_shed_brownout", "brownout_escalated",
                  "brownout_recovered",
                  # continuous-batching generative inference
                  # (docs/GENERATIVE.md)
                  "gen_prefills", "gen_decode_iters", "gen_tokens",
                  "gen_pages_shed", "gen_preempted", "gen_resumed",
                  "gen_brownout_shed",
                  # fleet layer: sharded replicas + autoscaling
                  # (docs/SHARDED_SERVING.md)
                  "fleet_replicas_added", "fleet_replicas_removed",
                  "fleet_scale_ups", "fleet_scale_downs",
                  "fleet_heartbeats", "fleet_heartbeats_dropped",
                  "fleet_reaped",
                  # cross-process fleet: gateway + worker supervision
                  # (docs/SHARDED_SERVING.md "Deployment")
                  "fleet_worker_restarts", "fleet_worker_crashes",
                  "fleet_worker_kills", "fleet_worker_beats",
                  "fleet_worker_beats_failed", "fleet_worker_requests",
                  "fleet_worker_idem_replays",
                  "gateway_requests", "gateway_retries",
                  "gateway_stream_lost", "gateway_stream_resumed",
                  "gateway_registry_errors",
                  # diagnosis plane (docs/OBSERVABILITY.md): cost-capture
                  # failures behind mfu_source fallbacks, and postmortem
                  # bundles written by the debug plane
                  "cost_analysis_failures", "debug_bundles")
_DISPATCH_PREFIX = "dispatch."


def _registry():
    from . import telemetry          # lazy: telemetry imports us back

    return telemetry.registry()


def dispatch_count(name, delta=1):
    """Bump a dispatch counter (internal hook API; unknown names are
    created on the fly so experiments don't need a registry edit)."""
    value = _registry().counter(_DISPATCH_PREFIX + name).inc(delta)
    if _state == "run" and not _paused:
        _append({"name": "dispatch::%s" % name, "cat": "counter",
                 "ph": "C", "ts": _now_us(), "pid": os.getpid(),
                 "args": {"value": value}})


def dispatch_value(name):
    """Current value of one dispatch counter (cheaper than a full
    dispatch_stats snapshot on the hot path)."""
    return _registry().counter(_DISPATCH_PREFIX + name).value


def dispatch_stats(reset=False):
    """Snapshot of the dispatch counters as a plain dict (all the
    well-known keys, zero-filled, plus any ad-hoc ones)."""
    from . import telemetry

    out = {k: 0 for k in _DISPATCH_KEYS}
    for full, metric in _registry().find(_DISPATCH_PREFIX):
        if not isinstance(metric, telemetry.Counter):
            continue
        key = full[len(_DISPATCH_PREFIX):]
        out[key] = metric.reset() if reset else metric.value
    return out


# -- public API (reference profiler.py surface) -----------------------------
def set_config(**kwargs):
    """Configure the profiler (reference :33).  Accepts the reference kwargs
    (filename, profile_all, profile_symbolic, profile_imperative,
    profile_memory, profile_api, aggregate_stats, continuous_dump) plus
    ``tensorboard_dir`` for XLA device traces."""
    unknown = set(kwargs) - set(_config)
    if unknown:
        raise ValueError("unknown profiler options: %s" % sorted(unknown))
    _config.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    """'run' or 'stop' (reference :151)."""
    global _state
    assert state in ("run", "stop"), state
    if state == _state:
        return
    if state == "run":
        _maybe_start_device_trace()
    else:
        _maybe_stop_device_trace()
        if _config.get("continuous_dump"):
            dump()
    _state = state


def start():
    set_state("run")


def stop():
    set_state("stop")


def pause(profile_process="worker"):
    global _paused
    _paused = True


def resume(profile_process="worker"):
    global _paused
    _paused = False


def state():
    return _state


_device_trace_on = False


def _maybe_start_device_trace():
    global _device_trace_on
    tb = _config.get("tensorboard_dir")
    if tb:
        import jax
        # as the benchmark traces: the host's TraceAnnotations (the layer
        # spans) and no Python tracer, which would slow the host it times
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tb, profiler_options=opts)
        _device_trace_on = True


def _maybe_stop_device_trace():
    global _device_trace_on
    if _device_trace_on:
        import jax
        jax.profiler.stop_trace()
        _device_trace_on = False


def dump(finished=True, profile_process="worker"):
    """Write chrome://tracing JSON to ``filename`` (reference :287,
    Profiler::DumpProfile).  ``finished=True`` (default) drains the event
    buffer so back-to-back profile sessions in one process don't
    accumulate (aggregate stats are kept; reset those via dumps)."""
    with _lock:
        payload = {"traceEvents": list(_events),
                   "displayTimeUnit": "ms"}
        if finished:
            _events.clear()
    with open(_config["filename"], "w") as f:
        json.dump(payload, f)


def dumps(reset=False, format="table"):
    """Aggregate per-op stats table (reference :291 over
    aggregate_stats.cc).  Requires ``set_config(aggregate_stats=True)``.
    Where the session took a device trace (``tensorboard_dir``), the device's
    table follows the host's: :func:`device_time_by_scope` of that trace."""
    with _lock:
        rows = sorted(_agg.items(), key=lambda kv: -kv[1][1])
        out = ["%-40s %8s %12s %12s %12s %12s" %
               ("Name", "Calls", "Total(ms)", "Avg(ms)", "Min(ms)",
                "Max(ms)")]
        for name, (cnt, tot, mn, mx) in rows:
            out.append("%-40s %8d %12.3f %12.3f %12.3f %12.3f" %
                       (name, cnt, tot / 1e3, tot / cnt / 1e3, mn / 1e3,
                        mx / 1e3))
        if reset:
            _agg.clear()
    tb = _config.get("tensorboard_dir")
    if tb and _config.get("aggregate_stats") and not _device_trace_on:
        try:
            out += ["", device_table(device_time_by_scope(tb))]
        except (FileNotFoundError, ValueError):
            pass                    # no trace taken, or one with no device
    return "\n".join(out)


# -- object model (reference ProfileDomain/Task/Event/Counter/Frame) --------
class ProfileDomain:
    """Named grouping for profile objects (reference profiler.py Domain)."""

    def __init__(self, name):
        self.name = name


class Task:
    """A named span tied to a domain; start()/stop() (reference Task)."""

    _cat = "task"

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._begin = None

    def start(self):
        self._begin = _now_us()

    def stop(self):
        assert self._begin is not None, "%s not started" % self.name
        record_span("%s::%s" % (self.domain.name, self.name), self._cat,
                    self._begin, _now_us() - self._begin)
        self._begin = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class Event(Task):
    """Like Task but not domain-scoped per-thread (reference Event)."""

    _cat = "event"

    def __init__(self, name):
        self.domain = ProfileDomain("event")
        self.name = name
        self._begin = None


class Frame(Task):
    """Repeating frame span, e.g. one per training iteration."""

    _cat = "frame"


class Counter:
    """A named value tracked over time (reference Counter)."""

    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self._value = value
        if _state != "run" or _paused:
            return
        _append({"name": "%s::%s" % (self.domain.name, self.name),
                 "cat": "counter", "ph": "C", "ts": _now_us(),
                 "pid": os.getpid(),
                 "args": {"value": value}})

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


class Marker:
    """Instant event (reference Marker.mark)."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        if _state != "run" or _paused:
            return
        _append({"name": "%s::%s" % (self.domain.name, self.name),
                 "cat": "marker", "ph": "i", "ts": _now_us(),
                 "pid": os.getpid(), "s": scope[0]})


# ---------------------------------------------------------------------------
# Device time by named scope: where the work happens.  One reducer in the
# program (the benchmark keeps its own, ``benchmarks/harness/trace_reduce.py``,
# as the yardstick's copy: nothing here is imported from it or by it).
# ---------------------------------------------------------------------------
# Every literal the package gives ``jax.named_scope`` (a test greps for
# them).  A scope is a string in the HLO's metadata and costs nothing at run
# time; :func:`device_time_by_scope` sums a device trace by the last of these
# in each operation's path.  The LM train step's (`models/`, `parallel/moe.py`,
# every ``pallas_call``'s own) first, so that no operation of that step lies
# outside a leaf; then the Gluon step's and the mesh's.  A collective's scope
# is ``<what>.<mesh axes>`` (``allreduce.dp``) and this holds ``<what>``.
DEVICE_SCOPES = (
    # the LM step, outside the layers
    "embed", "layers", "norm", "residual", "head", "loss", "optimizer",
    # the attention half: the container, then its leaves
    "attn", "attn.qkv", "attn.qk_norm", "attn.rope", "attn.kv_broadcast",
    "attn.core", "attn.gate", "attn.out",
    "mla.q", "mla.kv_a", "mla.kv_b", "mla.rope",
    "ssm", "ssm.in_proj", "conv", "ssm.x_proj", "ssm.dt", "scan",
    "ssm.out_proj",
    "sconv", "sconv.in_proj", "sconv.out_proj",
    # the MLP half
    "mlp", "mlp.up", "mlp.act", "mlp.down",
    "moe.route", "moe.dispatch", "moe.dispatch.sort", "moe.dispatch.gather",
    "moe.experts", "moe.experts.act", "moe.combine", "moe.combine.gather",
    "moe.combine.sum", "moe.shared", "moe.bias_update",
    # the kernels (`ops/pallas/`): a call's ``name=`` is its scope
    "flash_fwd", "flash_dq", "flash_dkv", "rmsnorm_fwd", "xent_fwd",
    "xent_bwd", "ssm_scan_fwd", "ssm_scan_bwd", "gmm_fwd", "gmm_dx",
    "gmm_dw", "int8_matmul", "int8_matmul_dequant", "short_conv_fwd",
    "short_conv_bwd", "moe_pack", "moe_gather_rows", "moe_gather_grad",
    "moe_slot_sum", "rope_fwd", "rope_bwd",
    # the Gluon step (`gluon/contrib/fused.py`)
    "forward", "backward", "update",
    # the mesh (`parallel/`)
    "ring_kv", "pp_activations", "pp_cotangents", "pp_outputs", "pp_loss",
    "dp_loss", "dp_grads",
    "allreduce", "allgather", "reduce_scatter", "ppermute", "all_to_all",
    "broadcast",
)
_SCOPE_SET = frozenset(DEVICE_SCOPES)
_COLLECTIVE_SCOPES = frozenset(("allreduce", "allgather", "reduce_scatter",
                                "ppermute", "all_to_all", "broadcast"))
_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:(\d+)$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
UNSCOPED = "unscoped"
# under this share of a fusion's weighed bytes in one (leaf, pass) the
# fusion is also counted as mixed
_AGREE = 0.8


def _neutral_trace(profile):
    """``jax.profiler.ProfileData`` -> ``{"planes": [{"name", "lines":
    [{"name", "events": [[name, start_ns, dur_ns, {stats}]]}]}]}``: the
    device planes' op and module lines and the host's ``engine.*`` spans.
    The same form ``benchmarks/harness/trace_reduce.py`` works on, so one
    recorded trace can be checked in as JSON for either."""
    planes = []
    for plane in profile.planes:
        is_dev = _DEVICE_PLANE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (_OPS_LINE, _MODULES_LINE):
                continue
            events = []
            for e in line.events:
                if not is_dev and not e.name.startswith("engine."):
                    continue
                stats = {}
                if is_dev:
                    stats = {k: v for k, v in e.stats
                             if isinstance(v, (str, int, float))}
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _load_trace(trace_dir):
    """The newest trace under ``trace_dir`` in the neutral form: an
    ``*.xplane.pb`` as ``jax.profiler`` leaves it (``plugins/profile/<time>/``),
    or a neutral form recorded from one (``*.neutral.json``, gzipped or not:
    what the tests hold and ``tools/profile_train.py --save`` keeps)."""
    import glob
    import gzip

    pbs = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if pbs:
        from jax.profiler import ProfileData

        return _neutral_trace(ProfileData.from_file(pbs[-1]))
    recorded = sorted(
        glob.glob(os.path.join(trace_dir, "*.neutral.json*"))
        + glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                 "*.neutral.json*")))
    if not recorded:
        raise FileNotFoundError("no jax.profiler trace under %s" % trace_dir)
    opener = gzip.open if recorded[-1].endswith(".gz") else open
    with opener(recorded[-1], "rt") as f:
        return json.load(f)


# -- the compiled module's text ---------------------------------------------
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)]+)")
_KIND = re.compile(r"\bkind=k([A-Za-z]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([^\s,)]+)")
_BRACES = re.compile(r"\{[^{}]*\}")
_ARRAY = re.compile(r"\b([a-z]+)(\d*)[a-z0-9]*\[([0-9,]*)\]")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_OPERAND = re.compile(r"%([^\s,()]+)")
# how far an instruction the compiler made looks for a neighbour's place
_INHERIT_HOPS = 4
# no device time of their own, and no say in a fusion's vote
_NO_VOTE = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast", "iota"))
_PRODUCTS = ("convolution", "dot")


def _bytes_of(shapes):
    total = 0.0
    for dtype, bits, dims in _ARRAY.findall(shapes):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * (int(bits) if bits else 8) / 8.0
    return total


def _group_end(text):
    """Index of the parenthesis that closes the one ``text`` opens with."""
    depth = 0
    for i, ch in enumerate(text):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return i
    return len(text) - 1


def _split_instruction(rest):
    """``<result shape> opcode(operands), attributes`` -> (result, opcode,
    operand names)."""
    while _BRACES.search(rest):
        rest = _BRACES.sub("", rest)
    if rest.startswith("("):
        i = _group_end(rest)
        result, after = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, after = rest.partition(" ")
    opcode, paren, operands = after.partition("(")
    operands = operands[:_group_end(paren + operands)]
    return result, opcode.strip(), tuple(_OPERAND.findall(operands))


class _Module:
    """What the join needs of one compiled module's text: for every
    instruction its opcode, its ``op_name``, its operands and, inside fused
    computations, its result's bytes."""

    def __init__(self, text):
        m = _MODULE.search(text)
        self.name = m.group(1) if m else ""
        # name -> (opcode, op_name, calls, kind, operand names)
        self.inst = {}
        # computation -> [(opcode, op_name, result bytes, calls)]
        self.body = {}
        self._placed = {}
        self._users = None
        # a sort's comparator, a reduction's region: instruction ->
        # computation it stands in, computation -> instruction applying it
        self._comp_of, self._applied_by = {}, {}
        comp = None
        for line in text.splitlines():
            m = _INSTRUCTION.match(line)
            if m is None:
                c = _COMPUTATION.match(line)
                if c:
                    comp = c.group(1)
                    self.body[comp] = []
                continue
            name, rest = m.groups()
            op = _OP_NAME.search(rest)
            op_name = op.group(1) if op else ""
            meta = rest.find(", metadata=")
            head = rest if meta < 0 else rest[:meta]
            result, opcode, operands = _split_instruction(head)
            calls = _CALLS.search(head) if opcode == "fusion" else None
            kind = _KIND.search(head) if opcode == "fusion" else None
            self.inst[name] = (opcode, op_name,
                               calls.group(1) if calls else None,
                               kind.group(1) if kind else None, operands)
            applies = _TO_APPLY.search(head)
            if applies:
                self._applied_by[applies.group(1)] = name
            if comp is not None:
                self._comp_of[name] = comp
                self.body[comp].append((opcode, op_name, _bytes_of(result),
                                        calls.group(1) if calls else None))

    def place(self, instance):
        """``(leaf, pass, category, mixed, inherited)`` of one instruction:
        ``mixed`` is None, or the fusion's two largest ``((leaf, pass),
        share)``.  An instruction the compiler made, with no metadata on it
        or in it (a prefetch of a layer's weights, a constant's broadcast),
        has the place of the nearest of its users that has one, else of its
        operands, else of the instruction that applies the computation it
        stands in (a sort's fused comparator), and ``inherited`` says so."""
        if instance not in self._placed:
            placed = self._own(instance)
            if placed is None:
                placed = self._inherit(instance) or (
                    UNSCOPED, "-", self.category(instance), None, False)
            self._placed[instance] = placed
        return self._placed[instance]

    def fused(self, calls, depth=3):
        """The instructions of a fused computation, a fusion nested in it
        giving its own in its place."""
        for row in self.body.get(calls, ()):
            if row[0] == "fusion" and depth and row[3] in self.body:
                yield from self.fused(row[3], depth - 1)
            else:
                yield row

    def category(self, instance):
        """The opcode; a fusion's kind, ``convolution fusion`` where it
        holds a product."""
        opcode, _name, calls, kind, _operands = self.inst[instance]
        if opcode != "fusion":
            return opcode
        if any(row[0] in _PRODUCTS for row in self.fused(calls)):
            return "convolution fusion"
        return "%s fusion" % (kind or "").lower()

    def _inherit(self, instance):
        if self._users is None:
            self._users = users = {}
            for name, found in self.inst.items():
                for operand in found[4]:
                    users.setdefault(operand, []).append(name)
        for step in (lambda n: self._users.get(n, ()),
                     lambda n: self.inst[n][4] if n in self.inst else ()):
            front, seen = [instance], {instance}
            for _ in range(_INHERIT_HOPS):
                front = [m for n in front for m in step(n)
                         if m not in seen and not seen.add(m)]
                for n in front:
                    placed = self._own(n)
                    if placed is not None and placed[0] != UNSCOPED:
                        return (placed[0], placed[1],
                                self.category(instance), None, True)
        # what the compiler fused inside a comparator has its sort's place
        applier = self._applied_by.get(self._comp_of.get(instance))
        if applier is not None:
            placed = self.place(applier)
            return placed[0], placed[1], self.category(instance), None, True
        return None

    def _own(self, instance):
        """The instruction's place by its own metadata: None where it has
        none at all, ``unscoped`` where its path names no scope."""
        found = self.inst.get(instance)
        if found is None:
            return UNSCOPED, "-", "", None, False
        opcode, op_name, calls, _kind, _operands = found
        category = self.category(instance)
        own_leaf, own_pass = _leaf_and_pass(op_name)
        votes, products, named = {}, {}, bool(op_name)
        for o, name, size, _calls in self.fused(calls):
            if o in _NO_VOTE or not name:
                continue
            named = True
            leaf, pas = _leaf_and_pass(name)
            if pas == "-" and not name.startswith(("jit(", "pjit(")):
                pas = own_pass      # a path cut short: the fusion's says
            if leaf is not None:
                size = max(size, 1.0)
                votes[leaf, pas] = votes.get((leaf, pas), 0.0) + size
                if o in _PRODUCTS:
                    products[leaf, pas] = products.get((leaf, pas), 0) + size
        if not named:
            return None
        if not votes:
            return (own_leaf or UNSCOPED, own_pass if own_leaf else "-",
                    category, None, False)
        ranked = sorted(votes.items(), key=lambda kv: -kv[1])
        total = sum(votes.values())
        # a product's time is the product's, whatever rides along with it
        (leaf, pas) = max(products, key=products.get) if products \
            else ranked[0][0]
        mixed = None
        if votes[leaf, pas] < _AGREE * total:
            mixed = [((leaf, pas), votes[leaf, pas] / total)] + [
                (k, v / total) for k, v in ranked if k != (leaf, pas)][:1]
        return leaf, pas, category, mixed, False



_PATH_TOKEN = re.compile(r"p?jit\([^()]*\)|([^/()]+)")


def _leaf_and_pass(op_name):
    """An operation's path -> (the last component that is in
    :data:`DEVICE_SCOPES` or None, its pass).  ``jit(<function>)`` names a
    function, not a scope; ``jvp(attn)`` and ``transpose(jvp(attn))`` wrap
    the scope ``attn``.  The pass: ``remat`` under ``rematted_computation``
    (a checkpointed layer's second forward), else ``bwd`` under
    ``transpose(``, else ``fwd`` under ``jvp(``, else ``-`` (the optimizer,
    and whatever no autodiff touched)."""
    tokens = [t for t in _PATH_TOKEN.findall(op_name) if t]
    leaf = None
    for t in reversed(tokens):
        if t in _SCOPE_SET:
            leaf = t
            break
        if t.partition(".")[0] in _COLLECTIVE_SCOPES:   # allreduce.dp
            leaf = t.partition(".")[0]
            break
    if "rematted_computation" in tokens:
        return leaf, "remat"
    if "transpose(" in op_name:     # the transform, not the primitive
        return leaf, "bwd"
    if "jvp(" in op_name:
        return leaf, "fwd"
    return leaf, "-"



def live_hlo():
    """The compiled text of every program this process holds loaded on the
    default backend (none before jax is imported): what
    :func:`device_time_by_scope` joins a trace to where it is given no
    ``hlo``, and what to keep beside a trace that is read elsewhere."""
    jax = sys.modules.get("jax")
    if jax is None:
        return []
    return [m.to_string()
            for exe in jax.devices()[0].client.live_executables()
            for m in exe.hlo_modules()]


def _modules_of(hlo):
    """``hlo`` as :func:`device_time_by_scope` takes it -> {module name:
    [_Module]} (two programs of one function share a name)."""
    if hlo is None:
        texts = live_hlo()
    elif isinstance(hlo, str):
        texts = [hlo]
    elif isinstance(hlo, dict):
        texts = list(hlo.values())
    elif isinstance(hlo, (list, tuple)):
        texts = list(hlo)
    else:                                   # a compiled executable
        texts = [hlo.as_text()]
    out = {}
    for text in texts:
        m = _Module(text)
        out.setdefault(m.name, []).append(m)
    return out


def _instance(event_name):
    """On the TPU an op's event is named by its whole HLO line, ``%name =
    ...``; elsewhere by the bare name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(events):
    """Each event's time less what the events nested inside it on the same
    line cover: a ``while`` spans its body's operations, and a loop of 24
    trips would else count its layers twice."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    open_ = []
    for i in order:
        start, dur = events[i][1], events[i][2]
        while open_ and events[open_[-1]][1] + events[open_[-1]][2] <= start:
            open_.pop()
        if open_:
            own[open_[-1]] -= dur
        open_.append(i)
    return [max(t, 0.0) for t in own]


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_time_by_scope(trace_dir, hlo=None, steps=1):
    """Where the device's time went, by named scope and by pass.

    Reads the newest ``jax.profiler`` trace under ``trace_dir`` (its
    ``*.xplane.pb``, through ``jax.profiler.ProfileData``) and joins every
    event of the first device's ``XLA Ops`` line to its instruction in the
    compiled module's text, where the ``op_name`` metadata holds the path of
    ``jax.named_scope`` s and autodiff passes the operation was traced
    under.  ``hlo``: a compiled executable, its ``as_text()``, a ``{module
    name: text}`` map or a list of texts; left out, the event's own line
    where it carries the metadata, else :func:`live_hlo`.  ``steps``: how
    many executions the trace holds; every ``*_ms`` below is a step's.

    Returns a dict: ``rows`` -- ``(leaf, pass, ms, share of busy, calls a
    step, HLO category)`` by falling ms, the leaf being the last component
    of the path that is in :data:`DEVICE_SCOPES` (``unscoped`` where there
    is none, or no instruction of that name: never dropped) and the pass
    ``fwd`` / ``bwd`` / ``remat`` / ``-``; a fusion goes to the (leaf, pass)
    most of its fused instructions lie under by result bytes -- to its
    product's where it holds one (``convolution fusion``: the time is the
    product's, whatever XLA fused round it) -- and where under 80 % of the
    bytes agree it is counted in ``mixed_ms`` too and listed under ``mixed``
    as ``(instance, ms, [((leaf, pass), share), ...two])``;
    times are self times (an event's less the events nested in it).
    ``busy_ms`` (the union of the op intervals), ``window_ms``,
    ``unscoped_ms``, ``mixed_ms``, ``inherited_ms`` (of instructions the
    compiler made, placed by a neighbour: ``_Module.place``); ``gaps`` --
    the device's idle time by the shortest ``engine.*`` span covering each
    gap's middle, ``(name, ms)``, and ``longest_gap`` ``(name, ms)``, one
    gap whole, not a step's share."""
    trace = _load_trace(trace_dir)
    steps = max(int(steps), 1)
    devices = sorted((int(_DEVICE_PLANE.match(p["name"]).group(1)), i)
                     for i, p in enumerate(trace["planes"])
                     if _DEVICE_PLANE.match(p["name"]))
    if not devices:
        raise ValueError("the trace under %s has no device plane (a CPU "
                         "run records none)" % trace_dir)
    plane = trace["planes"][devices[0][1]]
    ops = [e for ln in plane["lines"] if ln["name"] == _OPS_LINE
           for e in ln["events"]]
    runs = sorted((e[1], e[1] + e[2], e[0].split("(", 1)[0])
                  for ln in plane["lines"] if ln["name"] == _MODULES_LINE
                  for e in ln["events"])
    starts = [r[0] for r in runs]
    modules = _modules_of(hlo)

    def module_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][2] if i >= 0 and t < runs[i][1] else None

    # the text of each module that ran: of several programs of one name,
    # the one that knows the most of the instances seen under that name
    ran = [module_at(e[1]) for e in ops]
    seen = {}
    for e, name in zip(ops, ran):
        seen.setdefault(name, set()).add(_instance(e[0]))
    chosen = {}
    for name, instances in seen.items():
        known = modules.get(name) or (
            [m for ms in modules.values() for m in ms] if name is None
            else [])
        if known:
            chosen[name] = max(known, key=lambda m: len(
                instances.intersection(m.inst)))

    rows, mixed, unscoped, inherited = {}, {}, {}, 0.0
    for e, own, module in zip(ops, _self_times(ops), map(chosen.get, ran)):
        name = e[0]
        instance = _instance(name)
        placed = None
        if "op_name=" in name:                  # the line holds its metadata
            own_name = _OP_NAME.search(name)
            leaf, pas = _leaf_and_pass(own_name.group(1) if own_name else "")
            if leaf:
                placed = (leaf, pas, _split_instruction(
                    name.split(" = ", 1)[-1])[1], None, False)
        if placed is None and module is not None:
            placed = module.place(instance)
        if placed is None:
            placed = (UNSCOPED, "-", "", None, False)
        leaf, pas, category, split, lent = placed
        inherited += own * lent
        if not category:
            category = str(e[3].get("hlo_category", "")) or (
                _split_instruction(name.split(" = ", 1)[1])[1]
                if " = " in name else "")
        row = rows.setdefault((leaf, pas, category), [0.0, 0])
        row[0] += own
        row[1] += 1
        if leaf == UNSCOPED:
            unscoped[instance] = unscoped.get(instance, 0.0) + own
        if split:
            m = mixed.setdefault(instance, [0.0, split])
            m[0] += own
    lo = min(e[1] for e in ops) if ops else 0.0
    hi = max(e[1] + e[2] for e in ops) if ops else 0.0
    union = _merged((e[1], e[1] + e[2]) for e in ops)
    busy = sum(e - s for s, e in union)
    ms = 1e-6 / steps
    gaps, longest = _gaps(union, _host_spans(trace))
    return {
        "steps": steps,
        "rows": [(leaf, pas, t * ms, t / busy if busy else 0.0,
                  n / steps, category)
                 for (leaf, pas, category), (t, n) in sorted(
                     rows.items(), key=lambda kv: -kv[1][0])],
        "busy_ms": busy * ms, "window_ms": (hi - lo) * ms,
        "unscoped_ms": sum(unscoped.values()) * ms,
        "unscoped": [(k, v * ms) for k, v in sorted(
            unscoped.items(), key=lambda kv: -kv[1])],
        "inherited_ms": inherited * ms,
        "mixed_ms": sum(t for t, _ in mixed.values()) * ms,
        "mixed": [(k, t * ms, split) for k, (t, split) in sorted(
            mixed.items(), key=lambda kv: -kv[1][0])],
        "gaps": [(k, v * ms) for k, v in gaps],
        "longest_gap": longest and (longest[0], longest[1] * 1e-6),
    }


def _host_spans(trace):
    return [(name, start, start + dur)
            for p in trace["planes"] if not _DEVICE_PLANE.match(p["name"])
            for ln in p["lines"] for name, start, dur, _ in ln["events"]
            if name.startswith("engine.")]


def _gaps(union, spans):
    """The idle time between the first and the last operation, by what the
    host was in: each gap goes to the shortest ``engine.*`` span that
    covers its middle.  Returns ``([(name, ns)] by falling ns, the longest
    single gap as (name, ns) or None)``."""
    by, longest = {}, None
    for (_, end), (start, _) in zip(union, union[1:]):
        mid = (end + start) / 2
        best = None
        for name, a, b in spans:
            if a <= mid <= b and (best is None or b - a < best[1]):
                best = (name, b - a)
        key = best[0] if best else "host(unattributed)"
        by[key] = by.get(key, 0.0) + start - end
        if longest is None or start - end > longest[1]:
            longest = (key, start - end)
    return sorted(by.items(), key=lambda kv: -kv[1]), longest


def device_table(result, min_share=0.0):
    """:func:`device_time_by_scope`'s result as the table ``dumps()``
    prints; rows under ``min_share`` of busy time are summed into one."""
    out = ["device time %.3f ms a step busy of %.3f (%d step%s); unscoped "
           "%.3f ms (%.2f %%), mixed %.3f ms, placed by a neighbour %.3f ms"
           % (result["busy_ms"], result["window_ms"], result["steps"],
              "" if result["steps"] == 1 else "s", result["unscoped_ms"],
              100 * result["unscoped_ms"] / result["busy_ms"]
              if result["busy_ms"] else 0.0, result["mixed_ms"],
              result["inherited_ms"]),
           "%-22s %-6s %10s %7s %8s  %s" % ("Scope", "Pass", "ms/step",
                                            "busy%", "calls", "HLO category")]
    rest = [0.0, 0.0, 0.0]
    for leaf, pas, t, share, calls, category in result["rows"]:
        if share < min_share:
            rest = [rest[0] + t, rest[1] + share, rest[2] + calls]
            continue
        out.append("%-22s %-6s %10.3f %6.2f%% %8.1f  %s"
                   % (leaf, pas, t, 100 * share, calls, category))
    if rest[0]:
        out.append("%-22s %-6s %10.3f %6.2f%% %8.1f  %s"
                   % ("(rows under %.1f %%)" % (100 * min_share), "", rest[0],
                      100 * rest[1], rest[2], ""))
    for instance, t, split in result["mixed"][:10]:
        if min_share and t < min_share * result["busy_ms"]:
            break
        out.append("mixed %-28s %8.3f ms  %s" % (instance, t, ", ".join(
            "%s %s %.0f %%" % (leaf, pas, 100 * share)
            for (leaf, pas), share in split)))
    for name, t in result["gaps"][:5]:
        out.append("idle  %-28s %8.3f ms a step" % (name, t))
    if result["longest_gap"]:
        out.append("idle  longest single gap %.3f ms under %s"
                   % (result["longest_gap"][1], result["longest_gap"][0]))
    return "\n".join(out)
