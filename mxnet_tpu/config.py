"""Environment-variable configuration tier (reference: ~61 ``MXNET_*``
env vars read via ``dmlc::GetEnv`` across ``src/``, documented centrally
in ``docs/faq/env_var.md``).

Each knob is declared once with a type, default, and doc — ``describe()``
prints the env_var.md-style table.  Reference names are kept where the
behavior maps; TPU-obsolete knobs are accepted but marked inert so
existing launch scripts keep working.
"""
from __future__ import annotations

import os

__all__ = ["config", "describe", "Knob"]


class Knob:
    def __init__(self, name, typ, default, doc, inert=False):
        self.name = name
        self.typ = typ
        self.default = default
        self.doc = doc
        self.inert = inert

    @property
    def value(self):
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        if self.typ is bool:
            return raw.strip().lower() not in ("0", "false", "no", "off",
                                               "f", "")
        return self.typ(raw)


class _Config:
    """Typed view over the MXNET_* env tier."""

    _KNOBS = [
        Knob("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
             "Execution engine. 'NaiveEngine' disables op-level jit "
             "compilation (every op runs eagerly interpreted) — the "
             "debugging mode the reference uses to serialize execution "
             "(src/engine/engine.cc:40)."),
        Knob("MXNET_CPU_WORKER_NTHREADS", int, 4,
             "Host-side worker threads (decode/augment pools, e.g. "
             "ImageRecordIter preprocess_threads default; reference "
             "threaded_engine_perdevice.cc:79)."),
        Knob("MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
             "Reference op-bulking switch. Inert: XLA fuses the whole "
             "graph into one module already.", inert=True),
        Knob("MXNET_GPU_MEM_POOL_RESERVE", int, 5,
             "Reference GPU pool reserve %. Inert: XLA owns the HBM "
             "arena.", inert=True),
        Knob("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
             "Reference PS sharding bound. Inert: collectives shard by "
             "mesh, not key size.", inert=True),
        Knob("MXNET_PROFILER_AUTOSTART", bool, False,
             "Start mx.profiler at import (reference env var of the same "
             "name)."),
        Knob("MXNET_ENFORCE_DETERMINISM", bool, False,
             "Ask XLA for deterministic ops (maps to "
             "--xla_gpu_deterministic_ops on GPU; TPU is deterministic "
             "by default)."),
        Knob("MXNET_SUBGRAPH_BACKEND", str, "",
             "Reference subgraph-fusion backend selector. Inert: XLA "
             "fusion replaces subgraph properties.", inert=True),
        Knob("MXNET_DONATE_BUFFERS", bool, True,
             "Donate mutated inputs (params, optimizer state, BN "
             "running stats) to XLA so compiled steps update them "
             "in-place in HBM instead of allocating fresh outputs — the "
             "TPU analogue of the reference CachedOp's static_alloc "
             "in-place memory planning. Donated pre-step buffers are "
             "invalidated; reading one afterwards raises. Set 0 to "
             "fall back to copy-on-step."),
        Knob("MXNET_SHAPE_BUCKETS", str, "",
             "Leading-batch-dim bucketing for the io/DataLoader "
             "boundary and FusedTrainStep: pad ragged batches up to the "
             "next bucket so jit caches key on the bucket, not the raw "
             "shape (reference bucketing module / BucketingModule "
             "analogue). '' disables; 'pow2' rounds up to powers of "
             "two; else a comma list like '8,16,32,64'."),
        Knob("MXNET_TRACE_GUARD", str, "",
             "Runtime trace-safety guard (complements the mxlint static "
             "analyzer): when a device->host sync (NDArray.asnumpy and "
             "everything routed through it: .item(), float(), int()) "
             "executes inside a traced region, 'warn' emits a "
             "RuntimeWarning naming the offending user frame, 'raise' "
             "turns it into dispatch.TraceGuardError. Each hit bumps the "
             "profiler's trace_guard dispatch counter. '' disables."),
        Knob("MXNET_NUMERIC_GUARD", str, "",
             "Numerical-health sentinel over the training hot path "
             "(docs/NUMERICAL_HEALTH.md): a fused on-device finiteness "
             "reduction over loss+gradients rides FusedTrainStep / "
             "Trainer.step. 'warn' counts+warns but still applies the "
             "update; 'skip' keeps params/optimizer state bitwise "
             "unchanged across a non-finite step (selected on device, no "
             "host round-trip); 'escalate' runs the full ladder "
             "skip -> rescale -> rollback-k -> restore-checkpoint -> "
             "exit(77, retryable). '' disables (zero overhead)."),
        Knob("MXNET_ROLLBACK_STEPS", int, 0,
             "Depth k of the bad-step rollback ring (host-RAM snapshots "
             "of params + optimizer state kept by the sentinel; restore "
             "is shape/dtype-preserving so it never recompiles). 0 "
             "disables snapshotting; the escalation ladder then skips "
             "the rollback rung. See docs/NUMERICAL_HEALTH.md."),
        Knob("MXNET_CHAOS", str, "",
             "Deterministic seeded fault-injection plan for the chaos "
             "harness (mxnet_tpu.chaos), e.g. "
             "'seed=7,nan_grad@3,kv_drop@5'. Faults: nan_grad, "
             "bitflip_param, kv_drop, kv_delay, kv_dup, ckpt_truncate, "
             "ckpt_bitflip, loader_raise, slow_replica, replica_crash, "
             "request_burst (serving — docs/SERVING.md). Each firing "
             "bumps the faults_injected dispatch counter. '' disables. "
             "Testing only — never set in production."),
        Knob("MXNET_PROFILER_MAX_EVENTS", int, 1000000,
             "Cap on the profiler's in-RAM chrome-trace event ring "
             "(docs/OBSERVABILITY.md). Beyond it the oldest events are "
             "dropped (counted in the profiler.events_dropped telemetry "
             "counter) so week-long serving runs with the profiler on "
             "cannot grow host memory without bound. Read at import; "
             "profiler.set_max_events() resizes at runtime."),
        Knob("MXNET_TELEMETRY_EXPORT", str, "",
             "Path for the telemetry registry's periodic JSONL export "
             "(one snapshot per line: counters, gauges, histogram "
             "quantiles — docs/OBSERVABILITY.md). '' disables the "
             "exporter thread."),
        Knob("MXNET_TELEMETRY_INTERVAL_S", float, 10.0,
             "Seconds between JSONL telemetry snapshots when "
             "MXNET_TELEMETRY_EXPORT is set."),
        Knob("MXNET_TELEMETRY_HTTP_PORT", int, 0,
             "Serve the telemetry registry on 127.0.0.1:<port> "
             "(/metrics Prometheus text, /metrics.json snapshot). "
             "0 disables. Localhost-only by design."),
        Knob("MXNET_TELEMETRY_COST", bool, True,
             "Capture XLA cost analysis (FLOPs/bytes) for compiled "
             "train steps at first dispatch so live MFU / HBM-"
             "bandwidth-utilization gauges are published with zero "
             "device syncs. Costs one extra (non-compiling) trace per "
             "TrackedJit; set 0 to skip."),
        Knob("MXTPU_EXPLAIN_RECOMPILES", str, "record",
             "Recompile flight recorder (docs/OBSERVABILITY.md diagnosis "
             "plane): on every TrackedJit retrace, diff the call "
             "signature (arg shapes/dtypes/shardings, static args, "
             "donation flags) against the last trace and keep a "
             "human-readable explanation in a capped ring. 'off' "
             "disables capture (counter still ticks); 'record' (default) "
             "captures silently; 'warn' additionally warns on every "
             "retrace after the first trace; 'raise' turns a retrace "
             "into dispatch.RecompileError — the enforcement mode for "
             "zero-recompile contracts."),
        Knob("MXTPU_RECOMPILE_RING", int, 256,
             "Capacity of the recompile flight recorder's explanation "
             "ring (oldest entries dropped). Read when the first entry "
             "is recorded."),
        Knob("MXTPU_RECOMPILE_STORM", int, 16,
             "Retraces within a 60s window that count as a recompile "
             "storm and trigger a postmortem debug bundle (0 disables "
             "the storm trigger)."),
        Knob("MXTPU_DEBUG_BUNDLE_DIR", str, "",
             "Directory for postmortem debug bundles "
             "(docs/OBSERVABILITY.md): on rc-77, sentinel "
             "restore-checkpoint, breaker-trip storms, the bench "
             "regression tripwire, or a recompile storm, one JSON file "
             "capturing the registry snapshot, recent profiler events, "
             "recompile explanations, dispatch stats, memory/fleet "
             "views and the active chaos plan is written here "
             "(inspect with tools/inspect_bundle.py). '' disables."),
        Knob("MXTPU_DEBUG_BUNDLE_KEEP", int, 20,
             "Newest-N bundles kept in MXTPU_DEBUG_BUNDLE_DIR; older "
             "ones are pruned after each write."),
        Knob("MXTPU_DEBUG_BUNDLE_EVENTS", int, 500,
             "How many of the newest profiler ring events, and as many "
             "of the newest spans, each debug bundle embeds."),
        Knob("MXTPU_MEM_ACCOUNTING", bool, True,
             "Tagged device-memory accounting (mxnet_tpu.memory): "
             "per-device live/peak gauges from device.memory_stats() "
             "where the backend reports it (TPU/GPU), falling back to "
             "summing live jax buffers by device on CPU, plus "
             "per-subsystem tag providers (params, optimizer_state, "
             "kv_pages, replica slices) published as mem.* gauges on "
             "every memory.update(). Set 0 to make update() a no-op."),
        Knob("MXTPU_PALLAS", str, "auto",
             "Which implementation a Pallas kernel's entry point takes "
             "(docs/KERNELS.md; one rule, "
             "ops.pallas.common.kernel_impl): 'auto' runs the hand-tiled "
             "kernels (flash attention fwd+bwd, the selective scan, int8 "
             "matmul with fused dequant, fused rmsnorm/xent) on "
             "single-device TPU, flash inside its shard_map wrapper "
             "under a mesh, and the identical-math lax forms elsewhere; "
             "'off' forces the lax forms everywhere; 'interpret' runs "
             "the real kernels through the Pallas interpreter on any "
             "backend (the lax forms under a mesh) — the CPU "
             "parity-testing mode. Each answer bumps a "
             "pallas.select.<kernel>.<impl> telemetry counter."),
        Knob("MXTPU_LOCKDEP", str, "off",
             "Runtime lock-order sanitizer (mxnet_tpu.lockdep; "
             "docs/STATIC_ANALYSIS.md 'Runtime lockdep'): wraps every "
             "threading.Lock/RLock created by mxnet_tpu code at import "
             "and maintains the acquisition-order graph by creation "
             "site. 'record' keeps edges, inversions, and held-across-"
             "blocking events (lockdep.* telemetry gauges + a 'lockdep' "
             "debug-bundle section); 'raise' additionally turns an "
             "acquisition that closes a cycle into "
             "lockdep.LockOrderError at the acquire that would deadlock "
             "— the CI mode for the chaos and gateway suites. 'off' "
             "(default) leaves the factories untouched: zero overhead. "
             "Read once, before the first framework lock exists."),
        Knob("MXNET_INT64_TENSOR_SIZE", bool, False,
             "Opt into int64 tensor sizes/indices (arrays past 2^31 "
             "elements) by enabling jax x64 mode at import — the "
             "analogue of the reference's MXNET_USE_INT64_TENSOR_SIZE "
             "build flag (its large-tensor support is a special build "
             "too). Changes jnp weak-type promotion; use for host-side "
             "large-array jobs, not the TPU hot path."),
    ]

    def __init__(self):
        self._by_name = {k.name: k for k in self._KNOBS}

    def __getattr__(self, item):
        # two env prefixes share the attr namespace: MXNET_* (reference
        # parity knobs) and MXTPU_* (this framework's own runtime knobs)
        for prefix in ("MXNET_", "MXTPU_"):
            key = prefix + item.upper()
            if key in self._by_name:
                return self._by_name[key].value
        raise AttributeError(item)

    def knob(self, name):
        return self._by_name[name]

    def describe(self):
        """env_var.md-style knob table (also module-level describe())."""
        return describe()

    @property
    def naive_engine(self):
        return self.engine_type == "NaiveEngine"


config = _Config()

if config.int64_tensor_size:
    # must happen before any jax computation: index dtypes are chosen at
    # trace time and silently truncate to int32 without x64
    import jax as _jax

    _jax.config.update("jax_enable_x64", True)


def describe():
    """env_var.md-style table of every knob."""
    lines = ["%-32s %-10s %-12s %s" % ("Variable", "Type", "Default",
                                       "Description")]
    for k in _Config._KNOBS:
        doc = k.doc + (" [inert on TPU]" if k.inert else "")
        lines.append("%-32s %-10s %-12s %s" % (k.name, k.typ.__name__,
                                               k.default, doc))
    return "\n".join(lines)
