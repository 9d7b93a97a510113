#!/usr/bin/env bash
# CI entrypoint matrix (reference: ci/docker/runtime_functions.sh — the
# function-per-job entrypoints the CI matrix dispatches on).
#
#   ci/runtime_functions.sh <function> [args...]
#
# Shards are grouped so each stays within a CI worker's budget; all run
# on the CPU oracle backend with the virtual 8-device mesh
# (tests/conftest.py forces this; MXTPU_TEST_ON_TPU=1 reruns the same
# corpus on a real chip — the reference's test_operator_gpu.py trick).
set -euo pipefail
cd "$(dirname "$0")/.."

build_native() {
    make -C native
    make -C native test_client cpp_example cpp_train autograd_cpp predict_cpp abi_extras abi_r4
}

sanity_check() {
    # import + op registry + entry-point compile check
    python -c "import mxnet_tpu as mx; import mxnet_tpu.ops.pallas;
from mxnet_tpu.ops import registry
assert len(registry.OPS) > 250, len(registry.OPS)
print('ops:', len(registry.OPS))"
    lint_check
}

lint_check() {
    # mxlint v2 inter-procedural analyzer over the whole tree
    # (docs/STATIC_ANALYSIS.md), gated on the committed baseline ledger:
    # the run fails on any finding NOT in ci/mxlint_baseline.json,
    # whatever its severity — the ratchet only tightens.  Shrink the
    # ledger by fixing findings and rerunning with --write-baseline.
    python -m mxnet_tpu.lint mxnet_tpu/ example/ tools/ \
        --baseline ci/mxlint_baseline.json
    python -m pytest tests/test_lint.py -q
}

lockdep_check() {
    # Runtime lock-order sanitizer (docs/STATIC_ANALYSIS.md "Runtime
    # lockdep"): the concurrency-heavy suites run with every
    # mxnet_tpu-created lock wrapped and MXTPU_LOCKDEP=raise — an
    # acquisition-order inversion anywhere in the chaos or gateway
    # scenarios fails the lane at the acquire that would deadlock.
    python -m pytest tests/test_lockdep.py -q
    MXTPU_LOCKDEP=raise python -m pytest tests/ -q -m chaos
    MXTPU_LOCKDEP=raise python -m pytest tests/test_gateway.py \
        tests/test_serving.py -q -m "not slow"
}

racecheck_check() {
    # Runtime lockset race sanitizer (docs/STATIC_ANALYSIS.md
    # "Data-race detection"): first the detector's own suite, then the
    # concurrency-heavy serving suites with all three runtime
    # sanitizers stacked in raise mode — every tracked serving counter
    # written by two threads without a common lock fails the lane at
    # the racing write (racecheck), every acquisition-order inversion
    # at the acquire that would deadlock (lockdep), and every stranded
    # resource at the first non-quiescent test (leakcheck).
    python -m pytest tests/test_racecheck.py -q
    MXTPU_RACECHECK=raise MXTPU_LOCKDEP=raise MXTPU_LEAKCHECK=raise \
        python -m pytest tests/test_chaos.py tests/test_gateway.py \
        tests/test_failover.py tests/test_migration.py \
        tests/test_racecheck.py -q -m "not slow"
    # the sanitizer itself and the guard-disciplined serving modules it
    # instruments must lint clean under the RC rules — no suppressions
    python -m mxnet_tpu.lint mxnet_tpu/racecheck.py \
        mxnet_tpu/gateway.py mxnet_tpu/fleet_worker.py mxnet_tpu/fleet.py
    if grep -n "mxlint: disable" mxnet_tpu/racecheck.py \
            mxnet_tpu/gateway.py mxnet_tpu/fleet_worker.py \
            mxnet_tpu/fleet.py; then
        echo "racecheck-path modules must not carry mxlint suppressions" >&2
        return 1
    fi
}

tenant_check() {
    # Multi-tenant serving plane (docs/SHARDED_SERVING.md "Multi-tenant
    # serving"): hostile-header hardening, the TenantGovernor's
    # token-bucket/fair-share/exemption admission, the named-route +
    # adapter hot-swap spawned acceptance scenario, the tenant_flood /
    # adapter_swap_mid_burst chaos kinds, and the reactive-vs-predictive
    # autoscaling A/B in SimFleet.  All three runtime sanitizers ride in
    # raise mode: the governor's bucket lock, the worker's multi-route
    # stats lock, and the adapter-swap path cross handler threads, the
    # heartbeat loop, and the scheduler loop.
    MXTPU_RACECHECK=raise MXTPU_LOCKDEP=raise MXTPU_LEAKCHECK=raise \
        python -m pytest tests/test_tenancy.py \
        tests/test_tenant_serving.py -q -m "not slow"
    # the admission-path modules must lint clean with no suppressions
    python -m mxnet_tpu.lint mxnet_tpu/tenancy.py \
        mxnet_tpu/fleet_worker.py mxnet_tpu/gateway.py mxnet_tpu/fleet.py
    if grep -n "mxlint: disable" mxnet_tpu/tenancy.py; then
        echo "tenancy.py must not carry mxlint suppressions" >&2
        return 1
    fi
}

unittest_core() {
    python -m pytest tests/test_operator.py tests/test_operator_corpus.py \
        tests/test_operator_extra.py tests/test_random.py \
        tests/test_ndarray.py tests/test_autograd.py \
        tests/test_higher_order.py tests/test_sparse.py \
        tests/test_torch_oracle.py -q
}

unittest_frontend() {
    python -m pytest tests/test_gluon.py tests/test_module.py \
        tests/test_optimizer.py tests/test_monitor_viz.py \
        tests/test_runtime_config.py tests/test_fixes_r2.py \
        tests/test_fixes_r3.py tests/test_fixes_r4.py \
        tests/test_image.py tests/test_control_flow.py \
        tests/test_custom_op.py tests/test_ops_r4.py \
        tests/test_model_zoo_pretrained.py tests/test_benchmark.py \
        tests/test_io.py -q
}

unittest_parallel() {
    # test_dispatch.py rides with the fused-step tests: donation,
    # persistent compile cache, shape bucketing, and the no-tree-flatten
    # hot-path regression guard.  Every pytest run prints the jit
    # cache-hit/recompile counters via the conftest terminal-summary
    # hook — watch "recompile" for dispatch regressions.
    python -m pytest tests/test_parallel.py tests/test_dist.py \
        tests/test_fused_step.py tests/test_dispatch.py \
        tests/test_elastic.py tests/test_async_kv.py \
        tests/test_data_parallel.py tests/test_gradient_compression.py -q
}

fault_injection_smoke() {
    # Preemption-safety smoke (docs/FAULT_TOLERANCE.md): one supervised
    # run per fault mode — mid-epoch crash, SIGTERM drain, torn save —
    # each must resume to a final bit-identical to the clean oracle.
    # Budget: 60s wall (the e2e suite proper lives in test_elastic.py).
    timeout 60 env JAX_PLATFORMS=cpu MXTPU_RESTART_BACKOFF=0.05 \
        python - <<'PY'
import json, os, sys, tempfile
sys.path.insert(0, "tests")
from conftest import subprocess_env
from mxnet_tpu.elastic import supervise

env = subprocess_env(MXTPU_RESTART_BACKOFF="0.05")
d = tempfile.mkdtemp()
worker = os.path.join("tests", "elastic_worker.py")

def run(name, fault):
    p = os.path.join(d, name)
    supervise([sys.executable, worker, p, "10"], max_restarts=2,
              env={**env, **fault})
    return json.load(open(p + ".final.json"))

clean = run("clean", {})
for name, fault in (("crash", {"MXTPU_FI_AT_STEP": "7"}),
                    ("sigterm", {"MXTPU_FI_SIGTERM_AT_STEP": "4"}),
                    ("torn", {"MXTPU_FI_CRASH_AFTER_PARAMS": "5"})):
    got = run(name, fault)
    assert got["w"] == clean["w"] and got["b"] == clean["b"], name
    print("fault mode %-8s -> bit-identical resume" % name)
print("fault_injection_smoke OK")
PY
}

chaos_check() {
    # Numerical-health sentinel + chaos fault-injection matrix
    # (docs/NUMERICAL_HEALTH.md): every seeded fault plan in
    # tests/test_chaos.py — NaN-gradient skip/rollback/rescale/restore
    # escalation, KV drop/delay/dup healing, checkpoint-corruption CRC
    # fallback, loader skip-and-count — plus the preemption smoke.
    # MXTPU_LEAKCHECK=raise: every test must end quiescent — pages
    # freed, probe slots released, admitted futures settled
    # (docs/STATIC_ANALYSIS.md "Runtime leakcheck").
    MXTPU_LEAKCHECK=raise python -m pytest tests/ -q -m chaos
    fault_injection_smoke
}

unittest_serving() {
    python -m pytest tests/test_predict.py tests/test_native.py \
        tests/test_quantization.py tests/test_pallas.py \
        tests/test_profiler.py tests/test_rtc.py tests/test_contrib.py \
        tests/test_detection.py tests/test_serde_interop.py \
        tests/test_onnx.py -q
}

serving_check() {
    # Overload-safe serving front (docs/SERVING.md): admission/shedding,
    # deadline batching, hedging, circuit breaker, SIGTERM drain (rc 76),
    # hot-swap reload, and the chaos acceptance scenario (replica_crash +
    # request_burst: every admitted request gets exactly one typed
    # terminal outcome, queue depth bounded, breaker recovers).
    python -m pytest tests/test_serving.py -q
    # the serving module must lint clean — NO suppressions: the batcher
    # holds a lock, so a single CC001 slip is a latency cliff
    python -m mxnet_tpu.lint mxnet_tpu/serving.py
    if grep -n "mxlint: disable" mxnet_tpu/serving.py; then
        echo "serving.py must not carry mxlint suppressions" >&2
        return 1
    fi
}

gen_check() {
    # Continuous-batching generative inference (docs/GENERATIVE.md):
    # paged-KV decode parity vs the full-forward oracle, zero recompiles
    # across join/leave churn on a warmed server, bitwise solo-vs-batched
    # token streams, typed Overloaded on page exhaustion, and the
    # exactly-one-typed-outcome contract under drain.
    python -m pytest tests/test_generation.py -q
    # the generation module must lint clean — NO suppressions: the
    # scheduler holds a lock between device iterations, so a single
    # CC001 slip stalls every active stream at once
    python -m mxnet_tpu.lint mxnet_tpu/generation.py
    if grep -n "mxlint: disable" mxnet_tpu/generation.py; then
        echo "generation.py must not carry mxlint suppressions" >&2
        return 1
    fi
}

kernel_check() {
    # Pallas kernel program (docs/KERNELS.md): kernel_impl's mode
    # semantics, flash-attention fwd+bwd parity (incl. the lse-cotangent
    # custom VJP), int8 matmul int32 exactness + fused per-channel
    # dequant oracle, and the quantized_dense wiring.  The second run
    # routes every entry point through the Pallas interpreter —
    # the CPU stand-in for the real kernels.
    python -m pytest tests/test_pallas.py tests/test_quantization.py -q
    MXTPU_PALLAS=interpret python -m pytest tests/test_pallas.py -q
    # the kernel layer must lint clean — NO suppressions: these are the
    # hand-written hot paths everything else trusts blindly
    python -m mxnet_tpu.lint mxnet_tpu/ops/pallas/ mxnet_tpu/ops/quantization.py
    if grep -rn "mxlint: disable" mxnet_tpu/ops/pallas/ \
            mxnet_tpu/ops/quantization.py; then
        echo "kernel-layer modules must not carry mxlint suppressions" >&2
        return 1
    fi
}

fleet_check() {
    # Fleet layer (docs/SHARDED_SERVING.md): pjit-sharded replicas over
    # mesh slices (single-device output parity, zero under-load
    # recompiles, param-ownership regression), KV-backed registry
    # TTL/reap semantics, and the shed-rate autoscaler acceptance —
    # scale-up on burst, drain on idle, chaos registry_stale +
    # replica_slow_start convergence with every request typed.
    python -m pytest tests/test_fleet.py -q
    # the fleet module must lint clean — NO suppressions: both
    # supervisor loops run lock-free by design, so a single CC001 slip
    # means someone added a lock across a blocking registry RPC
    python -m mxnet_tpu.lint mxnet_tpu/fleet.py
    if grep -n "mxlint: disable" mxnet_tpu/fleet.py; then
        echo "fleet.py must not carry mxlint suppressions" >&2
        return 1
    fi
}

gateway_check() {
    # Cross-process fleet (docs/SHARDED_SERVING.md "Deployment"):
    # gateway routing/affinity units, worker idempotent replay,
    # partition staleness + heal, supervisor restart semantics, the
    # mid-stream ReplicaLost contract, and the spawned 2-process
    # acceptance scenario (worker_kill + gateway_partition mid-burst,
    # every request typed, killed worker back in rotation, survivor
    # zero-recompile across the process boundary).
    # MXTPU_LEAKCHECK=raise: a resume-heavy burst must leave zero live
    # stream journals and zero unsettled futures behind
    MXTPU_LEAKCHECK=raise python -m pytest tests/test_gateway.py -q \
        -m "not slow"
    # both new modules must lint clean — NO suppressions: the gateway
    # handler threads and the worker heartbeat do blocking socket I/O,
    # so a single CC001 slip serializes the whole front door
    python -m mxnet_tpu.lint mxnet_tpu/gateway.py mxnet_tpu/fleet_worker.py
    if grep -n "mxlint: disable" mxnet_tpu/gateway.py \
            mxnet_tpu/fleet_worker.py; then
        echo "gateway.py/fleet_worker.py must not carry mxlint suppressions" >&2
        return 1
    fi
}

failover_check() {
    # Durable generation streams (docs/SHARDED_SERVING.md failure
    # matrix, docs/GENERATIVE.md QoS/brownout): bitwise greedy resume +
    # seeded-sampled replay after preemption, QoS-tiered victim
    # selection under page exhaustion (preempt before shed; shed only
    # when every victim is same-or-higher priority), the chaos
    # worker_kill_mid_decode / page_pressure gates, and the brownout
    # ladder engaging and fully recovering with hysteresis.  Runs
    # under the lockdep sanitizer in raise mode: the resume path
    # crosses the scheduler loop, the allocator, and gateway handler
    # threads — any new lock inversion should fail here, not deadlock
    # in production.  Leakcheck rides along in raise mode: a failover
    # or preemption that strands a page, probe slot, or future fails
    # the lane at the first non-quiescent test.
    MXTPU_LOCKDEP=raise MXTPU_LEAKCHECK=raise \
        python -m pytest tests/test_failover.py \
        tests/test_gateway.py -q -m "not slow"
    # every module the failover path touches must lint clean — NO
    # suppressions: preemption holds allocator state across the
    # scheduler turn and the gateway journals inside handler threads
    python -m mxnet_tpu.lint mxnet_tpu/generation.py \
        mxnet_tpu/serving.py mxnet_tpu/gateway.py mxnet_tpu/fleet.py \
        mxnet_tpu/fleet_worker.py mxnet_tpu/simfleet.py \
        mxnet_tpu/loadgen.py mxnet_tpu/chaos.py
    if grep -n "mxlint: disable" mxnet_tpu/generation.py \
            mxnet_tpu/serving.py mxnet_tpu/gateway.py \
            mxnet_tpu/fleet.py mxnet_tpu/fleet_worker.py \
            mxnet_tpu/simfleet.py mxnet_tpu/loadgen.py \
            mxnet_tpu/chaos.py; then
        echo "failover-path modules must not carry mxlint suppressions" >&2
        return 1
    fi
}

migrate_check() {
    # Live KV-state migration (docs/SHARDED_SERVING.md "Live
    # migration"): the MXKV blob round-trip + corruption rejection,
    # bitwise forced migration (greedy AND seeded-sampled — the rng
    # ships in the blob), defrag with bitwise continuation, the
    # chunked /v1/migrate_in receiver (idempotent replay, abort), the
    # rebalancer policy, the gateway HTTP handoff with the
    # migrate_interrupt chaos kind degrading to journal resume, and
    # the SimFleet drain-storm policy A/B.  Lockdep rides along in
    # raise mode (the transfer path crosses the scheduler loop, the
    # worker's buffer lock, and gateway handler threads) and leakcheck
    # in raise mode audits BOTH sides of every transfer including
    # aborts — a stranded page or half-assembled buffer fails the lane
    # at the first non-quiescent test.
    MXTPU_LOCKDEP=raise MXTPU_LEAKCHECK=raise \
        python -m pytest tests/test_migration.py -q -m "not slow"
    # every module the migration path touches must lint clean — NO
    # suppressions: export/import hold allocator state across the
    # scheduler turn and the receiver buffers live under a worker lock
    python -m mxnet_tpu.lint mxnet_tpu/generation.py \
        mxnet_tpu/serving.py mxnet_tpu/gateway.py mxnet_tpu/fleet.py \
        mxnet_tpu/fleet_worker.py mxnet_tpu/simfleet.py \
        mxnet_tpu/loadgen.py mxnet_tpu/chaos.py \
        mxnet_tpu/leakcheck.py
    if grep -n "mxlint: disable" mxnet_tpu/generation.py \
            mxnet_tpu/serving.py mxnet_tpu/gateway.py \
            mxnet_tpu/fleet.py mxnet_tpu/fleet_worker.py \
            mxnet_tpu/simfleet.py mxnet_tpu/loadgen.py \
            mxnet_tpu/chaos.py mxnet_tpu/leakcheck.py; then
        echo "migration-path modules must not carry mxlint suppressions" >&2
        return 1
    fi
}

sim_check() {
    # Trace-driven load replay + simulated-clock fleet
    # (docs/SIMULATION.md): trace-model determinism (Poisson/MMPP
    # arrivals, deadline classes, sessions, shared prefixes), the
    # replay typed-outcome contract against a real server, and the
    # simulator acceptance — seeded runs bit-identical, the REAL
    # FleetSupervisor + gateway routing policy at 200 replicas under a
    # combined storm (registry partition + worker kills) in seconds.
    python -m pytest tests/test_loadgen.py tests/test_simfleet.py \
        -q -m "not slow"
    # fleet-scale scenario smoke in a fresh process: 100 simulated
    # replicas, partition + kill mid-ramp, every request exactly one
    # typed outcome and a detectable shed knee — laptop-speed
    env JAX_PLATFORMS=cpu python - <<'EOF'
import time

from mxnet_tpu import loadgen
from mxnet_tpu.simfleet import SimFleet, partition_window

spec = loadgen.TraceSpec(seed=3, segments=[
    {"duration_s": 6.0, "rate_rps": 300.0},
    {"duration_s": 6.0, "rate_rps": 1300.0},
], deadline_classes=[{"name": "std", "deadline_ms": 3000.0,
                      "weight": 1.0}])
trace = loadgen.generate_trace(spec)
t0 = time.monotonic()
with SimFleet(trace, initial_replicas=100, max_replicas=120,
              slots=2, queue_cap=8, seed=5) as fl:
    res = fl.run(chaos_spec=partition_window(6, 4) + ",worker_kill@60")
wall = time.monotonic() - t0
assert wall < 60.0, "storm took %.1fs" % wall
assert sum(res["outcomes"].values()) == len(trace), res["outcomes"]
assert set(res["outcomes"]) <= set(loadgen.TYPED_OUTCOMES)
knee = loadgen.shed_knee(res["curve"])
assert knee is not None, "no shed knee in the goodput curve"
kinds = [i["kind"] for i in res["incidents"]]
assert "worker_kill" in kinds and "registry_partition" in kinds, kinds
print("sim storm smoke OK: %d reqs, %.1fs wall, knee %.0f rps"
      % (len(trace), wall, knee))
EOF
    # the simulator must lint clean — NO suppressions: it drives the
    # real control plane, so a CC001 slip here hides a production stall
    python -m mxnet_tpu.lint mxnet_tpu/loadgen.py mxnet_tpu/simfleet.py \
        mxnet_tpu/clock.py
    if grep -n "mxlint: disable" mxnet_tpu/loadgen.py \
            mxnet_tpu/simfleet.py mxnet_tpu/clock.py; then
        echo "loadgen.py/simfleet.py/clock.py must not carry mxlint" \
             "suppressions" >&2
        return 1
    fi
}

obs_check() {
    # Always-on telemetry plane (docs/OBSERVABILITY.md): metrics
    # registry, histogram quantiles, exporters, profiler ring buffer +
    # dispatch bridge, cost-analysis step accounting, trace IDs, and
    # the blackout-proof bench harness (forced leg timeout).
    python -m pytest tests/test_telemetry.py tests/test_profiler.py -q
    # registry smoke: counters/histograms round-trip through the
    # Prometheus dump in a fresh process
    env JAX_PLATFORMS=cpu python - <<'EOF'
from mxnet_tpu import telemetry
reg = telemetry.MetricsRegistry()
reg.counter("smoke.hits").inc(3)
h = reg.histogram("smoke.lat_ms")
for v in (1.0, 2.0, 8.0):
    h.observe(v)
text = reg.dump_prometheus()
assert "smoke_hits 3" in text, text
assert "smoke_lat_ms_count 3" in text, text
for line in text.strip().split("\n"):
    if not line.startswith("#"):
        float(line.rsplit(" ", 1)[1])
snap = reg.snapshot()
p50 = snap["histograms"]["smoke.lat_ms"]["p50"]
assert abs(p50 - 2.0) / 2.0 <= 0.25, snap   # growth-1 relative bound
print("obs registry smoke OK")
EOF
    # the telemetry module must lint clean — NO suppressions: every
    # layer reports through it, so a CC001 slip is a global stall
    python -m mxnet_tpu.lint mxnet_tpu/telemetry.py
    if grep -n "mxlint: disable" mxnet_tpu/telemetry.py; then
        echo "telemetry.py must not carry mxlint suppressions" >&2
        return 1
    fi
}

debug_check() {
    # Diagnosis plane (docs/OBSERVABILITY.md "Diagnosis plane"):
    # recompile flight recorder, tagged device-memory accounting,
    # postmortem debug bundles + the stdlib-only bundle inspector.
    python -m pytest tests/test_debug.py -q
    # end-to-end smoke in a fresh process: force a retrace, capture a
    # bundle, and round-trip it through tools/inspect_bundle.py
    smoke_dir=$(mktemp -d)
    env JAX_PLATFORMS=cpu MXTPU_DEBUG_BUNDLE_DIR="$smoke_dir" \
        python - <<'EOF'
import jax.numpy as jnp
from mxnet_tpu import debug, dispatch

tj = dispatch.TrackedJit(lambda x: x + 1, label="ci_smoke")
tj(jnp.zeros((2, 2)))
tj(jnp.zeros((4, 2)))
text = dispatch.explain_recompiles()
assert "(2, 2) -> (4, 2)" in text, text
path = debug.write_bundle("ci_smoke", force=True)
assert path, "bundle not written"
print("debug bundle smoke OK:", path)
EOF
    env JAX_PLATFORMS=cpu python tools/inspect_bundle.py "$smoke_dir" \
        | grep -q INSPECT_OK
    rm -rf "$smoke_dir"
    # the diagnosis plane runs on the runtime's worst day — it must
    # lint clean with NO suppressions, same bar as telemetry
    python -m mxnet_tpu.lint mxnet_tpu/debug.py mxnet_tpu/memory.py \
        mxnet_tpu/dispatch.py
    if grep -n "mxlint: disable" mxnet_tpu/debug.py \
            mxnet_tpu/memory.py mxnet_tpu/dispatch.py; then
        echo "diagnosis-plane modules must not carry mxlint" \
             "suppressions" >&2
        return 1
    fi
}

integration_examples() {
    python -m pytest tests/test_examples.py tests/test_tools.py -q
}

multichip_dryrun() {
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"
}

unittest_core_tpu() {
    # rerun the corpus on the real chip (reference parity:
    # tests/python/gpu/test_operator_gpu.py reruns the unittest corpus
    # with default ctx = gpu); needs TPU hardware attached
    MXTPU_TEST_ON_TPU=1 python -m pytest tests/test_operator.py \
        tests/test_operator_extra.py tests/test_ndarray.py \
        tests/test_autograd.py tests/test_module.py \
        tests/test_gluon.py -q
}

unittest_dtype_sweep() {
    # ctx x dtype cross-product of the op corpus (reference
    # test_operator_gpu.py check_consistency type_dict sweep): fp32
    # interpreted-vs-jit oracle + bf16 legs
    python -m pytest tests/test_dtype_sweep.py tests/test_large_tensor.py -q
}

unittest_dtype_sweep_tpu() {
    # same sweep on the real chip (run with hardware attached, like
    # unittest_core_tpu — NOT part of all())
    MXTPU_TEST_ON_TPU=1 python -m pytest tests/test_dtype_sweep.py -q
}

nightly_large_tensor() {
    # reference tests/nightly/test_large_array.py analogue:
    # MXNET_INT64_TENSOR_SIZE=1 subprocess crossing 2^31 elements
    MXTPU_TEST_NIGHTLY=1 python -m pytest tests/test_large_tensor.py -q
}

all() {
    build_native
    sanity_check
    unittest_core
    unittest_frontend
    unittest_parallel
    unittest_serving
    serving_check
    gen_check
    kernel_check
    fleet_check
    gateway_check
    failover_check
    migrate_check
    sim_check
    obs_check
    debug_check
    unittest_dtype_sweep
    integration_examples
    chaos_check
    lockdep_check
    racecheck_check
    tenant_check
    multichip_dryrun
}

"$@"
