"""Telemetry-plane tests (docs/OBSERVABILITY.md): typed metrics registry,
log-bucketed histogram math, exporters, the profiler ring buffer +
dispatch-counter bridge, cost-analysis step accounting, trace IDs, and
the blackout-proof bench harness (one leg timing out must not sink the
round)."""
import json
import math
import os
import subprocess
import sys
import threading
import time

import pytest

from mxnet_tpu import profiler, telemetry
from mxnet_tpu.telemetry import (Counter, Gauge, Histogram,
                                 MetricsRegistry)

from conftest import subprocess_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# histogram math
# ---------------------------------------------------------------------------
def test_histogram_bucket_boundaries():
    h = Histogram("t", base=1.0, growth=2.0, max_buckets=10)
    # bucket 0 absorbs <= base (zeros and negatives included)
    for v in (-1.0, 0.0, 0.5, 1.0):
        assert h.bucket_index(v) == 0, v
    # bucket i spans (base*g^(i-1), base*g^i]: exact powers land INSIDE
    # their bucket, one ulp above spills to the next
    assert h.bucket_index(1.5) == 1
    assert h.bucket_index(2.0) == 1
    assert h.bucket_index(2.0000001) == 2
    assert h.bucket_index(4.0) == 2
    assert h.bucket_index(8.0) == 3
    # beyond the range clamps into the last bucket, never lost
    assert h.bucket_index(1e12) == 9
    lo, hi = h.bucket_bounds(0)
    assert lo == 0.0 and hi == 1.0
    lo, hi = h.bucket_bounds(3)
    assert lo == 4.0 and hi == 8.0


def test_histogram_quantiles_known_data():
    h = Histogram("lat", base=1e-3, growth=1.25, max_buckets=120)
    for i in range(1, 1001):          # 1..1000 "ms"
        h.observe(float(i))
    s = h.snapshot()
    assert s["count"] == 1000
    assert s["min"] == 1.0 and s["max"] == 1000.0
    assert abs(s["sum"] - 500500.0) < 1e-6
    # geometric buckets + interpolation: relative error < growth-1
    assert abs(s["p50"] - 500.0) / 500.0 < 0.25
    assert abs(s["p99"] - 990.0) / 990.0 < 0.25
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    assert h.percentile(0) >= s["min"]
    assert h.percentile(100) == s["max"]


def test_histogram_empty_nan_and_reset():
    h = Histogram("x")
    assert h.percentile(50) is None
    assert h.snapshot()["count"] == 0
    h.observe(float("nan"))           # NaN: dropped, not bucketed
    assert h.count == 0
    h.observe(2.5)
    assert h.count == 1
    h.reset()
    assert h.snapshot() == {"count": 0, "sum": 0.0, "avg": None,
                            "min": None, "max": None, "p50": None,
                            "p95": None, "p99": None}
    with pytest.raises(ValueError):
        Histogram("bad", growth=1.0)
    with pytest.raises(ValueError):
        Histogram("bad", base=0.0)


# ---------------------------------------------------------------------------
# counters / gauges / registry
# ---------------------------------------------------------------------------
def test_counter_thread_hammer():
    c = Counter("hammer")
    n_threads, n_incs = 8, 10_000

    def spin():
        for _ in range(n_incs):
            c.inc()

    threads = [threading.Thread(target=spin) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_incs   # not one increment lost
    assert c.reset() == n_threads * n_incs
    assert c.value == 0
    assert c.inc(5) == 5                   # inc returns the post value


def test_histogram_thread_hammer():
    h = Histogram("hammer_ms")
    n_threads, n_obs = 8, 2_000

    def spin(k):
        for i in range(n_obs):
            h.observe(0.5 + (i + k) % 100)

    threads = [threading.Thread(target=spin, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == n_threads * n_obs


def test_registry_typed_accessors():
    reg = MetricsRegistry()
    c = reg.counter("a.count")
    assert reg.counter("a.count") is c     # same object on re-ask
    g = reg.gauge("a.gauge")
    g.set(3.5)
    assert g.add(0.5) == 4.0
    reg.histogram("a.lat_ms").observe(2.0)
    with pytest.raises(TypeError):         # one name, one type
        reg.gauge("a.count")
    with pytest.raises(TypeError):
        reg.counter("a.lat_ms")
    names = [n for n, _ in reg.find("a.")]
    assert names == ["a.count", "a.gauge", "a.lat_ms"]
    c.inc(7)
    snap = reg.snapshot()
    assert snap["counters"]["a.count"] == 7
    assert snap["gauges"]["a.gauge"] == 4.0
    assert snap["histograms"]["a.lat_ms"]["count"] == 1
    assert isinstance(snap["ts_unix"], float)
    reg.reset()
    snap = reg.snapshot()
    assert snap["counters"]["a.count"] == 0
    assert snap["histograms"]["a.lat_ms"]["count"] == 0


def test_prometheus_dump_parses():
    reg = MetricsRegistry()
    reg.counter("serving.requests_admitted").inc(3)
    reg.gauge("train.fused.mfu").set(0.47)
    h = reg.histogram("serving.latency_ms")
    for v in (1.0, 2.0, 5.0, 10.0):
        h.observe(v)
    text = reg.dump_prometheus()
    assert text.endswith("\n")
    seen = {}
    for line in text.strip().split("\n"):
        if line.startswith("#"):
            parts = line.split()
            assert parts[1] == "TYPE" and parts[3] in (
                "counter", "gauge", "summary"), line
            continue
        name, value = line.rsplit(" ", 1)
        float(value)                       # every sample parses
        seen[name] = value
    # dots sanitized to underscores, summary series present
    assert seen["serving_requests_admitted"] == "3"
    assert float(seen["train_fused_mfu"]) == 0.47
    assert seen["serving_latency_ms_count"] == "4"
    assert 'serving_latency_ms{quantile="0.5"}' in seen
    assert 'serving_latency_ms{quantile="0.99"}' in seen


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_jsonl_exporter_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("jobs.done").inc(11)
    reg.histogram("jobs.lat_ms").observe(4.2)
    path = str(tmp_path / "metrics.jsonl")
    exp = telemetry.JsonlExporter(path, interval_s=0.05, reg=reg).start()
    time.sleep(0.15)
    reg.counter("jobs.done").inc()
    exp.stop()                        # guarantees a final flushed line
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert len(lines) >= 1
    for snap in lines:
        assert set(snap) == {"ts_unix", "counters", "gauges",
                             "histograms"}
    assert lines[-1]["counters"]["jobs.done"] == 12
    assert lines[-1]["histograms"]["jobs.lat_ms"]["count"] == 1
    # timestamps are monotone non-decreasing across snapshots
    ts = [s["ts_unix"] for s in lines]
    assert ts == sorted(ts)


def test_http_endpoint(tmp_path):
    from urllib.request import urlopen

    reg = MetricsRegistry()
    reg.counter("http.hits").inc(2)
    port = telemetry.serve_http(port=0, reg=reg)
    try:
        raw = urlopen("http://127.0.0.1:%d/metrics" % port,
                      timeout=10).read().decode()
        assert "http_hits 2" in raw
        js = json.loads(urlopen(
            "http://127.0.0.1:%d/metrics.json" % port,
            timeout=10).read().decode())
        assert js["counters"]["http.hits"] == 2
    finally:
        telemetry.stop_http()


# ---------------------------------------------------------------------------
# profiler bridge: dispatch counters, ring buffer
# ---------------------------------------------------------------------------
def test_dispatch_bridge_and_reset():
    before = profiler.dispatch_value("jit_cache_hit")
    profiler.dispatch_count("jit_cache_hit", 3)
    assert profiler.dispatch_value("jit_cache_hit") == before + 3
    stats = profiler.dispatch_stats()
    assert stats["jit_cache_hit"] == before + 3
    # the bridged counters live in the shared registry under dispatch.
    assert telemetry.registry().counter(
        "dispatch.jit_cache_hit").value == before + 3
    stats = profiler.dispatch_stats(reset=True)   # returns pre-reset
    assert stats["jit_cache_hit"] == before + 3
    assert profiler.dispatch_value("jit_cache_hit") == 0
    # zero-filled schema: every known key present even when untouched
    assert "recompile" in profiler.dispatch_stats()


def test_profiler_ring_buffer_drops(tmp_path):
    drop_counter = telemetry.registry().counter("profiler.events_dropped")
    dropped0 = drop_counter.value
    profiler.set_config(filename=str(tmp_path / "ring.json"),
                        profile_all=True)
    profiler.start()
    try:
        profiler.set_max_events(100)
        t0 = profiler.now_us()
        for i in range(300):
            profiler.record_span("span%d" % i, "imperative", t0, 1.0)
        evts = profiler._events
        assert len(evts) <= 100
        # oldest evicted, newest kept
        names = {e.get("name") for e in evts}
        assert "span299" in names and "span0" not in names
        assert drop_counter.value - dropped0 >= 200
        with pytest.raises(ValueError):
            profiler.set_max_events(0)
    finally:
        profiler.stop()
        profiler.set_max_events(
            int(os.environ.get("MXNET_PROFILER_MAX_EVENTS", "1000000")))
        profiler.dump()               # drain the buffer for later tests


# ---------------------------------------------------------------------------
# step accounting
# ---------------------------------------------------------------------------
def test_step_accountant_gauges(monkeypatch):
    from mxnet_tpu import runtime

    def run(prefix):
        reg = MetricsRegistry()
        acc = telemetry.StepAccountant(prefix, reg=reg, alpha=1.0)
        acc.set_cost({"flops": 1.0e9, "bytes_accessed": 1.0e8})
        assert acc.on_step(32) is None    # first call only arms the clock
        time.sleep(0.02)
        sps = acc.on_step(32)
        assert sps and sps > 0
        return reg, sps, {n: m.value for n, m in reg.find(prefix + ".")}

    # the CPU is not in the peaks table: rates, but no utilization at all
    assert runtime.device_peaks() is None
    reg, sps, g = run("t.step")
    assert g["t.step.steps_per_sec"] == pytest.approx(sps)
    assert g["t.step.items_per_sec"] == pytest.approx(32 * sps)
    assert g["t.step.hbm_gbs"] == pytest.approx(1.0e8 * sps / 1e9)
    assert "t.step.mfu" not in g and "t.step.hbm_util" not in g
    # a device_kind the table lists: utilization against ITS peaks
    v5e = runtime.DEVICE_PEAKS["TPU v5 lite"]
    monkeypatch.setattr(runtime, "device_peaks", lambda device=None: v5e)
    _, sps, g = run("t.v5e")
    assert g["t.v5e.mfu"] == pytest.approx(1.0e9 * sps / v5e["bf16_flops"])
    assert g["t.v5e.hbm_util"] == pytest.approx(
        1.0e8 * sps / v5e["hbm_bytes_per_s"])
    # without a cost dict only the rate gauges publish
    acc2 = telemetry.StepAccountant("t.nocost", reg=reg)
    acc2.on_step()
    time.sleep(0.01)
    acc2.on_step()
    assert [n for n, _ in reg.find("t.nocost.")] == \
        ["t.nocost.steps_per_sec"]


def test_tracked_jit_cost_analysis():
    import jax.numpy as jnp

    from mxnet_tpu import dispatch

    def f(a, b):
        return jnp.dot(a, b)

    tj = dispatch.TrackedJit(f)
    a = jnp.ones((64, 64), jnp.float32)
    cost = tj.cost_analysis(a, a)
    assert cost is not None
    assert cost["flops"] > 0          # 2*64^3 matmul FLOPs
    assert cost["bytes_accessed"] > 0
    assert tj.cost_analysis(a, a) is cost   # cached, no re-lowering
    # the probe pre-warms the trace: the first real call must be a HIT
    hits0 = profiler.dispatch_value("jit_cache_hit")
    rec0 = profiler.dispatch_value("recompile")
    tj(a, a)
    assert profiler.dispatch_value("jit_cache_hit") == hits0 + 1
    assert profiler.dispatch_value("recompile") == rec0


# ---------------------------------------------------------------------------
# trace IDs
# ---------------------------------------------------------------------------
def test_trace_ids_roundtrip(tmp_path):
    ids = {telemetry.new_trace_id() for _ in range(100)}
    assert len(ids) == 100            # process-unique
    fname = str(tmp_path / "trace.json")
    profiler.set_config(filename=fname, profile_all=True)
    profiler.start()
    tid = telemetry.new_trace_id()
    telemetry.trace_begin("request", tid, args={"rows": 1})
    telemetry.trace_instant("batch_close", args={"trace_ids": [tid]})
    telemetry.trace_end("request", tid, args={"outcome": "ok"})
    profiler.stop()
    profiler.dump()
    evts = json.load(open(fname))["traceEvents"]
    spans = [e for e in evts if e.get("id") == tid]
    assert {e["ph"] for e in spans} == {"b", "e"}
    assert all(e["cat"] == "serving" and e["name"] == "request"
               for e in spans)
    inst = [e for e in evts if e.get("ph") == "i"
            and e.get("name") == "batch_close"]
    assert inst and inst[0]["args"]["trace_ids"] == [tid]


# ---------------------------------------------------------------------------
# bench harness: a timed-out leg must not sink the round
# ---------------------------------------------------------------------------
def test_bench_leg_timeout_isolated(tmp_path):
    """Force the serving leg over budget: the round must still print one
    parseable JSON line and carry records for the OTHER legs — including
    the transformer leg's cost-analysis FLOPs — and exit non-zero, because
    a leg that ran did not end ok.  On the CPU no MFU is published: its
    ``device_kind`` has no row in the peaks table."""
    partial = str(tmp_path / "partial.jsonl")
    env = subprocess_env(
        BENCH_LEGS="serving,transformer",
        BENCH_FORCE_TIMEOUT_LEG="serving",
        BENCH_PARTIAL_PATH=partial,
        BENCH_BUDGET_S="200",
        BENCH_QUICK="1",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--quick"],
        capture_output=True, text=True, timeout=280, env=env, cwd=REPO)
    assert proc.returncode == 1, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    extra = result["extra"]
    assert extra["serving_status"].startswith("timeout"), extra
    assert extra["transformer_status"] == "ok", extra
    # every record names its device
    assert extra["platform"] == "cpu" and extra["device_count"] >= 1
    assert extra["device_kind"]
    # XLA's cost analysis is in the record; a utilization is not
    assert extra["transformer_flops_per_step"] > 0
    assert extra["mfu_source"] == "xla_cost_analysis"
    assert extra["mfu"] is None
    assert extra["transformer_mfu_vs_v5e_peak"] is None
    assert extra["transformer_train_tokens_per_sec"] > 0
    # incremental flush: both legs on disk, timed-out one marked
    legs = {json.loads(l)["leg"]: json.loads(l)
            for l in open(partial) if l.strip()}
    assert legs["serving"]["status"].startswith("timeout")
    assert legs["transformer"]["status"] == "ok"
    assert legs["transformer"]["platform"] == "cpu"
    assert legs["transformer"]["record"]["transformer_flops_per_step"] > 0


def test_bench_refuses_non_tpu_without_quick(tmp_path):
    """The round's numbers are device metrics: on a non-TPU backend, with
    quick mode not asked for by name, bench.py is an error — it still
    prints its JSON line, naming the device, and measures nothing."""
    env = subprocess_env(BENCH_PARTIAL_PATH=str(tmp_path / "partial.jsonl"))
    env.pop("BENCH_QUICK", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 1, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "found platform 'cpu'" in result["error"], result
    assert result["extra"]["platform"] == "cpu"
    assert result["value"] == 0.0
    assert not [k for k in result["extra"] if k.endswith("_status")]


def test_bench_sigterm_still_emits_summary(tmp_path):
    """r05 regression: the driver's kill timer SIGTERMs a mid-flight
    round — bench must still print one parseable JSON summary line and
    exit promptly (non-zero) within the kill grace, instead of dying
    silently (r05: rc 124, zero output, `parsed: null`)."""
    import signal

    partial = str(tmp_path / "partial.jsonl")
    env = subprocess_env(BENCH_LEGS="train", BENCH_PARTIAL_PATH=partial,
                         BENCH_QUICK="1")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py"), "--quick"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO)
    try:
        time.sleep(6.0)                  # mid-import / mid-leg
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    # a round cut short is not a measurement: summary printed, exit non-zero
    assert proc.returncode == 1, (proc.returncode, err[-2000:])
    result = json.loads(out.strip().splitlines()[-1])
    assert result["extra"].get("budget_exceeded") == "SIGTERM from driver"


def test_bench_external_blackout_still_emits_summary(tmp_path):
    """Satellite hardening for the r05 blackout class: bench dies under
    an EXTERNAL ``timeout -k`` (exactly how the driver kills a round) —
    coreutils timeout reports 124, but the last stdout line must still
    parse as the JSON summary with the SIGTERM marker, so a blacked-out
    round is diagnosable instead of `parsed: null`."""
    import shutil

    if shutil.which("timeout") is None:
        pytest.skip("coreutils timeout not on PATH")
    partial = str(tmp_path / "partial.jsonl")
    env = subprocess_env(BENCH_LEGS="train", BENCH_PARTIAL_PATH=partial,
                         BENCH_QUICK="1")
    proc = subprocess.run(
        ["timeout", "-k", "30", "8",
         sys.executable, os.path.join(REPO, "bench.py"), "--quick"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    # 124 == timeout delivered SIGTERM; bench must have flushed first
    assert proc.returncode == 124, (proc.returncode, proc.stderr[-2000:])
    lines = proc.stdout.strip().splitlines()
    assert lines, "blackout: no stdout at all"
    result = json.loads(lines[-1])
    assert result["extra"].get("budget_exceeded") == "SIGTERM from driver"


def test_bench_quick_budgets_fit_strictly_below_outer_budget(tmp_path):
    """The quick-mode leg allowances for the legs that will RUN must sum
    STRICTLY below 0.8x the outer budget even after the 45s floors —
    otherwise a worst-case round overruns into the driver's kill."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    legs = [("a", None, 60.0), ("b", None, 45.0), ("c", None, 75.0),
            ("d", None, 45.0), ("e", None, 45.0), ("f", None, 45.0)]
    # plenty of budget: untouched
    out, scale = bench._quick_leg_budgets(legs, None, 1000.0)
    assert scale is None and out == legs
    # tight budget: every active leg fits, sum strictly below the cap
    out, scale = bench._quick_leg_budgets(legs, None, 240.0)
    assert scale is not None
    total = sum(need for _, _, need in out)
    assert total < 0.8 * 240.0
    # floors would sum to 6*45=270 > cap 192: the shave must have bitten
    assert all(need < 45.0 for _, _, need in out)
    # a BENCH_LEGS subset: skipped legs keep their budgets and the
    # selected pair needs no scaling under a 200s budget (115 < 160)
    out, scale = bench._quick_leg_budgets(legs, {"a", "b"}, 200.0)
    assert scale is None
    assert out == legs


def test_bench_regression_tripwire(tmp_path):
    """check_regressions flags >10% drops on higher-is-better metrics
    and >10% increases on latency metrics, and nothing else."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    base = {"value": 100.0,
            "extra": {"platform": "cpu",
                      "inference_img_per_sec": 50.0,
                      "serving_p99_ms": 10.0,
                      "transformer_train_tokens_per_sec": 1000.0,
                      "mfu": 0.40}}
    bpath = str(tmp_path / "base.json")
    json.dump(base, open(bpath, "w"))
    cur = {"value": 85.0,                       # -15%: flagged
           "extra": {"platform": "cpu",
                     "inference_img_per_sec": 48.0,   # -4%: fine
                     "serving_p99_ms": 13.0,          # +30%: flagged
                     "transformer_train_tokens_per_sec": 1500.0,
                     "mfu": 0.41}}
    out = bench.check_regressions(cur, baseline_path=bpath)
    assert out["status"] == "checked"
    flagged = {f["metric"] for f in out["flagged"]}
    assert flagged == {"value", "serving_p99_ms"}
    # platform mismatch: skipped, never cross-compares cpu vs tpu
    cur["extra"]["platform"] = "tpu"
    out = bench.check_regressions(cur, baseline_path=bpath)
    assert out["status"].startswith("skipped (platform mismatch")
    # identical round: checked, nothing flagged
    out = bench.check_regressions(base, baseline_path=bpath)
    assert out["status"] == "checked" and out["flagged"] == []
