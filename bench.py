"""Benchmark: ResNet-50 training + inference throughput on one TPU chip.

Reference headline numbers (BASELINE.md, `docs/faq/perf.md`):
  * training  b128 fp32 V100: 363.69 img/s (`perf.md:236`)
  * inference b128 fp16 V100: 2355.04 img/s (`perf.md:192`)

This runs the same workload through the Gluon user path — model-zoo
ResNet-50 cast to bfloat16 (the TPU-native training dtype, with fp32 master
weights via the optimizer's multi-precision states), SoftmaxCrossEntropyLoss,
sgd+momentum — with the whole train step compiled to ONE XLA module
(`gluon.contrib.FusedTrainStep`).

Blackout-proof harness (docs/OBSERVABILITY.md): the round is a sequence of
independently budgeted LEGS.  Each leg runs under its own SIGALRM budget
(BENCH_LEG_BUDGET_<NAME> overrides the default), so a leg that blows its
budget times out ALONE — every other leg still runs and the round still
emits its records (round 5 of this repo produced rc 124 / zero data when
one global watchdog fired; never again).  Each leg's record is flushed
incrementally to BENCH_PARTIAL_PATH (default bench_partial.jsonl, one
JSON line per leg) the moment the leg ends, and the final single-line
JSON still always prints.  All legs share one process — the one process
that holds the chip — so the persistent XLA compile cache
(``mxnet_tpu.runtime.init_compile_cache``) and every in-process jit cache
carry across legs.

The round measures the device, so a non-TPU backend is an error unless
quick mode is asked for by name (``--quick`` / ``BENCH_QUICK=1`` — small
model, few steps, a smoke of the harness whose numbers are no device
metrics).  Every record names the device it ran on (``platform``,
``device_kind``, ``device_count``); MFU is published only for a
``device_kind`` in ``mxnet_tpu.runtime.DEVICE_PEAKS`` and is null
elsewhere.

Env knobs: BENCH_BATCH (default 128), BENCH_STEPS (default 30),
BENCH_MODEL (default resnet50_v1), BENCH_DTYPE (default bfloat16),
BENCH_BUDGET_S (global wall-clock ceiling, default 480; quick mode
defaults to 390 so the whole round clears an external kill timer),
BENCH_QUICK / --quick (small model, few steps; required on a non-TPU
backend), BENCH_KERNELS (Pallas kernel-program leg, docs/KERNELS.md; on
by default),
BENCH_LEGS (comma list: run only these legs), BENCH_LOADREPLAY
(trace-driven overload replay leg, docs/SIMULATION.md; on by default),
BENCH_FORCE_TIMEOUT_LEG
(burn the named leg's budget so its watchdog fires — the harness's own
regression test; BENCH_FORCE_TIMEOUT_S tunes the burn window, default
1.5s), BENCH_PARTIAL_PATH, BENCH_BASELINE /
BENCH_REGRESSION_STRICT (regression tripwire vs the round recorded in
BENCH_BASELINE: >10% drop on a leg metric is flagged; strict mode exits
3).  Always prints ONE parseable JSON line — partial results carry
per-leg status markers instead of dying at rc 124 — and exits 0 only
when every leg that ran ended ``ok`` and ``main`` did not raise (1
otherwise).
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

TRAIN_BASELINE_IMG_S = 363.69   # V100 fp32 b128 training, perf.md:236
INFER_BASELINE_IMG_S = 2355.04  # V100 fp16 b128 inference, perf.md:192

# Built progressively by main(); the __main__ wrapper prints it no
# matter how the run ends, so the driver always gets a JSON line.
RESULT = {
    "metric": "resnet50_train_img_per_sec",
    "value": 0.0,
    "unit": "images/sec",
    "vs_baseline": 0.0,
    "extra": {},
}

_T0 = time.monotonic()

# platform / device_kind / device_count as JAX reports them, filled by
# main() and stamped on every record the round writes
_DEVICE = {}


class BudgetExceeded(Exception):
    """Raised by the SIGALRM watchdog and by in-loop budget checks."""


# SIGTERM (the driver's `timeout` sends it before SIGKILL) must shortcut
# straight to the summary line: r05 died at rc 124 with zero output
# because full-mode legs were still running when the term arrived.
_TERMINATED = False


def _term_handler(signum, frame):
    global _TERMINATED
    _TERMINATED = True
    raise BudgetExceeded("SIGTERM from driver")


def _budget_s():
    return float(os.environ.get("BENCH_BUDGET_S", "480"))


def _remaining():
    return _budget_s() - (time.monotonic() - _T0)


def _alarm_handler(signum, frame):
    raise BudgetExceeded("bench watchdog fired")


def _arm(seconds):
    """(Re)arm the SIGALRM watchdog for ``seconds`` (0 cancels).  Safe
    no-op off the main thread / on platforms without SIGALRM."""
    try:
        import signal

        if seconds:
            signal.signal(signal.SIGALRM, _alarm_handler)
            signal.alarm(max(1, int(math.ceil(seconds))))
        else:
            signal.alarm(0)
    except (ValueError, OSError, AttributeError):
        pass


# ---------------------------------------------------------------------------
# leg harness
# ---------------------------------------------------------------------------
def _partial_path():
    return os.environ.get("BENCH_PARTIAL_PATH", "bench_partial.jsonl")


def _reset_partial():
    try:
        with open(_partial_path(), "w"):
            pass
    except OSError:
        pass


def _flush_leg(name, status, record, elapsed):
    """Append this leg's record to the incremental JSONL file NOW — if a
    later leg (or the whole process) dies, everything measured so far is
    already on disk."""
    line = {"leg": name, "status": status, **_DEVICE,
            "elapsed_s": round(elapsed, 1), "record": record}
    try:
        with open(_partial_path(), "a") as f:
            f.write(json.dumps(line) + "\n")
            f.flush()
    except (OSError, TypeError, ValueError):
        pass


def _selected_legs():
    sel = os.environ.get("BENCH_LEGS", "").strip()
    if not sel:
        return None
    return {s.strip() for s in sel.split(",") if s.strip()}


def _quick_leg_budgets(legs, sel, budget_s):
    """Scale quick-mode leg budgets so the legs that will actually RUN
    collectively fit STRICTLY below 0.8x the outer budget — a
    worst-case round (every leg eats its allowance) must still end with
    legs marked, summary printed, rc 0, not an external kill.  Skipped
    legs (BENCH_LEGS subsets) keep their budgets and don't count toward
    the cap.  Floor at min(need, 45s): the compile-dominated CPU legs
    (sentinel ~37s, inference ~34s measured) must not be scaled below
    what a healthy run takes — but the floors may push the sum back
    over, so a final uniform shave re-asserts the strict bound.
    Returns (legs, scale-or-None)."""
    active = [leg for leg in legs if sel is None or leg[0] in sel]
    total_need = sum(need for _, _, need in active)
    cap = 0.8 * budget_s
    if total_need <= cap:
        return legs, None
    scale = cap / total_need
    scaled = {n: max(min(need, 45.0), need * scale)
              for n, _, need in active}
    floored = sum(scaled.values())
    if floored > cap:
        shave = cap / floored * 0.999
        scaled = {n: b * shave for n, b in scaled.items()}
    return [(n, f, scaled.get(n, need)) for n, f, need in legs], scale


def _leg_budget(name, default_need):
    try:
        return float(os.environ.get(
            "BENCH_LEG_BUDGET_" + name.upper(), default_need))
    except ValueError:
        return default_need


def _run_leg(extra, name, fn, need):
    """Run one leg under its own SIGALRM budget.  A timeout or error
    kills THIS leg only; its status lands in ``extra`` and the record
    (or lack of one) is flushed incrementally.  Returns the record dict
    on success, else None."""
    selected = _selected_legs()
    if selected is not None and name not in selected:
        extra[name + "_status"] = "skipped (BENCH_LEGS)"
        return None
    need = _leg_budget(name, need)
    remaining = _remaining()
    if remaining < min(need, 10.0):
        extra[name + "_status"] = "skipped (budget)"
        _flush_leg(name, "skipped (budget)", {}, 0.0)
        return None
    budget = min(need, remaining)
    forced = os.environ.get("BENCH_FORCE_TIMEOUT_LEG", "") == name
    if forced:
        try:
            burn = float(os.environ.get("BENCH_FORCE_TIMEOUT_S", "1.5"))
        except ValueError:
            burn = 1.5
        budget = min(budget, burn)
    t0 = time.monotonic()
    record, status = {}, "ok"
    _arm(budget)
    try:
        if forced:
            # burn this leg's budget so its watchdog fires: proves a
            # timed-out leg cannot take the round down with it
            while True:
                time.sleep(0.05)
        record = fn() or {}
    except BudgetExceeded:
        status = "timeout (leg budget %.0fs)" % budget
        if _TERMINATED:
            # the driver is tearing us down: flush this leg, then let the
            # exception reach __main__ so the summary prints within the
            # kill grace instead of starting another leg
            _flush_leg(name, "terminated", record,
                       time.monotonic() - t0)
            raise
    except Exception as e:  # one leg must never sink the round
        if _TERMINATED:
            # the handler's raise surfaced wrapped in another exception
            # (it can land inside arbitrary library code): still tear down
            _flush_leg(name, "terminated", record, time.monotonic() - t0)
            raise BudgetExceeded("SIGTERM from driver")
        status = "error: %s: %s" % (type(e).__name__, e)
    finally:
        # hand the watchdog back to the global ceiling between legs
        rem = _remaining()
        _arm(rem if rem > 0 else 1)
    elapsed = time.monotonic() - t0
    if status == "ok":
        extra.update(record)
    extra[name + "_status"] = status
    _flush_leg(name, status, record, elapsed)
    return record if status == "ok" else None


_EMITTED = False


def _emit_summary():
    """Print the single summary JSON line, exactly once, merging in any
    legs that only made it to the partial JSONL (a leg mid-flight when
    SIGTERM/SIGALRM hit has its record on disk but not in RESULT).
    Registered via atexit AND called from the __main__ finally, so every
    exit path short of SIGKILL produces a parseable line."""
    global _EMITTED
    if _EMITTED:
        return
    _EMITTED = True
    extra = RESULT.setdefault("extra", {})
    if _TERMINATED:
        extra.setdefault("budget_exceeded", "SIGTERM from driver")
    try:
        with open(_partial_path()) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                name = rec.get("leg")
                if not name or (name + "_status") in extra:
                    continue
                extra[name + "_status"] = "%s (from partial)" % \
                    rec.get("status", "?")
                if rec.get("status") == "ok":
                    for k, v in (rec.get("record") or {}).items():
                        extra.setdefault(k, v)
    except OSError:
        pass
    print(json.dumps(RESULT))
    sys.stdout.flush()


# ---------------------------------------------------------------------------
# regression tripwire
# ---------------------------------------------------------------------------
_HIGHER_BETTER = ("_img_per_sec", "_per_sec", "_tokens_per_sec", "mfu",
                  "_vs_bf16", "_vs_naive", "_vs_baseline",
                  "_vs_v100_fp16", "value")
_LOWER_BETTER = ("_ms", "_reprefill_ratio")


def _flat_metrics(result):
    out = {}
    v = result.get("value")
    if isinstance(v, (int, float)) and not isinstance(v, bool) and v > 0:
        out["value"] = float(v)
    for k, val in (result.get("extra") or {}).items():
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            out[k] = float(val)
    return out


def _direction(key):
    for s in _HIGHER_BETTER:
        if key.endswith(s):
            return 1
    for s in _LOWER_BETTER:
        if key.endswith(s):
            return -1
    return 0


def check_regressions(result, baseline_path=None, threshold=0.10):
    """Compare this round's leg metrics against a recorded round
    (``baseline_path`` or BENCH_BASELINE, same platform) and flag any
    metric that moved >``threshold`` in the bad direction — throughput/MFU
    drops, latency increases.  Returns {status, baseline, flagged:[...]};
    never raises."""
    try:
        path = baseline_path or os.environ.get("BENCH_BASELINE", "")
        if not path:
            return {"status": "skipped (no baseline)"}
        with open(path) as f:
            base = json.load(f)
        if not isinstance(base, dict):
            return {"status": "skipped (no baseline)"}
        bplat = (base.get("extra") or {}).get("platform")
        nplat = (result.get("extra") or {}).get("platform")
        if bplat != nplat:
            return {"status": "skipped (platform mismatch: baseline %s, "
                              "current %s)" % (bplat, nplat),
                    "baseline": os.path.basename(path)}
        old_m, new_m = _flat_metrics(base), _flat_metrics(result)
        flagged = []
        for key, old in sorted(old_m.items()):
            new = new_m.get(key)
            direction = _direction(key)
            if new is None or old <= 0 or direction == 0:
                continue
            drop = ((old - new) / old) * direction
            if drop > threshold:
                flagged.append({"metric": key,
                                "baseline": round(old, 4),
                                "current": round(new, 4),
                                "drop_pct": round(drop * 100.0, 1)})
        return {"status": "checked",
                "baseline": os.path.basename(path),
                "flagged": flagged}
    except Exception as e:
        return {"status": "error: %s: %s" % (type(e).__name__, e)}


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------
def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="mxnet_tpu training/inference benchmark")
    ap.add_argument("--quick", action="store_true",
                    help="small model, few steps, primary legs only")
    cli, _ = ap.parse_known_args(argv)

    import numpy as np
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, profiler, telemetry
    from mxnet_tpu.gluon.contrib import FusedTrainStep
    from mxnet_tpu.gluon.model_zoo import vision

    dev0 = jax.devices()[0]
    platform = dev0.platform
    _DEVICE.update(platform=platform, device_kind=dev0.device_kind,
                   device_count=len(jax.devices()))
    extra = RESULT["extra"]
    extra.update(_DEVICE)
    quick = cli.quick or os.environ.get("BENCH_QUICK", "") not in ("", "0")
    if platform != "tpu" and not quick:
        # the round's numbers are device metrics; a CPU timing must never
        # be written under their names
        raise RuntimeError(
            "bench.py measures the TPU and found platform %r (%s): run it "
            "on the chip, or pass --quick / BENCH_QUICK=1 for the harness "
            "smoke" % (platform, dev0.device_kind))
    if quick and "BENCH_BUDGET_S" not in os.environ:
        # keep the whole quick round comfortably under the driver's
        # external kill timer; the per-leg watchdogs re-read this
        os.environ["BENCH_BUDGET_S"] = "390"

    batch = int(os.environ.get("BENCH_BATCH", "8" if quick else "128"))
    steps = int(os.environ.get("BENCH_STEPS", "5" if quick else "30"))
    model_name = os.environ.get(
        "BENCH_MODEL", "resnet18_v1" if quick else "resnet50_v1")
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    # quick shrinks the spatial size too: XLA's CPU backend takes minutes
    # to compile/execute the 224px train graph, which is exactly the rc-124
    # failure mode this mode exists to avoid
    size = int(os.environ.get("BENCH_SIZE", "56" if quick else "224"))
    reps = 2 if quick else 3

    ctx = mx.current_context()
    extra["quick"] = quick
    extra["compile_cache_dir"] = mx.runtime.compile_cache_dir()
    RESULT["metric"] = "%s_train_img_per_sec_b%d_%s_%s" % (
        model_name.split("_")[0], batch, dtype, platform)
    _reset_partial()

    # shared training context, built lazily INSIDE the first leg that
    # needs it (so BENCH_LEGS=serving,transformer never compiles resnet,
    # and the build time is charged to a leg budget, not the round)
    tctx = {}

    def host_fetch(arr):
        # materialize on host: ends the timed region on the value itself
        arr.asnumpy()

    def ensure_train_ctx():
        if tctx:
            return tctx
        net = getattr(vision, model_name)(classes=1000)
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net.hybridize(static_alloc=True, static_shape=True)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        rng = np.random.RandomState(0)
        x32 = mx.nd.array(rng.rand(batch, 3, size, size).astype(np.float32),
                          ctx=ctx)
        y = mx.nd.array(rng.randint(0, 1000, (batch,)), ctx=ctx)
        # finish deferred init in fp32, then cast the net to the compute
        # dtype (BatchNorm keeps its statistics in fp32; the optimizer
        # holds fp32 master weights — the reference's mp_sgd flow)
        with mx.autograd.pause():
            net(x32)
        multi_precision = dtype != "float32"
        if multi_precision:
            net.cast(dtype)
        x = x32.astype(dtype) if multi_precision else x32
        trainer = gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9,
             "multi_precision": multi_precision})
        step = FusedTrainStep(net, loss_fn, trainer)
        for _ in range(2 if quick else 3):  # warmup: compile fwd+bwd+update
            loss = step(x, y)
        host_fetch(loss)
        tctx.update(net=net, loss_fn=loss_fn, trainer=trainer, step=step,
                    x=x, y=y)
        return tctx

    # ---- legs -----------------------------------------------------------
    def train_leg():
        c = ensure_train_ctx()
        step, x, y = c["step"], c["x"], c["y"]
        # best-of-N repetitions; every timed region ends with a HOST
        # VALUE FETCH.  The train loop is naturally serialized through
        # the donated parameter chain.
        train_img_s = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(x, y)
            host_fetch(loss)
            dt = time.perf_counter() - t0
            train_img_s = max(train_img_s, batch * steps / dt)
            # publish after every rep so a watchdog flush has the best
            # so far
            RESULT["value"] = round(train_img_s, 2)
            RESULT["vs_baseline"] = round(
                train_img_s / TRAIN_BASELINE_IMG_S, 4)
            extra["train_steps_per_sec"] = round(train_img_s / batch, 2)
        out = {"loss_final": float(np.asarray(
            loss.asnumpy(), dtype=np.float32).mean())}
        # live cost-analysis gauges the step accountant published during
        # the loop (docs/OBSERVABILITY.md): MFU + HBM utilization with
        # zero device syncs
        gauges = telemetry.registry().snapshot()["gauges"]
        for src, dst in (("train.fused.mfu", "train_mfu"),
                         ("train.fused.hbm_util", "train_hbm_util"),
                         ("train.fused.items_per_sec",
                          "train_live_img_per_sec")):
            if src in gauges:
                out[dst] = round(gauges[src], 4)
        return out

    def sentinel_leg():
        # same net/trainer with the guard armed: the fused finiteness
        # reduction + lax.cond containment must stay within the 3%
        # acceptance budget (docs/NUMERICAL_HEALTH.md).  Interleaved
        # base/guard window pairs; the overhead is the MEDIAN per-pair
        # ratio — host interference lands on one window of one pair and
        # would be read as sentinel cost (or savings) by a mean or an
        # extreme, while the median pair is clean on a mostly-idle
        # machine.
        c = ensure_train_ctx()
        step, x, y = c["step"], c["x"], c["y"]
        guard_step = FusedTrainStep(c["net"], c["loss_fn"], c["trainer"],
                                    numeric_guard="skip")
        for _ in range(2 if quick else 3):  # warmup: separate module
            gloss = guard_step(x, y)
        host_fetch(gloss)
        win = max(2, steps // 2)
        guard_img_s, ratios = 0.0, []
        for _ in range(3 if quick else 3 * reps):
            dts = {}
            for tag, s in (("base", step), ("guard", guard_step)):
                t0 = time.perf_counter()
                for _ in range(win):
                    gloss = s(x, y)
                host_fetch(gloss)
                dts[tag] = time.perf_counter() - t0
            guard_img_s = max(guard_img_s, batch * win / dts["guard"])
            ratios.append(dts["guard"] / dts["base"] - 1.0)
        ratios.sort()
        mid = len(ratios) // 2
        overhead = (ratios[mid] if len(ratios) % 2
                    else (ratios[mid - 1] + ratios[mid]) / 2.0)
        return {"sentinel_guard_img_per_sec": round(guard_img_s, 2),
                "sentinel_overhead_pct": round(overhead * 100.0, 2)}

    def inference_leg():
        # two disciplines (mxnet_tpu/benchmark.py): the compiled K-step
        # loop (one dispatch per draw — measures the device, the gate
        # metric) and the per-dispatch user path (host-dispatch
        # sensitive, published with its spread).
        from mxnet_tpu.benchmark import (compiled_throughput,
                                         percall_throughput)

        c = ensure_train_ctx()
        net, x = c["net"], c["x"]
        draws = 2 if quick else 5
        dev = compiled_throughput(net, x, steps=steps, draws=draws)
        percall = percall_throughput(net, x, steps=steps, draws=draws)
        tctx["infer_img_s"] = dev["median"]
        return {
            "inference_img_per_sec": round(dev["median"], 2),
            "inference_img_per_sec_spread": [round(dev["min"], 2),
                                             round(dev["max"], 2)],
            "inference_percall_img_per_sec": round(percall["median"], 2),
            "inference_percall_spread": [round(percall["min"], 2),
                                         round(percall["max"], 2)],
            "inference_vs_v100_fp16": round(
                dev["median"] / INFER_BASELINE_IMG_S, 4),
        }

    def serving_leg():
        return serving_bench(quick=quick)

    def latency_b1_leg():
        # batch-1 serving latency, 100 chained steps/dispatch so the
        # per-dispatch host cost amortizes away (docs/PERF_LATENCY.md)
        from mxnet_tpu.benchmark import compiled_throughput

        c = ensure_train_ctx()
        r1 = compiled_throughput(c["net"], c["x"][0:1], steps=100, draws=3)
        b1key = "latency_b1_%s" % model_name
        return {b1key + "_img_per_sec": round(r1["median"], 1),
                b1key + "_ms": round(1000.0 / r1["median"], 3)}

    def int8_leg():
        return int8_bench(batch=batch, steps=steps,
                          bf16_img_s=tctx.get("infer_img_s"))

    def transformer_leg():
        return transformer_bench(quick=quick)

    def decode_leg():
        return decode_bench(quick=quick)

    def kernels_leg():
        return kernels_bench(quick=quick)

    def racecheck_leg():
        return racecheck_bench(quick=quick)

    def longctx_leg():
        return long_context_bench()

    def fleet_leg():
        return fleet_bench(quick=quick)

    def gateway_leg():
        return gateway_bench(quick=quick)

    def loadreplay_leg():
        return loadreplay_bench(quick=quick)

    def migration_leg():
        return migration_bench(quick=quick)

    def tenant_leg():
        return tenant_bench(quick=quick)

    # quick (CPU-oracle) budgets are compile-dominated — the sentinel leg
    # builds a second XLA module — so some exceed their full-mode numbers
    legs = [
        ("train", train_leg, 150 if quick else 240),
        ("sentinel", sentinel_leg, 60 if quick else 45),
        ("inference", inference_leg, 45 if quick else 60),
        ("serving", serving_leg, 25 if quick else 45),
    ]
    if not quick:
        legs.append(("latency_b1", latency_b1_leg, 40))
        if os.environ.get("BENCH_INT8", "1") != "0":
            legs.append(("int8", int8_leg, 120))
    # the transformer leg runs in quick mode too: its record carries the
    # cost-analysis-derived "mfu", the number the observability layer is
    # accepted on
    if os.environ.get("BENCH_TRANSFORMER", "1") != "0":
        legs.append(("transformer", transformer_leg, 90 if quick else 120))
    # the decode leg runs in quick mode too: continuous-batching
    # generative inference is accepted on decode_tokens_per_sec / ttft_ms
    if os.environ.get("BENCH_DECODE", "1") != "0":
        legs.append(("decode", decode_leg, 60 if quick else 90))
    # the fleet leg runs in quick mode too: the sharded-serving +
    # autoscaling layer is accepted on fleet_scaleup_ms (lower-better
    # under the >10% regression tripwire) and the 2x-capacity shed rate
    if os.environ.get("BENCH_FLEET", "1") != "0":
        legs.append(("fleet", fleet_leg, 60 if quick else 120))
    # the gateway leg runs in quick mode too: the cross-process fleet is
    # accepted on gateway_route_p99_ms (lower-better) and the
    # burst-with-one-worker-killed gateway_kill_goodput_vs_baseline
    if os.environ.get("BENCH_GATEWAY", "1") != "0":
        legs.append(("gateway", gateway_leg, 90 if quick else 150))
    # the kernels leg runs in quick mode too: the Pallas kernel program
    # (flash fwd+bwd through the registry, int8 fused dequant) is
    # accepted on kernels_flash_vs_naive / kernels_int8_matmul_vs_bf16
    if os.environ.get("BENCH_KERNELS", "1") != "0":
        legs.append(("kernels", kernels_leg, 45 if quick else 90))
    # the racecheck leg runs in quick mode too: the armed lockset race
    # sanitizer is accepted on racecheck_checked_ops_per_sec (tripwired)
    # with racecheck_overhead_pct alongside; the off half of each pair
    # doubles as the off-mode zero-overhead baseline
    if os.environ.get("BENCH_RACECHECK", "1") != "0":
        legs.append(("racecheck", racecheck_leg, 20 if quick else 30))
    # the loadreplay leg runs in quick mode too: trace-driven overload
    # replay (docs/SIMULATION.md) is accepted on goodput at 2x measured
    # capacity and TTFT p99, both under the regression tripwire
    if os.environ.get("BENCH_LOADREPLAY", "1") != "0":
        legs.append(("loadreplay", loadreplay_leg, 45 if quick else 75))
    # the migration leg runs in quick mode too: live KV handoff
    # (docs/SHARDED_SERVING.md "Live migration") is accepted on
    # migrate_vs_reprefill_ratio at the longest context (lower-better
    # under the >10% tripwire; < 1.0 means the handoff beats re-prefill)
    if os.environ.get("BENCH_MIGRATION", "1") != "0":
        legs.append(("migration", migration_leg, 60 if quick else 150))
    # the tenant leg runs in quick mode too: the multi-tenant serving
    # plane is accepted on the deterministic SimFleet scale-up-lag A/B
    # (tenant_scaleup_lag_{reactive,predictive}_ms, lower-better under
    # the tripwire) with the noisy-neighbor isolation ratio alongside
    if os.environ.get("BENCH_TENANT", "1") != "0":
        legs.append(("tenant", tenant_leg, 75 if quick else 120))
    if not quick and os.environ.get("BENCH_LONGCTX", "1") != "0":
        legs.append(("longctx", longctx_leg, 150))
    if os.environ.get("BENCH_SERVING", "1") == "0":
        legs = [leg for leg in legs if leg[0] != "serving"]

    if quick:
        legs, scale = _quick_leg_budgets(legs, _selected_legs(),
                                         _budget_s())
        if scale is not None:
            extra["quick_budget_scale"] = round(scale, 3)

    for name, fn, need in legs:
        # the handler's raise can be swallowed by a broad except deep in a
        # leg (e.g. the cost-analysis probe) — the flag is authoritative
        if _TERMINATED:
            raise BudgetExceeded("SIGTERM from driver")
        _run_leg(extra, name, fn, need)

    extra["dispatch"] = profiler.dispatch_stats()
    extra["regression_check"] = check_regressions(RESULT)
    if extra["regression_check"].get("flagged"):
        # tripwire fired: capture a postmortem bundle so the regression
        # arrives with dispatch stats + recompile explanations attached
        from mxnet_tpu import debug as _debug

        _debug.write_bundle("bench_regression",
                            extra=extra["regression_check"])
    extra["elapsed_s"] = round(time.monotonic() - _T0, 1)


def serving_bench(quick=False):
    """Serving-front leg (docs/SERVING.md): batch-1 request latency
    p50/p99 through :class:`mxnet_tpu.serving.ModelServer` vs the bare
    ``Predictor.forward`` loop on the SAME model in the SAME process
    (drift-immune overhead reading), plus the shed rate under a
    synthetic burst at 4x the admission cap.  The served p50/p99 are
    read from the telemetry layer's ``serving.latency_ms`` histogram —
    the same numbers a production scrape of the registry reports."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.predict import Predictor

    n_req = 100 if quick else 400
    max_queue = 32
    rng = np.random.RandomState(0)

    # small MLP: the front's overhead is model-independent bookkeeping,
    # so a short forward makes the p99 delta legible instead of noise
    d_in, d_h = 64, 256
    data = mx.sym.var("data")
    w1, b1 = mx.sym.var("fc1_weight"), mx.sym.var("fc1_bias")
    w2, b2 = mx.sym.var("fc2_weight"), mx.sym.var("fc2_bias")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, w1, b1, num_hidden=d_h, name="fc1"),
        act_type="relu")
    sym = mx.sym.FullyConnected(h, w2, b2, num_hidden=8, name="fc2")
    params = {
        "arg:fc1_weight": mx.nd.array(
            (rng.rand(d_h, d_in) * 0.1).astype(np.float32)),
        "arg:fc1_bias": mx.nd.zeros((d_h,)),
        "arg:fc2_weight": mx.nd.array(
            (rng.rand(8, d_h) * 0.1).astype(np.float32)),
        "arg:fc2_bias": mx.nd.zeros((8,)),
    }
    xs = [rng.rand(1, d_in).astype(np.float32) for _ in range(16)]

    def pctl(lat_s, q):
        return round(float(np.percentile(np.asarray(lat_s), q)) * 1e3, 3)

    # -- bare Predictor loop (the overhead baseline) --
    bare = Predictor(sym, dict(params), input_shapes={"data": (1, d_in)})
    for x in xs:
        bare.forward(data=mx.nd.array(x))[0].asnumpy()  # warm
    bare_lat = []
    for i in range(n_req):
        t0 = time.perf_counter()
        bare.forward(data=mx.nd.array(xs[i % len(xs)]))[0].asnumpy()
        bare_lat.append(time.perf_counter() - t0)

    out = {"serving_bare_p50_ms": pctl(bare_lat, 50),
           "serving_bare_p99_ms": pctl(bare_lat, 99)}

    # -- steady state through the serving front (no faults) --
    # max_wait 0: a closed-loop sequential client would otherwise spend
    # every request waiting out the batching timer, which would read as
    # front overhead when it is really idle batching slack
    hist = telemetry.registry().histogram("serving.latency_ms")
    srv = serving.ModelServer(sym, dict(params),
                              input_shapes={"data": (1, d_in)},
                              max_queue=max_queue, max_batch=8,
                              max_wait_ms=0, deadline_ms=30_000)
    try:
        for x in xs:
            srv.submit({"data": x})  # settle the EWMA + caches
        hist.reset()                 # measurement window starts here
        for i in range(n_req):
            srv.submit({"data": xs[i % len(xs)]})
        hs = hist.snapshot()
        out["serving_p50_ms"] = round(hs["p50"], 3)
        out["serving_p99_ms"] = round(hs["p99"], 3)
        out["serving_latency_count"] = hs["count"]
        out["serving_overhead_p99_pct"] = round(
            (out["serving_p99_ms"] / max(out["serving_bare_p99_ms"], 1e-9)
             - 1.0) * 100.0, 1)

        # -- burst at 4x the admission cap: shedding, not collapse --
        hist.reset()
        futs, shed = [], 0
        offered = 4 * max_queue
        for i in range(offered):
            try:
                futs.append(srv.submit_async(
                    {"data": xs[i % len(xs)]}, deadline_ms=30_000))
            except serving.Overloaded:
                shed += 1
        for f in futs:
            f.result(timeout=60)
        out["serving_burst_offered"] = offered
        out["serving_shed_rate"] = round(shed / offered, 4)
        out["serving_burst_p99_ms"] = round(
            hist.snapshot()["p99"] or 0.0, 3)
        snap = srv.snapshot()
        out["serving_queue_depth_peak"] = snap["queue_depth_peak"]
        out["serving_batches"] = {
            k: snap[k] for k in ("batches_full", "batches_timer",
                                 "batches_deadline")}
    finally:
        srv.drain(timeout=30)
    return out


def racecheck_bench(quick=False):
    """Racecheck leg (docs/STATIC_ANALYSIS.md "Data-race detection"):
    cost of the armed lockset detector over a representative tracked
    critical section — a tracked counter bumped under a held lock, the
    shape every serving-stack stats field has — vs the same class with
    the sanitizer uninstalled (no hooks exist, so the baseline IS the
    off-mode zero-overhead path the tests pin).  The on-window seeds the
    field into shared-modified first so every access pays the full
    lockset-intersection step, not the cheap exclusive-phase one.
    Interleaved off/on window pairs; the overhead is the MEDIAN per-pair
    ratio, same discipline as the sentinel leg.  The tripwire gates on
    ``racecheck_checked_ops_per_sec``."""
    import threading as _threading

    from mxnet_tpu import racecheck

    if racecheck.installed():
        # the round itself is running under MXTPU_RACECHECK: there is no
        # off window to pair against, so the leg carries no number
        return {"racecheck_skipped": "sanitizer already armed"}

    @racecheck.track("ctr")
    class _Counter:
        def __init__(self):
            self.ctr = 0

    ops = 20_000 if quick else 100_000
    reps = 3 if quick else 5

    def window(box, lk):
        t0 = time.perf_counter()
        for _ in range(ops):
            with lk:
                box.ctr += 1
        return time.perf_counter() - t0

    checked_ops_s, ratios = 0.0, []
    for _ in range(reps):
        box, lk = _Counter(), _threading.Lock()
        dt_off = window(box, lk)
        racecheck.install("record")
        try:
            box = _Counter()
            lk = racecheck._LockToken(_threading._allocate_lock(),
                                      "bench.py:0", "Lock")

            def seed():
                with lk:
                    box.ctr = 0    # second thread: leave exclusive phase

            t = _threading.Thread(target=seed)
            t.start()
            t.join()
            dt_on = window(box, lk)
            races = racecheck.snapshot()["counters"]["races"]
        finally:
            racecheck.uninstall()
            racecheck.reset()
        if races:                  # the bench loop is lock-disciplined
            return {"racecheck_error": "false race in bench loop"}
        checked_ops_s = max(checked_ops_s, ops / dt_on)
        ratios.append(dt_on / dt_off - 1.0)
    ratios.sort()
    mid = len(ratios) // 2
    overhead = (ratios[mid] if len(ratios) % 2
                else (ratios[mid - 1] + ratios[mid]) / 2.0)
    return {"racecheck_checked_ops_per_sec": round(checked_ops_s, 1),
            "racecheck_overhead_pct": round(overhead * 100.0, 2)}


def decode_bench(quick=False):
    """Generative-decode leg (docs/GENERATIVE.md): continuous-batching
    token generation through :class:`mxnet_tpu.generation.GenerationServer`
    — paged KV cache, prefill/decode split, iteration-level scheduler.
    Reports steady-state ``decode_tokens_per_sec`` (median of the
    per-iteration histogram over the measurement window),
    ``ttft_ms`` (submit -> first streamed token, prefill-dominated), and
    ``kv_page_util`` (allocator peak over the run).  The server warms
    every (prefill, slot) bucket before the window, so the window itself
    must be compile-free — the recompile counter delta is reported so the
    tripwire catches a bucketing regression as well as a throughput one."""
    import jax
    import numpy as np

    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.generation import GenerationConfig, GenerationServer
    from mxnet_tpu.models import TransformerConfig, TransformerLM

    vocab = 1024
    cfg = TransformerConfig(vocab_size=vocab, d_model=128, n_heads=4,
                            n_layers=2, d_ff=256, max_len=128,
                            dtype="float32", remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))

    max_new = 16 if quick else 32
    gcfg = GenerationConfig(page_size=16, max_pages=128,
                            max_slots=4 if quick else 8,
                            max_new_tokens=max_new)
    n_req = 8 if quick else 32
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, size=4 + (i * 5) % 21).astype(np.int32)
               for i in range(n_req + 2)]

    reg = telemetry.registry()
    ttft = reg.histogram("gen.ttft_ms")
    tps = reg.histogram("gen.decode_tokens_per_sec")
    srv = GenerationServer(model, params, gcfg)
    out = {}
    try:
        for p in prompts[:2]:
            srv.submit(p, max_new_tokens=4)      # settle the host paths
        base_recompiles = profiler.dispatch_value("recompile")
        base_tokens = profiler.dispatch_value("gen_tokens")
        ttft.reset()
        tps.reset()                              # window starts here
        t0 = time.perf_counter()
        futs = [srv.submit_async(p, max_new_tokens=max_new)
                for p in prompts[2:]]
        for f in futs:
            f.result(timeout=300)
        wall = time.perf_counter() - t0
        toks = profiler.dispatch_value("gen_tokens") - base_tokens
        hs_tps, hs_ttft = tps.snapshot(), ttft.snapshot()
        out["decode_tokens_per_sec"] = round(hs_tps["p50"] or 0.0, 1)
        out["decode_wall_tokens_per_sec"] = round(toks / wall, 1)
        out["decode_tokens_total"] = int(toks)
        out["ttft_ms"] = round(hs_ttft["p50"] or 0.0, 3)
        out["ttft_p99_ms"] = round(hs_ttft["p99"] or 0.0, 3)
        out["kv_page_util"] = round(srv.engine.allocator.peak_util, 4)
        out["decode_recompiles_in_window"] = int(
            profiler.dispatch_value("recompile") - base_recompiles)
    finally:
        srv.drain(timeout=30)
    return out


def migration_bench(quick=False):
    """Live KV-migration leg (docs/SHARDED_SERVING.md "Live
    migration"): at each context length, a greedy stream is parked
    mid-decode and restored on a sibling server two ways — the live
    handoff (export -> import -> attach, no prefill) and the journal
    re-prefill (``resume_from``) — measuring park-to-next-token latency
    for both.  Reports per-context ``migrate_ctx<N>_ms`` /
    ``reprefill_ctx<N>_ms`` and the headline
    ``migrate_vs_reprefill_ratio`` at the LONGEST context (lower-better
    under the >10% tripwire): re-prefill grows with the attention
    window while the handoff moves pages, so the ratio must stay below
    1 at long contexts — migration earning its keep."""
    import threading

    import jax
    import numpy as np

    from mxnet_tpu.generation import GenerationConfig, GenerationServer
    from mxnet_tpu.models import TransformerConfig, TransformerLM
    from mxnet_tpu.serving import StreamMigrated

    vocab = 1024
    max_len = 576 if quick else 1024
    ctxs = (96, 512) if quick else (96, 256, 512, 896)
    cfg = TransformerConfig(vocab_size=vocab, d_model=128, n_heads=4,
                            n_layers=2, d_ff=256, max_len=max_len,
                            dtype="float32", remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    gcfg = GenerationConfig(page_size=16, max_pages=48 if quick else 64,
                            max_slots=4, max_new_tokens=16)
    a = GenerationServer(model, params, gcfg)
    b = GenerationServer(model, params, gcfg)
    rng = np.random.RandomState(0)

    def parked(prompt):
        fut = a.submit_async(prompt, temperature=0.0)
        while len(fut.stream_tokens) < 4:
            time.sleep(0.001)
        [handle] = a.park_streams(1)
        try:
            fut.result(timeout=30)
        except StreamMigrated:
            pass
        return handle, fut.stream_tokens

    def t_first(submit):
        # park-to-next-token: the client-visible gap each path leaves
        evt = threading.Event()
        t0 = time.perf_counter()
        fut = submit(lambda t: evt.set())
        if not evt.wait(120):
            raise TimeoutError("no continuation token within 120s")
        dt = (time.perf_counter() - t0) * 1e3
        fut.result(timeout=120)
        return dt

    out = {}
    reps = 3
    try:
        for ctx in ctxs:
            prompt = rng.randint(0, vocab, size=ctx).astype(np.int32)
            # warm every path at this context: both prefill buckets,
            # the export/import/attach machinery, the resume re-prefill
            a.submit(prompt, max_new_tokens=4)
            b.submit(prompt, max_new_tokens=4)
            handle, deliv = parked(prompt)
            h2 = b.import_stream(a.export_stream(handle))
            b.submit_async(prompt, resume_from=deliv, migrate_handle=h2,
                           temperature=0.0).result(timeout=120)
            b.submit_async(prompt, resume_from=deliv,
                           temperature=0.0).result(timeout=120)
            mig, rep = [], []
            for _ in range(reps):
                handle, deliv = parked(prompt)

                def migrate(cb, handle=handle, deliv=deliv):
                    h2 = b.import_stream(a.export_stream(handle))
                    return b.submit_async(
                        prompt, resume_from=deliv, migrate_handle=h2,
                        temperature=0.0, on_token=cb)

                mig.append(t_first(migrate))
                rep.append(t_first(
                    lambda cb, deliv=deliv: b.submit_async(
                        prompt, resume_from=deliv, temperature=0.0,
                        on_token=cb)))
            out["migrate_ctx%d_ms" % ctx] = round(min(mig), 3)
            out["reprefill_ctx%d_ms" % ctx] = round(min(rep), 3)
        last = ctxs[-1]
        out["migrate_vs_reprefill_ratio"] = round(
            out["migrate_ctx%d_ms" % last]
            / out["reprefill_ctx%d_ms" % last], 4)
        out["migration_ctx_longest"] = last
    finally:
        a.drain(timeout=30)
        b.drain(timeout=30)
    return out


def loadreplay_bench(quick=False):
    """Trace-driven load-replay leg (docs/SIMULATION.md): a seeded
    :mod:`mxnet_tpu.loadgen` trace replayed at ~2x measured capacity
    against a real in-process :class:`GenerationServer` — the
    steady-overload profile the bounded admission queue must shed, not
    absorb.  Accepted on ``loadreplay_goodput_per_sec`` (sustained
    completions under overload, higher-better) and
    ``loadreplay_ttft_p99_ms`` (lower-better), both under the >10%
    regression tripwire; ``loadreplay_shed_rate`` documents how much of
    the offered load was typed ``Overloaded`` rather than absorbed."""
    import jax

    from mxnet_tpu import loadgen
    from mxnet_tpu.generation import GenerationConfig, GenerationServer
    from mxnet_tpu.models import TransformerConfig, TransformerLM

    vocab = 1024
    cfg = TransformerConfig(vocab_size=vocab, d_model=128, n_heads=4,
                            n_layers=2, d_ff=256, max_len=128,
                            dtype="float32", remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_new = 8 if quick else 16
    gcfg = GenerationConfig(page_size=16, max_pages=128,
                            max_slots=4 if quick else 8,
                            max_new_tokens=max_new)
    srv = GenerationServer(model, params, gcfg, max_queue=8)
    out = {}
    try:
        # calibrate: an uncontended asap burst measures capacity (the
        # warmup doubles as compile settling for every bucket touched)
        cal_spec = loadgen.TraceSpec(
            seed=11, segments=[{"duration_s": 1.0,
                                "rate_rps": 8.0 if quick else 16.0}],
            prompt_len_mean=6.0, prompt_len_max=24,
            output_len_mean=float(max_new), output_len_max=max_new,
            deadline_classes=[{"name": "cal", "deadline_ms": 60000.0,
                               "weight": 1.0}])
        target = loadgen.generation_target(srv, vocab=vocab)
        cal = loadgen.replay(loadgen.generate_trace(cal_spec), target,
                             speed=float("inf"), name="calibrate")
        n_ok = cal.outcome_counts().get("ok", 0)
        if not n_ok:
            out["loadreplay_status_detail"] = "calibration produced " \
                "no completions: %s" % cal.outcome_counts()
            return out
        capacity_rps = max(0.5, n_ok / max(cal.wall_s, 1e-6))
        out["loadreplay_capacity_rps"] = round(capacity_rps, 2)

        # the measured leg: 2x capacity offered for a few wall seconds
        dur = 4.0 if quick else 8.0
        spec = loadgen.TraceSpec(
            seed=12,
            segments=[{"duration_s": dur,
                       "rate_rps": 2.0 * capacity_rps}],
            prompt_len_mean=6.0, prompt_len_max=24,
            output_len_mean=float(max_new), output_len_max=max_new,
            deadline_classes=[{"name": "std", "deadline_ms": 8000.0,
                               "weight": 1.0}])
        report = loadgen.replay(loadgen.generate_trace(spec), target,
                                speed=1.0, name="loadreplay")
        out.update(report.summary())
        out["loadreplay_knee_rps"] = loadgen.shed_knee(report.curve())
    finally:
        srv.drain(timeout=30)
    return out


def tenant_bench(quick=False):
    """Multi-tenant serving leg (docs/SHARDED_SERVING.md "Multi-tenant
    serving").  Two halves:

    * isolation — a three-tenant weighted trace replayed against a real
      in-process :class:`GenerationServer` twice on the same seed: once
      clean, once with a mid-burst ``tenant_flood`` storm from the
      tightly quota'd ``bulk`` tenant.  ``tenant_isolation_ratio`` is
      the victim (gold/free) TTFT p99 under flood over clean — 1.0 is
      perfect isolation — and ``tenant_flood_shed_rate`` how much of
      the flooder's offered load was typed ``QuotaExceeded``.  Both are
      wall-clock noisy on a shared box, so neither carries a tripwire
      suffix; the strict deterministic <10% proof is the SimFleet test
      in tests/test_tenancy.py.
    * scale-up lag A/B — the same seeded burst trace through SimFleet
      reactive then predictive.  ``tenant_scaleup_lag_reactive_ms`` /
      ``tenant_scaleup_lag_predictive_ms`` (mean ms from first raw
      breach tick to the scale-up fire; 0 = capacity ordered before the
      breach) are fully deterministic, so both sit under the >10%
      lower-better regression tripwire.
    """
    import jax

    from mxnet_tpu import loadgen, serving, simfleet, tenancy
    from mxnet_tpu.generation import GenerationConfig, GenerationServer
    from mxnet_tpu.models import TransformerConfig, TransformerLM

    out = {}
    tenants = [{"name": "gold", "weight": 4},
               {"name": "free", "weight": 2},
               {"name": "bulk", "weight": 1}]

    # -- isolation: real server, quota-contained flood ----------------
    vocab = 1024
    cfg = TransformerConfig(vocab_size=vocab, d_model=128, n_heads=4,
                            n_layers=2, d_ff=256, max_len=128,
                            dtype="float32", remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_new = 8 if quick else 16
    gcfg = GenerationConfig(page_size=16, max_pages=128,
                            max_slots=4 if quick else 8,
                            max_new_tokens=max_new)
    srv = GenerationServer(model, params, gcfg, max_queue=16)
    try:
        spec = loadgen.TraceSpec(
            seed=31,
            segments=[{"duration_s": 4.0 if quick else 6.0,
                       "rate_rps": 8.0 if quick else 12.0}],
            prompt_len_mean=6.0, prompt_len_max=24,
            output_len_mean=float(max_new), output_len_max=max_new,
            tenants=tenants)
        trace = loadgen.generate_trace(spec)
        target = loadgen.generation_target(srv, vocab=vocab)
        # warm every prefill bucket before anything is timed (no
        # governor yet: nothing sheds during warmup)
        loadgen.replay(trace, target, speed=float("inf"), name="warm")

        def run(chaos_spec):
            tenancy.reset_governor(tenancy.TenantGovernor(
                quotas={"bulk": tenancy.TenantSpec("bulk", rate=2.0,
                                                   burst=2.0)}))
            serving.brownout().reset()
            try:
                if chaos_spec:
                    from mxnet_tpu import chaos
                    with chaos.inject(chaos_spec):
                        return loadgen.replay(trace, target, speed=2.0,
                                              name="tenant")
                return loadgen.replay(trace, target, speed=2.0,
                                      name="tenant")
            finally:
                tenancy.reset_governor()
                serving.brownout().reset()

        def victim_p99(report):
            ttfts = [r["ttft_ms"] for r in report.records
                     if r["tenant"] in ("gold", "free")
                     and r["outcome"] == "ok"
                     and r["ttft_ms"] is not None]
            return loadgen._pctl(ttfts, 99) if ttfts else None

        base = run(None)
        bulk_idx = [i for i, r in enumerate(trace)
                    if r["tenant"] == "bulk"]
        steps = bulk_idx[len(bulk_idx) // 2:len(bulk_idx) // 2 + 3]
        flood = run(",".join("tenant_flood@%d" % s for s in steps))

        p99_base, p99_flood = victim_p99(base), victim_p99(flood)
        if p99_base and p99_flood:
            out["tenant_isolation_ratio"] = round(p99_flood / p99_base,
                                                  4)
        else:
            out["tenant_status_detail"] = ("victims produced no ok "
                                           "TTFTs: base=%s flood=%s"
                                           % (base.outcome_counts(),
                                              flood.outcome_counts()))
        bulk = flood.tenant_summary().get("bulk", {})
        out["tenant_flood_shed_rate"] = round(
            bulk.get("shed_quota", 0) / max(1, bulk.get("requests", 1)),
            4)
    finally:
        srv.drain(timeout=30)

    # -- scale-up lag: reactive vs predictive on one seeded trace -----
    burst = loadgen.generate_trace(loadgen.TraceSpec(
        seed=33, segments=[{"duration_s": 3.0, "rate_rps": 2.0},
                           {"duration_s": 6.0, "rate_rps": 60.0}]))

    def lags(predict):
        tenancy.reset_governor(tenancy.TenantGovernor(quotas={}))
        serving.brownout().reset()
        try:
            with simfleet.SimFleet(burst, initial_replicas=2,
                                   max_replicas=12, seed=5,
                                   predict=predict,
                                   predict_horizon_s=4.0,
                                   predict_depth_up=6) as fleet:
                res = fleet.run()
        finally:
            tenancy.reset_governor()
            serving.brownout().reset()
        return res["supervisor"]["scaleup_lags_ms"]

    r_lags, p_lags = lags(False), lags(True)
    if r_lags:
        out["tenant_scaleup_lag_reactive_ms"] = round(
            sum(r_lags) / len(r_lags), 1)
    if p_lags:
        out["tenant_scaleup_lag_predictive_ms"] = round(
            sum(p_lags) / len(p_lags), 1)
    out["tenant_scaleups_reactive"] = len(r_lags)
    out["tenant_scaleups_predictive"] = len(p_lags)
    return out


def kernels_bench(quick=False):
    """Pallas kernel-program leg (docs/KERNELS.md): measures the two
    tentpole kernels through the SAME entry points the model paths call,
    so the number tracks whatever implementation ``kernel_impl`` gives
    the backend (Pallas on TPU, lax fallbacks elsewhere — the quick/CPU
    reading gates plumbing regressions, the TPU reading gates the
    kernels).  Both are wrapped as ``kernel_unit`` TrackedJits, so the
    flight recorder and MFU attribution see them as ``kernel.*`` units.

    * flash attention forward+backward (``jax.value_and_grad`` through
      the custom VJP) in tokens/sec, against a naive materialized-scores
      attention with the same loss — ``kernels_flash_vs_naive``;
    * int8 matmul with fused per-channel dequant vs a bf16 ``jnp.dot``
      of the same shape, interleaved draws — ``kernels_int8_matmul_vs_bf16``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.pallas import (flash_attention, int8_matmul,
                                      kernel_impl, kernel_unit)

    on_tpu = jax.default_backend() == "tpu"
    B, H, D = 1, 4, 64
    T = 512 if quick else 2048
    steps = 3 if quick else 10
    reps = 2 if quick else 3
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, T, H, D), dt)
    k = jax.random.normal(kk, (B, T, H, D), dt)
    v = jax.random.normal(kv, (B, T, H, D), dt)

    attn_impl = kernel_impl("flash_attention")      # no mesh in this leg

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return (o.astype(jnp.float32) ** 2).sum()

    def naive_loss(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        return (o ** 2).sum()

    flash_step = kernel_unit("bench_flash_fwd_bwd",
                             jax.value_and_grad(flash_loss, (0, 1, 2)))
    naive_step = jax.jit(jax.value_and_grad(naive_loss, (0, 1, 2)))

    def tput(fn):
        jax.block_until_ready(fn(q, k, v))      # compile outside timing
        best = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            best = max(best, B * T * steps / (time.perf_counter() - t0))
        return best

    flash_tps, naive_tps = tput(flash_step), tput(naive_step)
    out = {
        "kernels_flash_impl": attn_impl,
        "kernels_flash_fwd_bwd_tokens_per_sec": round(flash_tps, 1),
        "kernels_naive_fwd_bwd_tokens_per_sec": round(naive_tps, 1),
        "kernels_flash_vs_naive": round(flash_tps / naive_tps, 4),
    }

    # -- int8 fused dequant vs bf16 dot, interleaved (drift-immune) --
    M = N = K = 512 if quick else 2048
    rng = np.random.RandomState(0)
    a8 = jnp.asarray(rng.randint(-127, 128, (M, K)), jnp.int8)
    w8 = jnp.asarray(rng.randint(-127, 128, (N, K)), jnp.int8)
    sa = jnp.float32(0.05)
    sw = jnp.asarray(rng.rand(N).astype(np.float32) * 0.1 + 0.01)
    int8_impl = kernel_impl("int8_matmul")
    int8_step = kernel_unit("bench_int8_matmul", int8_matmul)
    bdt = jnp.bfloat16 if on_tpu else jnp.float32
    a16 = (a8.astype(jnp.float32) * sa).astype(bdt)
    w16 = (w8.astype(jnp.float32) * sw[:, None]).astype(bdt)
    bf16_step = jax.jit(lambda a, b: jnp.dot(
        a, b.T, preferred_element_type=jnp.float32))

    jax.block_until_ready(int8_step(a8, w8, sa, sw))
    jax.block_until_ready(bf16_step(a16, w16))
    best_i = best_b = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            o = int8_step(a8, w8, sa, sw)
        jax.block_until_ready(o)
        best_i = max(best_i, steps / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        for _ in range(steps):
            o = bf16_step(a16, w16)
        jax.block_until_ready(o)
        best_b = max(best_b, steps / (time.perf_counter() - t0))
    gflop = 2.0 * M * N * K / 1e9
    out.update({
        "kernels_int8_impl": int8_impl,
        "kernels_int8_matmul_gflops_per_sec": round(best_i * gflop, 1),
        "kernels_bf16_matmul_gflops_per_sec": round(best_b * gflop, 1),
        "kernels_int8_matmul_vs_bf16": round(best_i / best_b, 4),
    })
    return out


def fleet_bench(quick=False):
    """Fleet-layer leg (docs/SHARDED_SERVING.md): a pjit-sharded
    ModelServer (tp=2 mesh slices) under a :class:`FleetSupervisor`.
    Reports ``fleet_scaleup_ms`` — wall time from burst onset to the
    autoscaled second replica entering rotation (the elasticity number
    the fleet layer is accepted on) — and the steady-state shed rate at
    2x admission capacity AFTER the scale-up, which the extra replica
    should hold well below the single-replica burst's."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.fleet import FleetSupervisor

    rng = np.random.RandomState(0)
    d_in = 64
    data = mx.sym.var("data")
    w1, b1 = mx.sym.var("fc1_weight"), mx.sym.var("fc1_bias")
    sym = mx.sym.FullyConnected(data, w1, b1, num_hidden=8, name="fc1")
    params = {
        "arg:fc1_weight": mx.nd.array(
            (rng.rand(8, d_in) * 0.1).astype(np.float32)),
        "arg:fc1_bias": mx.nd.zeros((8,)),
    }
    rules = [("fc1_weight", ("tp", None))]
    max_queue = 16
    xs = [rng.rand(1, d_in).astype(np.float32) for _ in range(16)]

    out = {}
    srv = serving.ModelServer(sym, dict(params),
                              input_shapes={"data": (1, d_in)},
                              mesh_axes={"tp": 2}, rules=rules,
                              max_queue=max_queue, max_batch=8,
                              max_wait_ms=0, deadline_ms=30_000)
    sup = FleetSupervisor(srv, service="bench", heartbeat_s=0.05,
                          interval_s=0.05, min_replicas=1,
                          max_replicas=2, shed_up=0.02,
                          idle_down_s=60, cooldown_s=0.2,
                          breach_ticks=2)
    try:
        for x in xs:
            srv.submit({"data": x})              # settle caches
        out["fleet_replica_devices"] = \
            srv.snapshot()["replicas"][0]["devices"]

        # -- burst -> scale-up latency --
        futs = []
        t0 = time.perf_counter()
        deadline = t0 + (60 if quick else 120)
        while time.perf_counter() < deadline and \
                srv.num_active_replicas() < 2:
            for i in range(2 * max_queue):
                try:
                    futs.append(srv.submit_async(
                        {"data": xs[i % len(xs)]}, deadline_ms=30_000))
                except serving.Overloaded:
                    pass
        out["fleet_scaleup_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        for f in futs:
            try:
                f.result(timeout=60)
            except serving.ServingError:
                pass

        # -- steady-state shed rate at 2x capacity, scaled fleet --
        n_waves = 10 if quick else 40
        offered = shed = 0
        futs = []
        for _ in range(n_waves):
            for i in range(2 * max_queue):
                offered += 1
                try:
                    futs.append(srv.submit_async(
                        {"data": xs[i % len(xs)]}, deadline_ms=30_000))
                except serving.Overloaded:
                    shed += 1
            time.sleep(0.01)
        for f in futs:
            try:
                f.result(timeout=60)
            except serving.ServingError:
                pass
        out["fleet_shed_rate_2x"] = round(shed / max(offered, 1), 4)
        out["fleet_replicas_final"] = srv.num_active_replicas()
        out["fleet_scale_ups"] = sup.scale_ups
    finally:
        sup.stop()
        sup.registry.close()
        srv.drain(timeout=30)
    return out


def gateway_bench(quick=False):
    """Cross-process fleet leg (docs/SHARDED_SERVING.md "Deployment"):
    2 spawned fleet workers behind the HTTP gateway.  Reports the
    routing overhead ``gateway_route_p99_ms`` — the ``gateway.route_ms``
    histogram p99 (admission -> request handed to a worker: pick +
    idempotency stamp + connect; lower-better under the tripwire), with
    the end-to-end ``gateway_p99_ms`` vs ``gateway_direct_p99_ms``
    (direct ``ModelServer.submit``) pair alongside — and
    ``gateway_kill_goodput_vs_baseline``: ok-fraction of a concurrent
    burst with one worker SIGKILLed mid-burst over the ok-fraction of
    the same burst undisturbed (the mid-stream failover number)."""
    import http.client
    import threading

    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.fleet import ServiceRegistry, WorkerSupervisor
    from mxnet_tpu.fleet_worker import demo_model
    from mxnet_tpu.gateway import Gateway

    def pctl(lat_s, q):
        return round(float(np.percentile(np.asarray(lat_s), q)) * 1e3, 3)

    def post(addr, obj, timeout=60):
        host, _, port = addr.rpartition(":")
        conn = http.client.HTTPConnection(host, int(port),
                                          timeout=timeout)
        try:
            conn.request("POST", "/v1/predict",
                         body=json.dumps(obj).encode(),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            return resp.status
        finally:
            conn.close()

    n_req = 50 if quick else 200
    burst = 24 if quick else 64
    x = {"inputs": {"data": [[1.0, 2.0, 3.0, 4.0]]}}
    out = {}

    # -- direct ModelServer baseline (same model the workers build) --
    direct = demo_model()
    try:
        arr = np.asarray(x["inputs"]["data"], np.float32)
        for _ in range(8):
            direct.submit({"data": arr})             # warm
        lat = []
        for _ in range(n_req):
            t0 = time.perf_counter()
            direct.submit({"data": arr})
            lat.append(time.perf_counter() - t0)
        direct_p99 = pctl(lat, 99)
    finally:
        direct.drain(timeout=30)

    # -- 2 spawned workers behind the gateway --
    # This process holds the chip, and a chip belongs to one process: the
    # children serve a four-float demo model on the CPU platform, and the
    # record says so.  The leg times the gateway's routing and failover,
    # which are host work.
    here = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": here + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}
    out["gateway_worker_platform"] = "cpu"
    reg = ServiceRegistry(service="bench-gw", ttl_s=1.0)
    sup = WorkerSupervisor(
        {rid: [sys.executable, "-m", "mxnet_tpu.fleet_worker",
               "--registry", reg.addr, "--service", "bench-gw",
               "--rid", rid, "--heartbeat-s", "0.1"]
         for rid in ("w0", "w1")},
        registry=reg, max_restarts=3, backoff=0.05, poll_s=0.05,
        env=env)
    gw = Gateway(registry=reg, refresh_s=0.1, suspect_s=0.5, retries=2)
    try:
        sup.wait_registered(2, timeout=180)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and (
                gw._view is None or len(gw._view.replicas) < 2):
            time.sleep(0.05)
        for _ in range(8):
            post(gw.addr, x)                         # warm both paths
        from mxnet_tpu import telemetry

        route_ms = telemetry.registry().histogram("gateway.route_ms")
        base_count = route_ms.snapshot()["count"]
        lat = []
        for _ in range(n_req):
            t0 = time.perf_counter()
            post(gw.addr, x)
            lat.append(time.perf_counter() - t0)
        out["gateway_p99_ms"] = pctl(lat, 99)
        out["gateway_direct_p99_ms"] = direct_p99
        hs = route_ms.snapshot()
        # both end-to-end p99s are dominated by the worker's batching
        # timer; the route histogram isolates the gateway's own overhead
        if hs["count"] > base_count and hs["p99"] is not None:
            out["gateway_route_p99_ms"] = round(hs["p99"], 3)

        def run_burst(kill_at=None):
            ok = [0]
            lock = threading.Lock()

            def one():
                try:
                    if post(gw.addr, x, timeout=90) == 200:
                        with lock:
                            ok[0] += 1
                except OSError:
                    pass
                except Exception:
                    pass
            ts = [threading.Thread(target=one) for _ in range(burst)]
            for i, t in enumerate(ts):
                t.start()
                if kill_at is not None and i == kill_at:
                    sup.kill_worker()
            for t in ts:
                t.join(timeout=120)
            return ok[0]

        ok_base = run_burst()
        ok_kill = run_burst(kill_at=burst // 4)
        out["gateway_burst_ok_baseline"] = ok_base
        out["gateway_burst_ok_killed"] = ok_kill
        out["gateway_retries"] = gw.retried
        out["gateway_worker_restarts"] = sup.restarts
    finally:
        gw.stop()
        sup.stop(timeout=20.0)
        reg.close()

    # -- durable generation streams: the kill-goodput number ------------
    # ``gateway_kill_goodput_vs_baseline`` is measured on *generation
    # streams*, where the failover win actually lives: a stream whose
    # worker is SIGKILLed mid-decode and resumes on the sibling counts
    # as goodput ("ok" terminal), a ``ReplicaLost`` terminal as loss.
    # (The old metric measured idempotent /v1/predict retries, which
    # masked mid-decode stream deaths entirely.)
    out.update(_gateway_gen_kill_goodput(quick=quick, env=env))
    return out


def _gateway_gen_kill_goodput(quick, env):
    """Streamed-generation kill burst behind the gateway: 2 generation
    workers, SIGKILL one after >= 1 token has streamed, count terminal
    outcomes (docs/SHARDED_SERVING.md "Failure matrix")."""
    import http.client
    import threading

    from mxnet_tpu.fleet import ServiceRegistry, WorkerSupervisor
    from mxnet_tpu.gateway import Gateway

    n_streams = 4 if quick else 8
    max_new = 8 if quick else 12
    out = {}

    reg = ServiceRegistry(service="bench-gw-gen", ttl_s=2.0)
    sup = WorkerSupervisor(
        {rid: [sys.executable, "-m", "mxnet_tpu.fleet_worker",
               "--registry", reg.addr, "--service", "bench-gw-gen",
               "--rid", rid, "--heartbeat-s", "0.1",
               "--builder", "mxnet_tpu.fleet_worker:demo_generation"]
         for rid in ("g0", "g1")},
        registry=reg, service="bench-gw-gen", max_restarts=3,
        backoff=0.05, poll_s=0.05, env=env)
    gw = Gateway(registry=reg, service="bench-gw-gen", refresh_s=0.1,
                 suspect_s=0.5, retries=2)

    def stream(i, outcomes, lock):
        host, _, port = gw.addr.rpartition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=120)
        try:
            conn.request(
                "POST", "/v1/generate",
                body=json.dumps({"prompt": [1 + i, 2, 3],
                                 "max_new_tokens": max_new,
                                 "deadline_ms": 60000}).encode(),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            outcome = "UNTYPED:HTTP%d" % resp.status
            if resp.status == 200:
                outcome = "UNTYPED:TruncatedStream"
                while True:
                    raw = resp.readline()
                    if not raw:
                        break
                    line = json.loads(raw)
                    if "done" in line:
                        outcome = "ok"
                        break
                    if "error" in line:
                        outcome = line["error"]
                        break
        except OSError as e:
            outcome = "UNTYPED:%s" % type(e).__name__
        finally:
            conn.close()
        with lock:
            outcomes.append(outcome)

    def run_burst(kill=False):
        outcomes, lock = [], threading.Lock()
        ts = [threading.Thread(target=stream, args=(i, outcomes, lock))
              for i in range(n_streams)]
        base_tokens = gw.tokens_streamed
        for t in ts:
            t.start()
        if kill:
            # mid-decode by construction: wait for >= 1 streamed token
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline \
                    and gw.tokens_streamed <= base_tokens:
                time.sleep(0.005)
            sup.kill_worker()
        for t in ts:
            t.join(timeout=180)
        return outcomes

    try:
        sup.wait_registered(2, timeout=180)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and (
                gw._view is None or len(gw._view.replicas) < 2):
            time.sleep(0.05)
        base = run_burst()
        killed = run_burst(kill=True)
        ok_base = sum(1 for o in base if o == "ok")
        ok_kill = sum(1 for o in killed if o == "ok")
        out["gateway_gen_ok_baseline"] = ok_base
        out["gateway_gen_ok_killed"] = ok_kill
        out["gateway_gen_replica_lost"] = sum(
            1 for o in killed if o == "ReplicaLost")
        out["gateway_streams_resumed"] = gw.streams_resumed
        out["gateway_kill_goodput_vs_baseline"] = round(
            ok_kill / max(ok_base, 1), 4)
    finally:
        gw.stop()
        sup.stop(timeout=20.0)
        reg.close()
    return out


def int8_bench(batch=128, steps=30, bf16_img_s=None):
    """INT8 resnet50 inference leg (VERDICT r3 next #8): post-training
    symmetric quantization (naive calib), run through the quantized
    symbol graph — int8 x int8 -> int32 MXU matmuls/convs
    (``ops/quantization.py``, preferred_element_type) — measured with
    the same compiled-loop discipline as the bf16 number."""
    import os as _os

    from mxnet_tpu.benchmark import compiled_throughput

    model_name = _os.environ.get("BENCH_INT8_MODEL", "resnet50_v1")
    size = int(_os.environ.get("BENCH_INT8_SIZE", "224"))
    n_calib = int(_os.environ.get("BENCH_INT8_CALIB", "32"))
    # fold conv+BN and fuse int8 chains (requantize + quantized relu /
    # pool) — the best int8 configuration measured in docs/PERF_INT8.md;
    # BENCH_INT8_FUSE=0 measures the reference-shaped per-layer graph
    fuse = _os.environ.get("BENCH_INT8_FUSE", "1") != "0"

    qnet, x32 = _build_int8_net(model_name, batch=batch, size=size,
                                n_calib=n_calib, fuse=fuse)
    r = compiled_throughput(qnet, x32, steps=steps, draws=5)
    out = {
        "int8_img_per_sec": round(r["median"], 2),
        "int8_img_per_sec_spread": [round(r["min"], 2),
                                    round(r["max"], 2)],
    }
    if bf16_img_s:
        out["int8_vs_bf16"] = round(r["median"] / bf16_img_s, 4)
    # VGG16: the weight-streaming-bound model where int8's halved bytes
    # pay off hardest (docs/PERF_INT8.md r5) — interleaved bf16/int8
    # draws in THIS process so the ratio is immune to session drift
    if _os.environ.get("BENCH_INT8_VGG", "1") != "0":
        out.update(_int8_vs_bf16_pair("vgg16", batch=batch,
                                      steps=20, reps=3, fuse=fuse))
    return out


def _build_int8_net(model_name, batch=128, size=224, n_calib=16,
                    fuse=True):
    """fp32 zoo model -> calibrated int8 SymbolBlock (+ its input).
    Shared by the int8 leg and the interleaved A/B pair."""
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.contrib.quantization import quantize_model
    from mxnet_tpu.gluon import SymbolBlock
    from mxnet_tpu.gluon.model_zoo import vision

    rng = np.random.RandomState(0)
    net = getattr(vision, model_name)(classes=1000)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x32 = mx.nd.array(rng.rand(batch, 3, size, size).astype(np.float32))
    with mx.autograd.pause():
        net(x32[0:1])  # deferred init only; skip the full-batch compile
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "m")
        net.export(prefix, 0)
        sym, args, auxs = mx.model.load_checkpoint(prefix, 0)
        calib = mx.io.NDArrayIter(
            rng.rand(n_calib, 3, size, size).astype(np.float32),
            np.zeros((n_calib,)), max(1, n_calib // 2))
        qsym, qargs, qauxs = quantize_model(
            sym, args, auxs, calib_mode="naive", calib_data=calib,
            num_calib_examples=n_calib, fold_bn=fuse, fuse_int8=fuse)
        mx.model.save_checkpoint(os.path.join(d, "q"), 0, qsym, qargs,
                                 qauxs)
        qnet = SymbolBlock.imports(os.path.join(d, "q-symbol.json"),
                                   ["data"],
                                   os.path.join(d, "q-0000.params"))
    return qnet, x32


def _int8_vs_bf16_pair(model_name, batch=128, size=224, steps=20,
                       reps=3, n_calib=16, fuse=True):
    """Interleaved same-process bf16 vs int8 measurement of one model:
    each loop compiles ONCE, timed draws alternate (drift-immune)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.benchmark import interleaved_throughput
    from mxnet_tpu.gluon.model_zoo import vision

    rng = np.random.RandomState(0)
    net16 = getattr(vision, model_name)(classes=1000)
    net16.initialize(mx.init.Xavier())
    net16.cast("bfloat16")
    net16.hybridize()
    x16 = mx.nd.array(rng.rand(batch, 3, size, size)
                      .astype(np.float32)).astype("bfloat16")
    with mx.autograd.pause():
        net16(x16[0:1])
    qnet, x32 = _build_int8_net(model_name, batch=batch, size=size,
                                n_calib=n_calib, fuse=fuse)
    mb, mi = interleaved_throughput([(net16, x16), (qnet, x32)],
                                    steps=steps, reps=reps)
    key = "int8_%s" % model_name
    return {key + "_img_per_sec": round(mi, 2),
            key + "_bf16_img_per_sec": round(mb, 2),
            key + "_vs_bf16": round(mi / mb, 4)}


def long_context_bench(seq=8192, steps=5):
    """Long-context metric: full training steps at 8k/16k/32k sequences
    on one chip (flash attention keeps memory O(seq); the reference's
    long-sequence story tops out at BucketingModule — this is net-new
    capability, SURVEY §5).  Multi-chip sequence scaling (ring
    attention over an "sp" mesh axis) is exercised by dryrun_multichip.

    MFU accounting (VERDICT r4 #7, same discipline as the transformer
    number): model FLOPs per token = 6*N (matmuls, fwd+bwd) plus the
    attention score/value FLOPs 6*L*T*d (12*L*T*d for full attention,
    halved because the kernel is causal), over the device's bf16 peak
    from ``runtime.DEVICE_PEAKS`` (null for a device not listed there).
    Remat recompute is NOT credited — MFU counts the math the
    model requires, so the remat config pays its recompute as lost
    utilization, which is the honest reading.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models import TransformerLM, TransformerConfig
    from mxnet_tpu.models.transformer import make_train_step
    from mxnet_tpu.runtime import device_peaks

    peaks = device_peaks()
    seqs = [int(s) for s in os.environ.get(
        "BENCH_LONGCTX_SEQS", "8192,16384,32768").split(",")]
    out = {}
    scaling = {}
    for T in seqs:
        cfg = TransformerConfig(vocab_size=32000, d_model=1024,
                                n_heads=16, n_layers=4, d_ff=4096,
                                max_len=T, dtype="bfloat16", remat=True)
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        velocity = jax.tree_util.tree_map(jnp.zeros_like, params)
        step = jax.jit(make_train_step(model))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, T + 1), 0,
                                  cfg.vocab_size)
        x, y = toks[:, :-1], toks[:, 1:]
        params, velocity, loss = step(params, velocity, x, y)
        float(loss)
        n_steps = steps if T <= seq else max(2, steps // 2)
        best = 0.0
        for _ in range(2):
            t0 = _time.perf_counter()
            for _ in range(n_steps):
                params, velocity, loss = step(params, velocity, x, y)
            float(np.asarray(loss))  # host fetch: real barrier
            best = max(best, T * n_steps / (_time.perf_counter() - t0))
        n_params = sum(int(np.prod(v.shape))
                       for v in jax.tree_util.tree_leaves(params))
        flops_per_tok = 6 * n_params + 6 * cfg.n_layers * T * cfg.d_model
        mfu = (round(best * flops_per_tok / peaks["bf16_flops"], 4)
               if peaks else None)
        scaling[str(T)] = {"tokens_per_sec": round(best, 1), "mfu": mfu}
        # headline keys track the canonical seq, or the first measured
        # one if the env override dropped it (never silently absent)
        if T == seq or (seq not in seqs and T == seqs[0]):
            out["longctx_seq%d_tokens_per_sec" % T] = round(best, 1)
            out["longctx_mfu"] = mfu
        del params, velocity, step, model
    out["longctx_scaling"] = scaling
    return out


def transformer_bench(batch=8, seq=1024, steps=10, quick=False):
    """Secondary metric: flagship TransformerLM training throughput.

    The matmul-dominated flagship shows the MXU utilization the
    framework reaches when the workload maps cleanly onto the systolic
    array (GPT-style LM, bf16, single chip); reported as tokens/sec +
    two MFU readings: ``mfu`` from XLA's own cost analysis of the
    compiled step (``lower().cost_analysis()`` — counts the FLOPs the
    executable actually schedules) and the analytic 6*N*tokens estimate
    (``transformer_mfu_vs_v5e_peak``, kept for trajectory continuity
    with earlier rounds).  ``quick`` shrinks the model/seq so the leg
    fits a CPU-oracle budget while still exercising the cost path.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models import TransformerLM, TransformerConfig
    from mxnet_tpu.models.transformer import make_train_step
    from mxnet_tpu.runtime import device_peaks

    if quick:
        batch, seq, steps = 2, min(seq, 128), 3
        cfg = TransformerConfig(vocab_size=2048, d_model=256, n_heads=4,
                                n_layers=2, d_ff=1024, max_len=seq,
                                dtype="float32", remat=False)
    else:
        # wide-and-shallow at batch 8 keeps all activations resident (no
        # remat recompute) and the d=2048 matmuls fill the MXU: measured
        # ~47% single-chip MFU vs ~19% for the d=1024/8-layer remat config
        cfg = TransformerConfig(vocab_size=32000, d_model=2048, n_heads=16,
                                n_layers=4, d_ff=8192, max_len=seq,
                                dtype="bfloat16", remat=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    velocity = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = jax.jit(make_train_step(model))

    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (batch, seq + 1), 0, cfg.vocab_size)
    x, y = tokens[:, :-1], tokens[:, 1:]

    # cost analysis BEFORE the first call: the lowering it produces is
    # exactly the trace the compile below reuses, so the probe is ~free
    flops_per_step = None
    try:
        ca = step.lower(params, velocity, x, y).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            f = float(ca.get("flops", 0.0) or 0.0)
            if f > 0:
                flops_per_step = f
    except Exception as e:
        from mxnet_tpu import dispatch as _dispatch

        _dispatch.note_cost_failure("bench.transformer_step",
                                    "lower.cost_analysis", e)

    params, velocity, loss = step(params, velocity, x, y)  # compile
    float(loss)  # real sync
    best = 0.0
    for _ in range(2 if quick else 3):
        t0 = _time.perf_counter()
        for _ in range(steps):
            params, velocity, loss = step(params, velocity, x, y)
        float(np.asarray(loss))  # host fetch: real execution barrier
        dt = _time.perf_counter() - t0
        best = max(best, batch * seq * steps / dt)

    n_params = sum(int(np.prod(v.shape))
                   for v in jax.tree_util.tree_leaves(params))
    # no peak for this device_kind -> no utilization: null, not a default
    peak = (device_peaks() or {}).get("bf16_flops")

    def mfu(flops_per_sec):
        return round(flops_per_sec / peak, 4) if peak else None

    steps_per_sec = best / (batch * seq)
    out = {
        "transformer_train_tokens_per_sec": round(best, 1),
        "transformer_params_m": round(n_params / 1e6, 1),
        "transformer_mfu_vs_v5e_peak": mfu(best * 6 * n_params),
        "transformer_loss": float(np.asarray(loss, np.float32)),
    }
    if flops_per_step is not None:
        out["mfu"] = mfu(steps_per_sec * flops_per_step)
        out["mfu_source"] = "xla_cost_analysis"
        out["transformer_flops_per_step"] = flops_per_step
    else:
        out["mfu"] = out["transformer_mfu_vs_v5e_peak"]
        out["mfu_source"] = "analytic_6n"
        # why the xla_cost_analysis source fell back (first recorded
        # cost-capture failure in this process, if any)
        from mxnet_tpu import dispatch as _dispatch

        fail = _dispatch.first_cost_failure()
        if fail:
            out["mfu_fallback_reason"] = "%s (%s)" % (fail["error"],
                                                      fail["stage"])
    if not quick:
        try:
            out["transformer_kernel_breakdown_ms"] = _kernel_breakdown(
                step, (params, velocity), (x, y), steps=3)
        except Exception as e:  # diagnostics must not sink the bench
            out["transformer_kernel_breakdown_error"] = str(e)
    return out


def _kernel_breakdown(step, state, data, steps=3):
    """Per-HLO-category device ms/step from a short jax.profiler trace
    (VERDICT r2 next #6 'publish a per-kernel breakdown in BENCH
    extras'): ``profiler.device_time_by_scope``'s rows summed by their
    category.  State threads through the loop, as in the timed loops."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from mxnet_tpu.profiler import device_time_by_scope

    outdir = tempfile.mkdtemp(prefix="benchprof")
    try:
        with jax.profiler.trace(outdir):
            params, velocity = state
            for _ in range(steps):
                params, velocity, loss = step(params, velocity, *data)
            float(np.asarray(loss))
        rows = device_time_by_scope(outdir, steps=steps)["rows"]
    finally:
        shutil.rmtree(outdir, ignore_errors=True)
    cats = {}
    for _leaf, _pass, ms, _share, _calls, category in rows:
        cats[category] = cats.get(category, 0.0) + ms
    return {cat: round(ms, 3)
            for cat, ms in sorted(cats.items(), key=lambda kv: -kv[1])
            if ms >= 0.01}


def _exit_code():
    """0 only for a round in which ``main`` ran to its end and every leg
    that ran ended ``ok``: a failed leg or a crashed round must not look
    like a measurement to whoever reads only the exit code."""
    extra = RESULT["extra"]
    failed = [k for k, v in extra.items()
              if k.endswith("_status") and isinstance(v, str)
              and not v.startswith(("ok", "skipped"))]
    if "error" in RESULT or "budget_exceeded" in extra or failed:
        return 1
    strict = os.environ.get("BENCH_REGRESSION_STRICT", "") not in ("", "0")
    flagged = (extra.get("regression_check") or {}).get("flagged")
    return 3 if strict and flagged else 0


if __name__ == "__main__":
    import atexit
    import signal as _signal

    try:
        _signal.signal(_signal.SIGTERM, _term_handler)
    except (ValueError, OSError, AttributeError):
        pass
    atexit.register(_emit_summary)
    # global ceiling until the first leg arms its own budget; legs re-arm
    # the remaining global budget on exit, so imports and between-leg
    # glue stay covered too
    _arm(_budget_s())
    try:
        main()
    except BudgetExceeded as e:
        RESULT["extra"]["budget_exceeded"] = str(e)
    except Exception as e:  # the driver needs a JSON line no matter what
        RESULT["error"] = "%s: %s" % (type(e).__name__, e)
    finally:
        _arm(0)
        _emit_summary()
        sys.exit(_exit_code())
