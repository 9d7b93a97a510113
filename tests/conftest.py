"""Test configuration: virtual 8-device CPU mesh, reproducible seeds.

Mirrors the reference's test strategy (SURVEY.md §4): context-generic corpus
run on CPU by default (CPU is the reference oracle), with the same tests
re-runnable on real TPU; multi-device collective tests use a virtual 8-device
host platform (the analogue of `launch.py --launcher local` multi-process
testing without a cluster).
"""
import os
import sys

# Force the CPU oracle backend: the unit tests never need the chip, and a
# chip belongs to one process at a time.  JAX honours JAX_PLATFORMS when it
# is set before the first `import jax`, which is here.
# Set MXTPU_TEST_ON_TPU=1 to rerun the same corpus on the real chip
# (reference parity: tests/python/gpu/test_operator_gpu.py reruns the
# unittest corpus with default ctx = gpu).
if not os.environ.get("MXTPU_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# the CPU oracle must be numerically faithful: default matmul precision uses
# bf16 passes (TPU-style) even on host — force full f32 for the test corpus
jax.config.update("jax_default_matmul_precision", "highest")


def subprocess_env(**extra):
    """Env for test subprocesses: CPU oracle backend, 8-device virtual
    mesh, and a repo-only PYTHONPATH.  Single source of truth for every
    test that spawns a python child (import as
    ``from conftest import subprocess_env``)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": repo}
    env.update(extra)
    return env


def pytest_configure(config):
    # the tier-1 gate runs with -m 'not slow'; register the marker so
    # the deselect is intentional, not a typo pytest warns about
    config.addinivalue_line(
        "markers", "slow: long-running variant excluded from the tier-1 "
        "gate (run explicitly with -m slow)")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection scenario (the chaos "
        "harness; run the full matrix with `make chaos` / "
        "ci/runtime_functions.sh chaos_check)")


def pytest_terminal_summary(terminalreporter):
    """Print the dispatch counters (jit cache hits/misses, recompiles,
    donated bytes) after every run — the tier-1 gate reads these to spot
    recompile regressions (ci/runtime_functions.sh)."""
    try:
        from mxnet_tpu import profiler

        stats = profiler.dispatch_stats()
        terminalreporter.write_sep(
            "-", "dispatch counters (mxnet_tpu.profiler.dispatch_stats)")
        terminalreporter.write_line(
            "  ".join("%s=%d" % (k, v) for k, v in sorted(stats.items())))
    except Exception:
        pass  # never let diagnostics fail the suite
    # on failure, dump the full telemetry registry: the counters/gauges/
    # histograms the run accumulated are exactly the state a triager
    # would ask for first (docs/OBSERVABILITY.md)
    if not (terminalreporter.stats.get("failed")
            or terminalreporter.stats.get("error")):
        return
    try:
        from mxnet_tpu import telemetry

        snap = telemetry.registry().snapshot()
        terminalreporter.write_sep(
            "-", "telemetry registry snapshot (failures present)")
        for kind in ("counters", "gauges"):
            live = {k: v for k, v in sorted(snap[kind].items()) if v}
            if live:
                terminalreporter.write_line("%s: %s" % (
                    kind, "  ".join("%s=%g" % kv for kv in live.items())))
        for name, h in sorted(snap["histograms"].items()):
            if h["count"]:
                terminalreporter.write_line(
                    "hist %s: count=%d p50=%.3g p99=%.3g max=%.3g"
                    % (name, h["count"], h["p50"], h["p99"], h["max"]))
    except Exception:
        pass  # never let diagnostics fail the suite
    try:
        from mxnet_tpu import leakcheck

        if leakcheck.installed():
            snap = leakcheck.snapshot()
            terminalreporter.write_sep(
                "-", "leakcheck ledger (failures present)")
            terminalreporter.write_line(
                "live: %s  counters: %s"
                % ("  ".join("%s=%d" % kv
                             for kv in sorted(snap["live"].items())),
                   "  ".join("%s=%d" % kv
                             for kv in sorted(snap["counters"].items()))))
            for kind, entries in sorted(snap.get("sites", {}).items()):
                for e in entries:
                    terminalreporter.write_line(
                        "  %s: %s [%s]" % (kind, e["site"], e["thread"]))
    except Exception:
        pass  # never let diagnostics fail the suite
    try:
        from mxnet_tpu import racecheck

        if racecheck.installed():
            snap = racecheck.snapshot()
            terminalreporter.write_sep(
                "-", "racecheck ledger (failures present)")
            terminalreporter.write_line(
                "field states: %s  counters: %s"
                % ("  ".join("%s=%d" % kv for kv in
                             sorted(snap["field_states"].items())),
                   "  ".join("%s=%d" % kv for kv in
                             sorted(snap["counters"].items()))))
            for r in snap["races"]:
                terminalreporter.write_line(
                    "  %s.%s: %s at %s [%s, %s] vs %s at %s [%s, %s]"
                    % (r["cls"], r["field"],
                       r["access"]["kind"], r["access"]["at"],
                       r["access"]["thread"], r["access"]["held"],
                       r["prior"]["kind"], r["prior"]["at"],
                       r["prior"]["thread"], r["prior"]["held"]))
    except Exception:
        pass  # never let diagnostics fail the suite


@pytest.fixture(autouse=True)
def _seed_everything():
    """Reference parity: @with_seed decorator — reproducible randomized
    tests.  MXTPU_TEST_SEED (set by tools/flakiness_checker.py) varies
    the seed to surface flaky tolerance margins."""
    import mxnet_tpu as mx

    seed = int(os.environ.get("MXTPU_TEST_SEED", "0"))
    np.random.seed(seed)
    mx.random.seed(seed)
    yield


@pytest.fixture(autouse=True)
def _reset_brownout():
    """The brownout ladder is process-global and fed by every
    FleetSupervisor tick — reset it after each test so an overload test
    cannot leak degraded admission into its neighbors."""
    yield
    serving = sys.modules.get("mxnet_tpu.serving")
    if serving is not None and serving._BROWNOUT is not None:
        serving._BROWNOUT.reset()


@pytest.fixture(autouse=True)
def _leakcheck_quiescent():
    """When the leak sanitizer is armed (MXTPU_LEAKCHECK, the CI chaos/
    gateway/failover lanes), every test must end quiescent: pages freed,
    probe slots released, futures settled, journals evicted.  In raise
    mode a leak fails THIS test (the one that leaked), with creation
    sites in the LeakError; the ledger is cleared afterwards so one leak
    cannot cascade into its neighbors."""
    yield
    leakcheck = sys.modules.get("mxnet_tpu.leakcheck")
    if leakcheck is None or not leakcheck.installed():
        return
    try:
        leakcheck.assert_quiescent()
    finally:
        leakcheck.reset()
