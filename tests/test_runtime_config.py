"""Config/env tier + runtime feature tests (reference: docs/faq/env_var.md
knob table, python/mxnet/runtime.py feature introspection)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.config import config, describe
from mxnet_tpu.test_utils import check_consistency


def test_config_defaults_and_env(monkeypatch):
    assert config.engine_type == "ThreadedEnginePerDevice"
    assert not config.naive_engine
    assert config.cpu_worker_nthreads == 4
    monkeypatch.setenv("MXNET_CPU_WORKER_NTHREADS", "9")
    assert config.cpu_worker_nthreads == 9
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")
    assert config.naive_engine
    table = describe()
    assert "MXNET_ENGINE_TYPE" in table and "inert" in table
    assert config.describe() == table  # mx.config.describe() works too
    # shell-convention falsy values parse as False
    for v in ("FALSE", "no", "off", "0", " False "):
        monkeypatch.setenv("MXNET_PROFILER_AUTOSTART", v)
        assert not config.profiler_autostart, v
    monkeypatch.setenv("MXNET_PROFILER_AUTOSTART", "1")
    assert config.profiler_autostart


def test_naive_engine_executes_correctly(monkeypatch):
    """NaiveEngine skips jit but must give identical results — including
    ops with array_params (traced scalars), which the interpreted path
    must pass by keyword."""
    x = np.random.RandomState(0).randn(3, 4).astype(np.float32)
    ref = mx.nd.relu(mx.nd.array(x)).asnumpy()
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")
    out = mx.nd.relu(mx.nd.array(x)).asnumpy()
    np.testing.assert_array_equal(out, ref)
    # scalar-broadcast comparison (array_params path)
    gt = (mx.nd.array(x) > 0.5).asnumpy()
    np.testing.assert_array_equal(gt, (x > 0.5).astype(np.float32))
    # momentum optimizer update (lr/momentum array_params)
    w = mx.nd.ones((3,))
    g = mx.nd.ones((3,))
    mom = mx.nd.zeros((3,))
    mx.nd.sgd_mom_update(w, g, mom, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(w.asnumpy(), 0.9, rtol=1e-6)


def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats.is_enabled("CPU")
    assert feats.is_enabled("PALLAS")
    assert feats.is_enabled("DIST_KVSTORE")
    assert not feats.is_enabled("CUDA")  # no CUDA analogue on TPU builds
    names = {f.name for f in mx.runtime.feature_list()}
    assert {"TPU", "OPENCV", "INT8"} <= names


def test_explicit_accelerator_context_never_hides_the_device():
    """``mx.tpu()``/``mx.gpu()`` with no accelerator, or an index past the
    device count, raises; the default context follows the backend."""
    for ctx in (mx.Context("tpu", 0), mx.gpu(0), mx.tpu(5)):
        with pytest.raises(ValueError, match="0 accelerator device"):
            ctx.jax_device()
    assert mx.cpu(0).jax_device().platform == "cpu"
    assert mx.current_context() == mx.cpu(0)
    # the CPU has no row in the peaks table, so no utilization is derived
    assert mx.runtime.device_peaks() is None
    assert "TPU v5 lite" in mx.runtime.DEVICE_PEAKS


def test_profiler_autostart_env():
    r = subprocess.run(
        [sys.executable, "-c",
         "import devtools, mxnet_tpu as mx; print(mx.profiler.state())"],
        env={**os.environ, "MXNET_PROFILER_AUTOSTART": "1"},
        capture_output=True, text=True, cwd="/root/repo", timeout=300)
    assert r.stdout.strip().endswith("run"), r.stdout + r.stderr


def test_check_consistency_single_device_is_meaningful():
    """On one device the oracle leg runs with jit disabled, so the check
    compares interpreted vs compiled execution (not x against itself)."""
    check_consistency(
        lambda a, b: mx.nd.dot(mx.nd.relu(a), b),
        [(4, 5), (5, 3)], ctx_list=[mx.cpu(0), mx.cpu(0)])
