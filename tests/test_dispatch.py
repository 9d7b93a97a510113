"""Donation-aware fused dispatch path: buffer donation, persistent compile
cache, shape-bucketed recompile avoidance (docs/PERF_DISPATCH.md).

Covers the dispatch module itself (bucket specs, donation scopes, TrackedJit
counters), the FusedTrainStep donation/bucketing semantics (bit-identical
numerics, single compile across ragged batches, clear error on stale donated
handles), the imperative Trainer donation path, the executor backward
donation, the io/DataLoader bucketing boundary, and the steady-state
no-tree-flatten regression guard.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import dispatch, gluon, profiler
from mxnet_tpu import symbol as sym_api
from mxnet_tpu.gluon.contrib import FusedTrainStep

from conftest import subprocess_env


# ---------------------------------------------------------------- helpers

def _tiny_net():
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu"))
        net.add(gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    return net


def _copy_params(src, dst):
    for ps, pd in zip(src.collect_params().values(),
                      dst.collect_params().values()):
        pd.set_data(ps.list_data()[0].copy())


def _data(batch=8):
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(batch, 12).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 10, (batch,)))
    return x, y


def _assert_params_match(netA, netB, **tol):
    for pA, pB in zip(netA.collect_params().values(),
                      netB.collect_params().values()):
        a, b = pA.list_data()[0].asnumpy(), pB.list_data()[0].asnumpy()
        if tol:
            np.testing.assert_allclose(a, b, **tol)
        else:
            np.testing.assert_array_equal(a, b)


# --------------------------------------------------------- dispatch module

def test_bucket_size_specs():
    # explicit bucket list: smallest bucket >= n; above max -> n itself
    assert dispatch.bucket_size(3, "8,16,32") == 8
    assert dispatch.bucket_size(8, "8,16,32") == 8
    assert dispatch.bucket_size(9, "8,16,32") == 16
    assert dispatch.bucket_size(33, "8,16,32") == 33
    assert dispatch.bucket_size(5, (4, 16)) == 16
    # pow2: next power of two
    assert dispatch.bucket_size(1, "pow2") == 1
    assert dispatch.bucket_size(5, "pow2") == 8
    assert dispatch.bucket_size(8, "pow2") == 8
    assert dispatch.bucket_size(100, "pow2") == 128
    # off: identity (default knob MXNET_SHAPE_BUCKETS is unset)
    assert dispatch.bucket_size(7, "") == 7
    assert dispatch.bucket_size(7, None) == 7


def test_pad_batch_wraps_rows():
    x = np.arange(6, dtype=np.float32).reshape(3, 2)
    out = np.asarray(dispatch.pad_batch(x, 8))
    assert out.shape == (8, 2)
    # pad rows wrap around the real rows (NDArrayIter 'pad' semantics)
    np.testing.assert_array_equal(out[3], x[0])
    np.testing.assert_array_equal(out[7], x[1])


def test_donation_scope_thread_local():
    assert dispatch.donation_active()  # knob default: on
    with dispatch.no_donation():
        assert not dispatch.donation_active()
        with dispatch.donation_scope(True):
            assert dispatch.donation_active()
        assert not dispatch.donation_active()
    assert dispatch.donation_active()
    # donation_scope(None) is a passthrough no-op
    with dispatch.donation_scope(None):
        assert dispatch.donation_active()


def test_tracked_jit_counters():
    import jax.numpy as jnp

    before = profiler.dispatch_stats()
    fn = dispatch.TrackedJit(lambda a: a * 2.0, label="t_counters")
    x = mx.nd.array(np.ones(4, np.float32))
    fn(x.data)   # compile: miss + recompile
    fn(x.data)   # cached: hit
    d = profiler.dispatch_stats()
    assert d["recompile"] - before["recompile"] == 1
    assert d["jit_cache_miss"] - before["jit_cache_miss"] == 1
    assert d["jit_cache_hit"] - before["jit_cache_hit"] >= 1

    # donating variant counts donated bytes and consumes the input
    fn2 = dispatch.TrackedJit(lambda a: a + 1.0, donate_argnums=(0,),
                              label="t_donate")
    buf = jnp.ones(8, jnp.float32)
    fn2(buf)
    d2 = profiler.dispatch_stats()
    assert d2["donated_bytes"] - d["donated_bytes"] == 32
    assert buf.is_deleted()


# ------------------------------------------------- fused donation numerics

@pytest.mark.parametrize("opt,opt_args", [
    ("sgd", {"learning_rate": 0.5, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
])
def test_fused_donated_step_bit_identical(opt, opt_args):
    """Donation only changes buffer lifetime, never math: the donated fused
    step must be BIT-identical to the non-donated one over 3 steps."""
    x, y = _data()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    netA, netB = _tiny_net(), _tiny_net()
    netA(x), netB(x)
    _copy_params(netA, netB)
    trA = gluon.Trainer(netA.collect_params(), opt, dict(opt_args))
    trB = gluon.Trainer(netB.collect_params(), opt, dict(opt_args))
    stepA = FusedTrainStep(netA, loss_fn, trA, donate=True)
    stepB = FusedTrainStep(netB, loss_fn, trB, donate=False)
    for _ in range(3):
        lA = stepA(x, y).asnumpy()
        lB = stepB(x, y).asnumpy()
        np.testing.assert_array_equal(lA, lB)
    _assert_params_match(netA, netB)


def test_trainer_imperative_donation_numerics():
    """The record/backward/Trainer(donate=True).step path matches the
    non-donated path exactly."""
    x, y = _data()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    netA, netB = _tiny_net(), _tiny_net()
    netA(x), netB(x)
    _copy_params(netA, netB)
    trA = gluon.Trainer(netA.collect_params(), "sgd",
                        {"learning_rate": 0.5, "momentum": 0.9},
                        donate=True)
    trB = gluon.Trainer(netB.collect_params(), "sgd",
                        {"learning_rate": 0.5, "momentum": 0.9},
                        donate=False)
    for _ in range(3):
        for net, tr in ((netA, trA), (netB, trB)):
            with mx.autograd.record():
                l = loss_fn(net(x), y)
            l.backward()
            tr.step(x.shape[0])
    _assert_params_match(netA, netB)


def test_donated_buffer_reuse_raises_clear_error():
    """Reading a pre-step param handle after a donated fused step must
    raise a RuntimeError that explains donation, not a cryptic XLA one."""
    x, y = _data()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _tiny_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = FusedTrainStep(net, loss_fn, tr, donate=True)
    # param NDArrays are refreshed in place by the write-back, so they
    # never go stale; what goes stale is anything still wrapping the
    # PRE-step device buffer
    w = list(net.collect_params().values())[0].list_data()[0]
    stale = mx.nd.NDArray(w.data)
    step(x, y)
    assert stale.data.is_deleted()
    with pytest.raises(RuntimeError, match="donated"):
        stale.asnumpy()
    # the refreshed param handle reads fine
    assert np.isfinite(w.asnumpy()).all()


# ------------------------------------------------- bucketed recompile count

def test_fused_bucketing_single_compile_across_ragged_batches():
    x, y = _data(8)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _tiny_net()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = FusedTrainStep(net, loss_fn, tr, bucket="8")
    step(x, y)  # the one compile
    base = profiler.dispatch_stats()
    for n in (7, 5, 3):  # >=3 ragged final-batch sizes
        loss = step(x[:n], y[:n])
        assert loss.shape[0] == n  # padded rows are sliced back off
    after = profiler.dispatch_stats()
    assert after["recompile"] - base["recompile"] == 0
    assert after["bucket_padded_batches"] - base["bucket_padded_batches"] == 3
    assert after["jit_cache_hit"] - base["jit_cache_hit"] >= 3


def test_fused_bucketing_matches_unbucketed_numerics():
    """Pad rows are masked out of the loss and rescale_grad counts only
    real rows, so a bucketed ragged step equals the unpadded step."""
    x, y = _data(8)
    n = 5
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    netA, netB = _tiny_net(), _tiny_net()
    netA(x), netB(x)
    _copy_params(netA, netB)
    trA = gluon.Trainer(netA.collect_params(), "sgd", {"learning_rate": 0.5})
    trB = gluon.Trainer(netB.collect_params(), "sgd", {"learning_rate": 0.5})
    stepA = FusedTrainStep(netA, loss_fn, trA, bucket="8")
    stepB = FusedTrainStep(netB, loss_fn, trB, bucket=False)
    for _ in range(2):
        lA = stepA(x[:n], y[:n]).asnumpy()
        lB = stepB(x[:n], y[:n]).asnumpy()
        np.testing.assert_allclose(lA, lB, rtol=1e-6, atol=1e-7)
    _assert_params_match(netA, netB, rtol=1e-6, atol=1e-7)


# -------------------------------------------------- executor backward path

def _bn_executor():
    data = sym_api.Variable("data")
    net = sym_api.FullyConnected(data, num_hidden=8, name="fc")
    net = sym_api.BatchNorm(net, fix_gamma=False, name="bn")
    out = sym_api.sum(net)
    exe = out.simple_bind(ctx=mx.cpu(), data=(4, 6), grad_req="write")
    rng = np.random.RandomState(7)
    for name, arr in exe.arg_dict.items():
        arr[:] = rng.randn(*arr.shape).astype(np.float32) * 0.1
    return exe


def test_executor_backward_donation_consistent():
    """Executor backward donates its aux snapshot; numerics must match the
    non-donated path (grads + updated aux) over repeated fwd/bwd."""
    exeA, exeB = _bn_executor(), _bn_executor()
    for _ in range(2):
        exeA.forward(is_train=True)
        exeA.backward()
    with dispatch.no_donation():
        for _ in range(2):
            exeB.forward(is_train=True)
            exeB.backward()
    for gA, gB in zip(exeA.grad_arrays, exeB.grad_arrays):
        if gA is not None:
            np.testing.assert_array_equal(gA.asnumpy(), gB.asnumpy())
    for aA, aB in zip(exeA.aux_arrays, exeB.aux_arrays):
        np.testing.assert_array_equal(aA.asnumpy(), aB.asnumpy())


# --------------------------------------------------- io/DataLoader boundary

def test_bucket_pad_iter():
    data = np.arange(20, dtype=np.float32).reshape(10, 2)
    label = np.arange(10, dtype=np.float32)
    # inner iterator yields batches of 3; bucket 4 pads every batch up
    inner = mx.io.NDArrayIter(data, label, batch_size=3)
    it = mx.io.BucketPadIter(inner, buckets=[4])
    batches = list(it)
    assert batches, "no batches"
    assert all(b.data[0].shape == (4, 2) for b in batches)
    assert all(b.label[0].shape == (4,) for b in batches)
    assert all(b.pad >= 1 for b in batches)  # accounts for bucket rows
    # wrap-around pad rows repeat the leading real rows
    first = batches[0].data[0].asnumpy()
    np.testing.assert_array_equal(first[3], first[0])
    it.reset()
    assert len(list(it)) == len(batches)


def test_dataloader_bucket_pads_final_batch():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    ds = ArrayDataset(np.arange(22, dtype=np.float32).reshape(11, 2),
                      np.arange(11, dtype=np.float32))
    before = profiler.dispatch_stats()["bucket_padded_batches"]
    dl = DataLoader(ds, batch_size=4, bucket=[4, 8])
    shapes = [(d.shape, l.shape) for d, l in dl]
    assert shapes == [((4, 2), (4,))] * 3
    # wrap-around: padded row repeats the first real row of the batch
    last = list(dl)[-1][0].asnumpy()
    np.testing.assert_array_equal(last[3], last[0])
    assert profiler.dispatch_stats()["bucket_padded_batches"] > before
    # bucket off (default knob unset): ragged final batch passes through
    shapes2 = [d.shape for d, _ in DataLoader(ds, batch_size=4)]
    assert shapes2[-1] == (3, 2)


# ----------------------------------------------- steady-state dispatch cost

def test_no_tree_flatten_in_steady_state():
    """Regression guard (ISSUE: dispatch plan caching): after warmup,
    neither the hybrid forward nor the fused step may flatten trees on
    the hot path."""
    from mxnet_tpu.gluon import block as block_mod

    x, y = _data()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net = _tiny_net()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = FusedTrainStep(net, loss_fn, tr)
    step(x, y)
    step(x, y)  # warmup: probe + compile done

    calls = {"flatten": 0, "states": 0}
    real_flatten = block_mod._flatten_arrays
    real_states = FusedTrainStep._flat_states

    def counting_flatten(*a, **k):
        calls["flatten"] += 1
        return real_flatten(*a, **k)

    def counting_states(self):
        calls["states"] += 1
        return real_states(self)

    block_mod._flatten_arrays = counting_flatten
    FusedTrainStep._flat_states = counting_states
    try:
        for _ in range(3):
            step(x, y)
        net(x)  # hybrid forward fast path: plain NDArray in, no flatten
    finally:
        block_mod._flatten_arrays = real_flatten
        FusedTrainStep._flat_states = real_states
    assert calls == {"flatten": 0, "states": 0}, calls


# ------------------------------------------------- persistent compile cache

def test_compile_cache_placed_by_environment(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, entries land there and the
    program sets no directory in code."""
    cache = str(tmp_path / "xla-cache")
    child = (
        "import os, jax\n"
        "updates, real = [], jax.config.update\n"
        "def spy(name, val):\n"
        "    updates.append(name)\n"
        "    return real(name, val)\n"
        "jax.config.update = spy\n"
        "import mxnet_tpu as mx, numpy as np\n"
        "assert 'jax_compilation_cache_dir' not in updates, updates\n"
        "want = os.environ['JAX_COMPILATION_CACHE_DIR']\n"
        "assert mx.runtime.compile_cache_dir() == want\n"
        "out = (mx.nd.array(np.ones(4, np.float32)) * 3.0).asnumpy()\n"
        "assert out.tolist() == [3.0] * 4\n"
    )
    # JAX's own threshold keeps sub-second compiles out of the cache; the
    # test's program is tiny, so lower it the way JAX offers
    env = subprocess_env(JAX_COMPILATION_CACHE_DIR=cache,
                         JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.listdir(cache), "persistent compile cache wrote no entries"


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch):
    """Where the variable is not set the cache is one fixed directory
    inside the checkout — not derived from a pid, a time or a temp name."""
    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert mx.runtime.init_compile_cache() == \
            os.path.join(repo, ".xla_cache")
        assert mx.runtime.compile_cache_dir() == \
            os.path.join(repo, ".xla_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
