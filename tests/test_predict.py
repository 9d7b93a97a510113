"""Serving/predict API tests (reference: c_predict_api semantics +
tests/python/predict).

The gold test: train a net, export, reload in a FRESH PROCESS, and check
bitwise-equal logits.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.predict import (MXPredCreate, MXPredForward, MXPredFree,
                               MXPredGetOutput, MXPredGetOutputShape,
                               MXPredReshape, MXPredSetInput, Predictor)


def _make_net():
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Conv2D(8, 3, padding=1))
        net.add(gluon.nn.BatchNorm())
        net.add(gluon.nn.Activation("relu"))
        net.add(gluon.nn.GlobalAvgPool2D())
        net.add(gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    return net


@pytest.fixture()
def exported(tmp_path):
    net = _make_net()
    x = mx.nd.array(np.random.RandomState(0).rand(2, 3, 8, 8)
                    .astype(np.float32))
    # a couple of training steps so BN aux states are non-trivial
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    y = mx.nd.array(np.array([1, 3]))
    for _ in range(2):
        with mx.autograd.record():
            l = lf(net(x), y)
        l.backward()
        tr.step(2)
    prefix = str(tmp_path / "model")
    net.export(prefix, epoch=0)
    logits = net(x).asnumpy()
    return prefix, x.asnumpy(), logits


def test_export_writes_symbol_and_params(exported):
    prefix, _, _ = exported
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0000.params")
    g = json.loads(open(prefix + "-symbol.json").read())
    assert any(n["op"] == "BatchNorm" for n in g["nodes"])


def test_predictor_matches_gluon(exported):
    prefix, xn, logits = exported
    pred = Predictor(prefix + "-symbol.json", prefix + "-0000.params",
                     input_shapes={"data": (2, 3, 8, 8)})
    out = pred.forward(data=mx.nd.array(xn))[0].asnumpy()
    np.testing.assert_allclose(out, logits, rtol=1e-5, atol=1e-6)


def test_predictor_fresh_process(exported, tmp_path):
    """Reference round-trip: export -> reload in a fresh process ->
    equal logits."""
    prefix, xn, logits = exported
    np.save(str(tmp_path / "x.npy"), xn)
    np.save(str(tmp_path / "want.npy"), logits)
    script = """
import sys, numpy as np
import mxnet_tpu as mx
from mxnet_tpu.predict import Predictor
prefix, xf, wf = sys.argv[1], sys.argv[2], sys.argv[3]
x = np.load(xf); want = np.load(wf)
p = Predictor(prefix + "-symbol.json", prefix + "-0000.params",
              input_shapes={"data": x.shape})
out = p.forward(data=mx.nd.array(x))[0].asnumpy()
np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
print("FRESH_PROCESS_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script, prefix,
                        str(tmp_path / "x.npy"), str(tmp_path / "want.npy")],
                       capture_output=True, text=True, env=env,
                       cwd="/root/repo", timeout=300)
    assert "FRESH_PROCESS_OK" in r.stdout, r.stdout + r.stderr


def test_c_shaped_abi(exported):
    prefix, xn, logits = exported
    h = MXPredCreate(open(prefix + "-symbol.json").read(),
                     open(prefix + "-0000.params", "rb").read(),
                     dev_type=1, dev_id=0,
                     input_keys=["data"], input_shapes=[(2, 3, 8, 8)])
    MXPredSetInput(h, "data", mx.nd.array(xn))
    MXPredForward(h)
    out = MXPredGetOutput(h, 0)
    np.testing.assert_allclose(out, logits, rtol=1e-5, atol=1e-6)
    assert MXPredGetOutputShape(h, 0) == (2, 10)
    # reshape to a different batch
    h2 = MXPredReshape(h, ["data"], [(4, 3, 8, 8)])
    MXPredSetInput(h2, "data", mx.nd.array(np.concatenate([xn, xn], 0)))
    MXPredForward(h2)
    out2 = MXPredGetOutput(h2, 0)
    np.testing.assert_allclose(out2[:2], logits, rtol=1e-5, atol=1e-6)
    MXPredFree(h)
    MXPredFree(h2)


def test_module_checkpoint_predictor(tmp_path):
    """save_checkpoint format feeds the same Predictor."""
    data = mx.sym.var("data")
    w = mx.sym.var("fc_weight")
    b = mx.sym.var("fc_bias")
    out = mx.sym.FullyConnected(data, w, b, num_hidden=5, name="fc")
    arg = {"fc_weight": mx.nd.array(np.random.rand(5, 4).astype(np.float32)),
           "fc_bias": mx.nd.zeros((5,))}
    from mxnet_tpu.model import save_checkpoint
    save_checkpoint(str(tmp_path / "m"), 3, out, arg, {})
    pred = Predictor(str(tmp_path / "m-symbol.json"),
                     str(tmp_path / "m-0003.params"),
                     input_shapes={"data": (2, 4)})
    xn = np.random.rand(2, 4).astype(np.float32)
    got = pred.forward(data=mx.nd.array(xn))[0].asnumpy()
    want = xn @ arg["fc_weight"].asnumpy().T
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_symbolblock_imports_export(exported):
    """Gluon-side consumption: SymbolBlock.imports round trip."""
    prefix, xn, logits = exported
    net2 = gluon.SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                     prefix + "-0000.params")
    out = net2(mx.nd.array(xn)).asnumpy()
    np.testing.assert_allclose(out, logits, rtol=1e-5, atol=1e-6)


def test_symbolblock_finetune(exported):
    """Imported SymbolBlock can be trained (reference backward support)."""
    prefix, xn, _ = exported
    net2 = gluon.SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                     prefix + "-0000.params")
    tr = gluon.Trainer(net2.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    y = mx.nd.array(np.array([1, 3]))
    x = mx.nd.array(xn)
    losses = []
    for _ in range(4):
        with mx.autograd.record():
            l = lf(net2(x), y)
        l.backward()
        tr.step(2)
        losses.append(float(l.asnumpy().mean()))
    assert losses[-1] < losses[0], losses


def test_symbolblock_composes_and_reexports(exported, tmp_path):
    """Transfer-learning shape: SymbolBlock inside a new HybridBlock,
    symbolically exportable."""
    prefix, xn, logits = exported
    base = gluon.SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                     prefix + "-0000.params")
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(base)
        net.add(gluon.nn.Dense(3))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(xn)
    want = net(x).asnumpy()
    prefix2 = str(tmp_path / "composed")
    net.export(prefix2, epoch=0)
    pred = Predictor(prefix2 + "-symbol.json", prefix2 + "-0000.params",
                     input_shapes={"data": x.shape})
    got = pred.forward(data=x)[0].asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
