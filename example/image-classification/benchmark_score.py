#!/usr/bin/env python
"""Inference throughput benchmark across the model zoo (reference:
``example/image-classification/benchmark_score.py`` — the script behind
docs/faq/perf.md's tables / BASELINE.md).

Per model x batch size it reports BOTH measurement disciplines (see
``mxnet_tpu.benchmark``): the compiled-loop device throughput (the
stable, gate-able number) and the per-dispatch user-path wall clock
(host-dispatch sensitive; published with min/max spread).  Medians over
``--draws`` repetitions.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.benchmark import (  # noqa: E402
    compiled_throughput, percall_throughput)
from mxnet_tpu.gluon.model_zoo import vision  # noqa: E402


def score(model_name, batch_size, image_shape, dtype="float32",
          steps=30, draws=5, percall=False):
    net = getattr(vision, model_name)(classes=1000)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    data32 = mx.nd.array(np.random.uniform(
        size=(batch_size,) + image_shape).astype(np.float32))
    with mx.autograd.pause():
        # finish deferred init on a 1-sample input: the full-batch fp32
        # graph would be compiled once and thrown away after cast()
        net(data32[0:1])
    if dtype != "float32":
        net.cast(dtype)
        data = data32.astype(dtype)
    else:
        data = data32
    dev = compiled_throughput(net, data, steps=steps, draws=draws)
    res = {"compiled": dev}
    if percall:
        res["percall"] = percall_throughput(net, data, steps=steps,
                                            draws=draws)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--networks", type=str,
                    default="alexnet,vgg16,inception_v3,resnet50_v1,"
                            "resnet152_v1")
    ap.add_argument("--batch-sizes", type=str, default="1,32,128,256")
    ap.add_argument("--image-shape", type=str, default="3,224,224")
    ap.add_argument("--dtype", type=str, default="bfloat16")
    ap.add_argument("--steps", type=int, default=30,
                    help="forwards per timed draw")
    ap.add_argument("--draws", type=int, default=5,
                    help="timed repetitions per cell (median reported)")
    ap.add_argument("--percall", action="store_true",
                    help="also time the per-dispatch user path")
    args = ap.parse_args()
    shape = tuple(int(x) for x in args.image_shape.split(","))
    for name in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            # inception's 299x299 canonical input, like the reference
            s = (3, 299, 299) if "inception" in name and shape[1] == 224 \
                else shape
            r = score(name, bs, s, args.dtype, args.steps, args.draws,
                      args.percall)
            c = r["compiled"]
            line = ("network: %-14s batch: %4d dtype: %-9s  "
                    "compiled: %9.1f img/s [%9.1f, %9.1f]"
                    % (name, bs, args.dtype, c["median"], c["min"],
                       c["max"]))
            if "percall" in r:
                p = r["percall"]
                line += ("  percall: %9.1f img/s [%9.1f, %9.1f]"
                         % (p["median"], p["min"], p["max"]))
            print(line, flush=True)


if __name__ == "__main__":
    main()
