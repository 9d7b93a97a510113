#!/usr/bin/env python
"""Device profile of a training step: where the device's time goes, by
``jax.named_scope`` and by pass (``mxnet_tpu.profiler.device_time_by_scope``,
the table ``profiler.dumps()`` prints after a session with a
``tensorboard_dir``; docs/OBSERVABILITY.md "Device time by named scope").

The reference ships a per-op profiler (``src/profiler/profiler.cc``,
``mx.profiler``) that we mirror at op granularity in
``mxnet_tpu/profiler.py``; this tool takes the device side of it for a whole
training step: a Gluon model through ``FusedTrainStep`` (the default), or
with ``--cell`` the very step a benchmark cell measures, built by the
benchmark's own ``harness/cells.py`` and the family's ``Trainer``.

Usage:
    python tools/profile_train.py [--model resnet50_v1] [--batch 128]
                                  [--steps 10] [--out /tmp/jaxprof]
    python tools/profile_train.py --cell smallthinker_train_s16k --steps 10
    python tools/profile_train.py --summarize-only --out <dir> --hlo <file>
"""
import argparse
import collections
import gzip
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)


class _GluonTrainer:
    """A model-zoo network through ``FusedTrainStep``, with the three calls
    the benchmark's trainers have."""

    in_flight = 1

    def __init__(self, model_name, batch, dtype):
        import numpy as np
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.contrib import FusedTrainStep
        from mxnet_tpu.gluon.model_zoo import vision

        ctx = mx.tpu() if jax.default_backend() != "cpu" else mx.cpu()
        net = getattr(vision, model_name)(classes=1000)
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net.hybridize(static_alloc=True, static_shape=True)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        rng = np.random.RandomState(0)
        x32 = mx.nd.array(rng.rand(batch, 3, 224, 224).astype(np.float32),
                          ctx=ctx)
        self.y = mx.nd.array(rng.randint(0, 1000, (batch,)), ctx=ctx)
        with mx.autograd.pause():
            net(x32)
        if dtype != "float32":
            net.cast(dtype)
        self.x = x32.astype(dtype)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9,
                                 "multi_precision": dtype != "float32"})
        self._step = FusedTrainStep(net, loss_fn, trainer)
        self._last = None

    def step(self, i):
        self._last = self._step(self.x, self.y)
        return self._last

    @staticmethod
    def fetch(loss):
        return loss.asnumpy()

    def fence(self):
        if self._last is not None:
            self._last.asnumpy()


def _cell_trainer(name, seed, rehearse):
    """The trainer of one benchmark cell, as ``benchmarks/run.py`` builds
    it (the harness is read, not edited: the profiled program is the
    measured one)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import cells, runtime

    cell = cells.Cell(cells.load_benchmark(), name)
    if cell.traffic["kind"] != "train_fixed":
        sys.exit("profile_train: %s is no training cell" % name)
    if rehearse:
        cell.rehearse()
    devices = runtime.find_devices(cell.chips, rehearse)
    trainer = cell.family.Trainer(cell.config, cell.traffic, seed, devices)
    trainer.in_flight = cell.traffic["in_flight"]
    return trainer, 3 + cell.traffic["warmup_steps"]


def _run(trainer, first, steps):
    """``steps`` steps with ``in_flight`` queued ahead of the loss being
    fetched, fenced at both ends, as the benchmark's window runs them.
    Returns the seconds a step."""
    pending = collections.deque()
    trainer.fence()
    t0 = time.perf_counter()
    for i in range(first, first + steps):
        pending.append(trainer.step(i))
        while len(pending) > trainer.in_flight:
            trainer.fetch(pending.popleft())
    while pending:
        trainer.fetch(pending.popleft())
    trainer.fence()
    return (time.perf_counter() - t0) / steps


def capture(trainer, warmup, steps, outdir):
    """Warm up, time ``steps`` steps untraced, then the same under the
    program's own profiler session.  Returns (seconds a step outside the
    profile, inside it)."""
    from mxnet_tpu import profiler

    for i in range(warmup):
        trainer.fetch(trainer.step(i))
    outside = _run(trainer, warmup, steps)
    profiler.set_config(aggregate_stats=True, tensorboard_dir=outdir)
    profiler.set_state("run")
    inside = _run(trainer, warmup + steps, steps)
    profiler.set_state("stop")
    return outside, inside


def summarize(outdir, steps, hlo=None, min_share=0.01):
    from mxnet_tpu import profiler

    t0 = time.perf_counter()
    try:
        result = profiler.device_time_by_scope(outdir, hlo=hlo, steps=steps)
    except ValueError as e:             # a CPU run: no device plane
        print("no device table: %s" % e)
        return None
    took = time.perf_counter() - t0
    print(profiler.device_table(result, min_share=min_share))
    print("reduced in %.2f s" % took)
    return result


def _save(save, outdir):
    """Beside the table: the trace in its neutral form and the text of every
    loaded program, so that the reduction can be made again elsewhere
    (``--summarize-only --out <save> --hlo <save>/hlo.json.gz``)."""
    from mxnet_tpu import profiler

    os.makedirs(save, exist_ok=True)
    with gzip.open(os.path.join(save, "trace.neutral.json.gz"), "wt") as f:
        json.dump(profiler._load_trace(outdir), f)
    with gzip.open(os.path.join(save, "hlo.json.gz"), "wt") as f:
        json.dump(profiler.live_hlo(), f)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--cell", default=None,
                    help="a training cell of BENCHMARK.json: profile the "
                         "step it measures instead of a model-zoo network")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="with --cell: its toy sizes, on any backend")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default="/tmp/jaxprof")
    ap.add_argument("--min-share", type=float, default=0.01,
                    help="rows under this share of busy time are summed")
    ap.add_argument("--save", default=None,
                    help="keep the neutral trace and the programs' text "
                         "there")
    ap.add_argument("--summarize-only", action="store_true",
                    help="read an existing trace instead of capturing")
    ap.add_argument("--hlo", default=None,
                    help="with --summarize-only: the programs' text (a "
                         "file of HLO text, or --save's hlo.json.gz)")
    args = ap.parse_args()
    hlo = None
    if args.summarize_only:
        if args.hlo:
            opener = gzip.open if args.hlo.endswith(".gz") else open
            with opener(args.hlo, "rt") as f:
                hlo = json.load(f) if ".json" in args.hlo else f.read()
    else:
        import jax

        # the persistent cache's key leaves metadata out, so a program
        # found there carries the scopes of the tree that compiled it:
        # compile anew, and the table names what this tree names
        jax.config.update("jax_enable_compilation_cache", False)
        if args.cell:
            trainer, warmup = _cell_trainer(args.cell, args.seed,
                                            args.rehearse)
        else:
            trainer, warmup = _GluonTrainer(args.model, args.batch,
                                            args.dtype), 3
        outside, inside = capture(trainer, warmup, args.steps, args.out)
        print("step %.3f ms outside the profile, %.3f ms inside it "
              "(host clock, %d steps each, fenced)"
              % (outside * 1e3, inside * 1e3, args.steps))
        if args.save:
            _save(args.save, args.out)
    summarize(args.out, args.steps, hlo, args.min_share)


if __name__ == "__main__":
    main()
