"""Throughput measurement utilities (reference:
``example/image-classification/benchmark_score.py`` +
``docs/faq/perf.md`` methodology, the scripts behind BASELINE.md).

Two disciplines, because the dispatch path and the device disagree about
what "throughput" means whenever the host is the slower of the two:

* :func:`compiled_throughput` — the K-step inference loop is compiled
  into ONE XLA module (``lax.fori_loop`` around the block's traced
  forward) with a runtime-zero probe chaining step *i*'s output into
  step *i+1*'s input.  One dispatch + one scalar fetch per draw, so the
  number measures the device, not the host.  The chain makes every
  iteration data-dependent on the previous one: XLA cannot hoist the
  network out of the loop (the carry changes each step as far as the
  compiler can prove — the zero arrives at run time) and cannot fold
  ``x * zero`` away (it is not a literal).  This is the stable gate
  metric: repeated draws agree within a few percent.
* :func:`percall_throughput` — the user path: one framework dispatch per
  ``net(x)`` call, timed wall-clock with a host value fetch as the
  barrier.  XLA's async dispatch pipelines this to device speed only
  while the host keeps ahead of the device, so it moves with host load.
  Published with its spread, never as a gate.

Both report the MEDIAN of ``draws`` timed repetitions with min/max
alongside, per VERDICT r3 ("median-of-k with documented k").
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from .ndarray.ndarray import NDArray

__all__ = ["compiled_throughput", "percall_throughput"]


def _first_out(out):
    if isinstance(out, (list, tuple)):
        out = out[0]
    return out.data


def _summarize(draws, items_per_draw):
    rates = [items_per_draw / dt for dt in draws]
    return {
        "median": float(np.median(rates)),
        "min": float(min(rates)),
        "max": float(max(rates)),
        "draws": len(rates),
    }


def _compiled_draw(net, x, steps):
    """Compile the K-step chained loop ONCE; return a zero-arg callable
    that runs one timed draw and returns ELAPSED SECONDS."""
    from .gluon.block import params_as_trace_inputs

    batch = x.shape[0]
    # parameters ride as explicit jit arguments (not trace constants):
    # a VGG-sized weight set embedded as HLO constants overflows the
    # remote-compile request body
    params = (list(net.collect_params().values())
              if hasattr(net, "collect_params") else [])
    pdatas = [p.data().data for p in params]

    def loop(xd, zero, pvals):
        stand_ins = [NDArray(v) for v in pvals]
        with params_as_trace_inputs(params, stand_ins):
            def body(_, carry):
                out = _first_out(net(NDArray(carry)))
                probe = jnp.ravel(out)[0].astype(carry.dtype)
                return carry + probe * zero
            return jax.lax.fori_loop(0, steps, body, xd)

    jloop = jax.jit(loop)
    zero = jnp.zeros((), dtype=x.data.dtype)
    with autograd.pause(train_mode=False):
        for _ in range(2):  # compile, then one warm draw off the clock
            r = jloop(x.data, zero, pdatas)
            np.asarray(jax.device_get(r.ravel()[0]))

    def draw():
        """One timed draw; returns elapsed seconds."""
        with autograd.pause(train_mode=False):
            t0 = time.perf_counter()
            r = jloop(x.data, zero, pdatas)
            np.asarray(jax.device_get(r.ravel()[0]))
            return time.perf_counter() - t0
    return draw


def compiled_throughput(net, x, steps=30, draws=5):
    """items/sec of ``net`` forward on batch ``x``, K steps per compiled
    dispatch; returns {median,min,max,draws} over ``draws`` repetitions.

    ``net`` must be callable on an NDArray inside a trace (hybridized
    Gluon blocks are); runs in inference mode (``autograd.pause``).
    """
    batch = x.shape[0]
    one_draw = _compiled_draw(net, x, steps)
    times = [one_draw() for _ in range(draws)]
    return _summarize(times, batch * steps)


def interleaved_throughput(pairs, steps=20, reps=3):
    """A/B measurement immune to chip/session drift: compile each
    (net, x) loop ONCE, then alternate timed draws A,B,A,B,...
    Returns a list of per-pair median items/sec."""
    draws = [_compiled_draw(net, x, steps) for net, x in pairs]
    results = [[] for _ in pairs]
    for _ in range(reps):
        for i, d in enumerate(draws):
            results[i].append(pairs[i][1].shape[0] * steps / d())
    return [float(np.median(r)) for r in results]


def percall_throughput(net, x, steps=30, draws=5):
    """items/sec of the per-dispatch user path: ``steps`` framework-level
    ``net(x)`` calls per draw, chained through a runtime-zero probe so
    identical launches cannot be deduped, ended by a host scalar fetch
    (the real execution barrier — a ready-barrier alone can read
    impossibly fast through a remote runtime)."""
    batch = x.shape[0]
    zero = NDArray(jnp.zeros((1,), dtype=x.data.dtype))
    with autograd.pause(train_mode=False):
        out = net(x)
        if isinstance(out, (list, tuple)):
            out = out[0]
        out.asnumpy()  # compile
        times = []
        for _ in range(draws):
            xi = x
            t0 = time.perf_counter()
            for _ in range(steps):
                out = net(xi)
                if isinstance(out, (list, tuple)):
                    out = out[0]
                xi = xi + out[0, 0] * zero
            float(out[0, 0].asnumpy())
            times.append(time.perf_counter() - t0)
    return _summarize(times, batch * steps)
