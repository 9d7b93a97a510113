#!/usr/bin/env python
"""Communication micro-benchmark (reference: ``tools/bandwidth/measure.py``
— measures kvstore push/pull bandwidth across devices/machines for a
range of array sizes).

TPU-native: the comm fabric is the XLA collective stack, so this measures

* host<->device transfer bandwidth (the PCIe analogue), and
* per-axis collective bus bandwidth — ``psum`` / ``all_gather`` /
  ``reduce_scatter`` / ``ppermute`` over every axis of a configurable
  device mesh, swept across message sizes (the NCCL-allreduce analogue;
  on a real pod the mesh axes ride ICI).

Each timed region chains iterations through a data dependency and ends
with a host value fetch (see bench.py, same discipline).

Usage::

    python tools/bandwidth/measure.py [--sizes 1e5,1e6,1e7] [--iters 10]
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python tools/bandwidth/measure.py --mesh 4,2 --axes dp,tp
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402


def _timed(fn, iters):
    fn()  # warmup / compile
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    return dt / iters, out


def _collective_fns(axis, k, iters):
    """name -> (per-device fn applying the collective ``iters`` times
    with a data dependency, bytes-on-the-wire model per element-buffer
    of b bytes)."""
    import jax
    from jax import lax

    def chain(step):
        def run(x):
            for _ in range(iters):
                # the tiny multiply defeats common-subexpression reuse
                # across iterations without touching bandwidth
                x = step(x * 1.000001)
            return x
        return run

    return {
        # ring all-reduce moves 2*(k-1)/k of the buffer per device
        "psum": (chain(lambda x: lax.psum(x, axis)),
                 lambda b: 2.0 * (k - 1) / k * b),
        # each device receives the other k-1 shards
        "all_gather": (chain(lambda x: lax.all_gather(
            x, axis, tiled=True)[: x.shape[0]]),
            lambda b: (k - 1.0) / k * b * k),
        "reduce_scatter": (chain(lambda x: jax.numpy.tile(
            lax.psum_scatter(x, axis, tiled=True), k)),
            lambda b: (k - 1.0) / k * b),
        # neighbor exchange: the full buffer crosses one link
        "ppermute": (chain(lambda x: lax.ppermute(
            x, axis, [(i, (i + 1) % k) for i in range(k)])),
            lambda b: 1.0 * b),
    }


def _host_device_rows(sizes, iters):
    import jax

    dev = jax.devices()[0]
    print("%12s %14s %14s" % ("size(MB)", "h2d(GB/s)", "d2h(GB/s)"))
    for n in sizes:
        host = np.random.RandomState(0).rand(n).astype(np.float32)

        def h2d_n():
            for _ in range(iters):
                arr = jax.device_put(host, dev)
            return arr.block_until_ready()

        t_h2d, dev_arr = _timed(h2d_n, iters)

        def d2h_n():
            for _ in range(iters):
                out = np.asarray(dev_arr)
            return out

        t_d2h, _ = _timed(d2h_n, iters)
        print("%12.2f %14.4g %14.4g" % (
            host.nbytes / 1e6, host.nbytes / t_h2d / 1e9,
            host.nbytes / t_d2h / 1e9))


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel.collectives import shard_map

    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1e5,1e6,1e7",
                    help="comma-separated PER-DEVICE element counts (fp32)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--mesh", default=None,
                    help="mesh shape, e.g. 4,2 (default: all devices, 1D)")
    ap.add_argument("--axes", default=None,
                    help="mesh axis names, e.g. dp,tp")
    ap.add_argument("--collectives",
                    default="psum,all_gather,reduce_scatter,ppermute")
    args = ap.parse_args()
    sizes = [int(float(s)) for s in args.sizes.split(",")]
    wanted = args.collectives.split(",")

    devs = jax.devices()
    print("devices: %d x %s" % (len(devs), devs[0].platform))
    _host_device_rows(sizes, args.iters)

    if args.mesh:
        shape = tuple(int(s) for s in args.mesh.split(","))
    else:
        shape = (len(devs),)
    axes = tuple((args.axes or ",".join(
        ["dp", "tp", "pp", "sp"][: len(shape)])).split(","))
    assert len(axes) == len(shape), "--axes must match --mesh arity"
    n_mesh = int(np.prod(shape))
    if n_mesh > len(devs):
        print("mesh %s needs %d devices, have %d — skipping collectives"
              % (shape, n_mesh, len(devs)))
        return
    mesh = Mesh(np.array(devs[:n_mesh]).reshape(shape), axes)
    print("mesh: %s x %s" % (dict(zip(axes, shape)), "fp32"))

    header = ["axis", "size(MB/dev)"] + ["%s(GB/s)" % c for c in wanted]
    print(" ".join("%14s" % h for h in header))
    for axis, k in zip(axes, shape):
        if k == 1:
            continue
        fns = _collective_fns(axis, k, args.iters)
        for n in sizes:
            host = np.random.RandomState(1).rand(n).astype(np.float32)
            repl = jax.device_put(host, NamedSharding(mesh, P()))
            row = ["%14s" % axis, "%14.2f" % (host.nbytes / 1e6)]
            for cname in wanted:
                step, bytes_model = fns[cname]
                run = jax.jit(shard_map(step, mesh=mesh, in_specs=P(),
                                        out_specs=P(), check_vma=False))

                def once(run=run, repl=repl):
                    out = run(repl)
                    return float(np.asarray(out).ravel()[0])  # value fetch

                dt, _ = _timed(once, args.iters)
                gbs = bytes_model(host.nbytes) / dt / 1e9
                row.append("%14.4g" % gbs)
            print(" ".join(row))


if __name__ == "__main__":
    main()
