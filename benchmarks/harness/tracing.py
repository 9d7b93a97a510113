"""Taking a profiler trace of a window and reading the per-layer metrics."""
import glob
import os
import shutil
import time

from . import cells, runtime, trace_reduce


class Tracer:
    def __init__(self, devices):
        self.dir = os.path.join(cells.ROOT, ".bench_trace")
        self.devices = devices
        self._span = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        """Ends the trace and reduces it; the files are removed."""
        import jax
        from jax.profiler import ProfileData

        self._span.__exit__(None, None, None)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        trace = trace_reduce.from_profile_data(
            ProfileData.from_file(paths[-1]))
        shutil.rmtree(self.dir, ignore_errors=True)
        red = trace_reduce.reduce(trace)
        if red is None:
            red = {"window_s": 0.0, "busy_s": 0.0, "device_ops": [],
                   "idle_gaps": [], "custom_calls": [], "custom_call_s": 0.0,
                   "collective_s": 0.0, "ops_s": 0.0, "devices": 0}
        red["breakdown"] = {
            "device_ops": [[k, v] for k, v in red["device_ops"]],
            "idle_gaps": [[k, v] for k, v in red["idle_gaps"]]}
        runtime.say("trace reduced in %.1fs: window %.3fs busy %.3fs "
                    "custom-call %.3fs collective %.3fs"
                    % (time.perf_counter() - t0, red["window_s"],
                       red["busy_s"], red["custom_call_s"],
                       red["collective_s"]))
        for k, v in red["custom_calls"][:12]:
            runtime.say("custom call %.4fs %s" % (v, k))
        return red


def read_per_layer(cell, ctx):
    """Every per-layer metric of the cell whose reader finds something to
    read; a reader that finds nothing returns None and is left out."""
    metrics = {}
    for m in cell.per_layer:
        value = cells.load_reader(m["name"])(ctx)
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def result(cell, args, ctx, device, end_to_end, rows, attempted, failed):
    """The run's last line.  A measured run reports the cell's end-to-end
    metrics (``end_to_end``: {name: value}), a traced run its per-layer
    metrics with the device's busy time and the breakdown, a rehearsal
    neither; the numbers compared come last."""
    from . import correct

    trace, metrics = ctx["trace"], {}
    if args.rehearse:
        pass
    elif args.trace:
        metrics = read_per_layer(cell, ctx)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {name: {"value": value, "unit": units[name]}
                   for name, value in end_to_end.items()}
    out = {"correct": correct.verdict(rows), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace and not args.rehearse:
        out["breakdown"] = trace["breakdown"]
    out["compared"] = correct.rows_as_dict(rows)
    return out
