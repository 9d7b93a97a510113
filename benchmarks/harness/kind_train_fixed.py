"""Traffic kind ``train_fixed``: a training job at a fixed shape.

Set-up builds one trainer (the compiled step with its state), drives it from
the seed through its first three steps — the steps the plain reference
follows — warms it up, and hands that same object to the window.  The window
opens and closes on a fence and keeps ``in_flight`` steps enqueued ahead of
the loss it is fetching, so a host stall shorter than a step costs the
device nothing.  The rate is every item of every step of the window over the
window's whole time.
"""
import collections
import gc
import math
import sys
import time

from . import correct, runtime, tracing


def program_readings(trainer):
    """The trainer's first three steps, through the window's own call."""
    losses = [trainer.fetch(trainer.step(0))]
    grad = trainer.first_grad_norms()
    losses.append(trainer.fetch(trainer.step(1)))
    losses.append(trainer.fetch(trainer.step(2)))
    trainer.fence()
    change = trainer.change_norms()
    return {"loss": losses, "grad": grad, "change": change}


def window(trainer, seconds, first_step, in_flight):
    """Returns a dict of what the window saw.  Fenced at both ends."""
    import jax

    now = time.perf_counter
    pending = collections.deque()
    losses, done_at, enqueue_s = [], [], []
    i = first_step
    trainer.fence()
    t_open = now()
    deadline = t_open + seconds
    while True:
        if now() >= deadline and i > first_step:
            break
        with jax.profiler.TraceAnnotation("bench.step_call"):
            t0 = now()
            pending.append(trainer.step(i))
            enqueue_s.append(now() - t0)
        i += 1
        while len(pending) > in_flight:
            with jax.profiler.TraceAnnotation("bench.fetch_loss"):
                losses.append(trainer.fetch(pending.popleft()))
            done_at.append(now())
    while pending:
        with jax.profiler.TraceAnnotation("bench.fetch_loss"):
            losses.append(trainer.fetch(pending.popleft()))
        done_at.append(now())
    trainer.fence()
    t_close = now()
    return {"t_open": t_open, "t_close": t_close,
            "seconds": t_close - t_open, "steps": i - first_step,
            "losses": losses, "done_at": done_at, "enqueue_s": enqueue_s,
            "items": (i - first_step) * trainer.items_per_step}


def run(cell, args, devices, t_start):
    family = cell.family
    traffic, config = cell.traffic, cell.config
    limits = traffic["limits"]

    if args.readings:
        return _readings_only(cell, args, devices, family)
    correct.refuse_unset(limits)

    compiles = runtime.CompileCounter()
    trainer = family.Trainer(config, traffic, args.seed, devices)
    got = program_readings(trainer)
    for i in range(3, 3 + traffic["warmup_steps"]):
        trainer.fetch(trainer.step(i))
    first = 3 + traffic["warmup_steps"]
    trainer.fence()
    counters0 = family.program_counters()
    runtime.quiet_host()
    compiles_before = compiles.count
    seconds = args.seconds
    tracer = None
    if args.trace:
        seconds = min(seconds, traffic["trace_seconds"])
        tracer = tracing.Tracer(devices)
        tracer.start()
    setup_s = time.perf_counter() - t_start
    win = window(trainer, seconds, first, traffic["in_flight"])
    trace = tracer.stop() if tracer else None
    runtime.unquiet_host()
    recompiles = compiles.count - compiles_before
    counters1 = family.program_counters()
    device = runtime.device_record(devices)
    host_batches = trainer.host_batches
    trainer.free()
    del trainer
    gc.collect()

    finite = all(math.isfinite(v) for v in win["losses"] + got["loss"])
    want = family.train_reference_readings(
        config, traffic, args.seed, devices, host_batches)
    rows = correct.compare_training(got, want, limits)
    rows.append(("nonfinite_losses", 0.0 if finite else 1.0, 0.0))
    rows.append(("compiles_in_window", float(recompiles), 0.0))
    rate = win["items"] / win["seconds"]
    ctx = {"cell": cell, "window": win, "trace": trace, "rate": rate,
           "recompiles": recompiles, "devices": devices,
           "counters": {k: counters1[k] - counters0.get(k, 0)
                        for k in counters1},
           "rehearse": args.rehearse}
    runtime.say("steps %d items %d window %.4fs setup %.2fs losses %s"
                % (win["steps"], win["items"], win["seconds"], setup_s,
                   ["%.5f" % v for v in got["loss"]]))
    correct.print_worst_leaves(got, want, sys.stderr)
    correct.print_rows(rows, sys.stderr)
    return tracing.result(
        cell, args, ctx, device,
        {"train_items_per_s": rate, "setup_s": setup_s}, rows,
        attempted=win["steps"],
        failed=sum(1 for v in win["losses"] if not math.isfinite(v)))


def _readings_only(cell, args, devices, family):
    """Not a benchmark run: the reference in a lower precision or with a
    planted fault stands in the program's place.  ``--readings`` may list
    several (``control,half_batch``): the reference is followed once."""
    traffic, config = cell.traffic, cell.config
    host_batches = family.host_batches(config, traffic, args.seed)
    want = family.train_reference_readings(
        config, traffic, args.seed, devices, host_batches)
    out = {"readings": args.readings, "correct": True, "compared": {}}
    for what in args.readings.split(","):
        kw = {"operand": config["precision"]["control"]} \
            if what == "control" else {"fault": what}
        got = family.train_reference_readings(
            config, traffic, args.seed, devices, host_batches, **kw)
        rows = correct.compare_training(got, want, traffic["limits"])
        runtime.say("readings of %s" % what)
        correct.print_worst_leaves(got, want, sys.stderr)
        correct.print_rows(rows, sys.stderr)
        out["correct"] = out["correct"] and correct.verdict(rows)
        out["compared"][what] = correct.rows_as_dict(rows)
    if "," not in args.readings:
        out["compared"] = out["compared"][args.readings]
    out["device"] = runtime.device_record(devices)
    return out
