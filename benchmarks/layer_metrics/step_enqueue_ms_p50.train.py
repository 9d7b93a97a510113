"""Median host time of the step call, before any fence (Dispatch layer)."""
from harness import stats


def read(ctx):
    xs = ctx["window"].get("enqueue_s")
    return 1e3 * stats.median(xs) if xs else None
