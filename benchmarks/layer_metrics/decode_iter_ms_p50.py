"""Median host time of one ``engine.decode`` call (it ends in a fence: the
logits come to the host), from the benchmark's span around it."""
from harness import stats


def read(ctx):
    w = ctx["window"]
    xs = [b - a for a, b, _ in ctx["calls"]["decode"]
          if w["t_open"] <= b <= w["t_close"]]
    return 1e3 * stats.median(xs) if xs else None
