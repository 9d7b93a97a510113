"""Median time between completions of consecutive steps.  Stands beside the
rate; never replaces it."""
from harness import stats


def read(ctx):
    done = ctx["window"].get("done_at") or []
    gaps = [b - a for a, b in zip(done, done[1:])]
    return 1e3 * stats.median(gaps) if gaps else None
