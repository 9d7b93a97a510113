"""Small statistics, kept here so every PR computes them the same way."""
import math


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks, as numpy's default; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values):
    return percentile(values, 50.0)


def token_gaps(token_times):
    """Gaps between consecutive tokens of one request."""
    return [b - a for a, b in zip(token_times, token_times[1:])]
