"""1 - union of device-op intervals / traced window."""
from harness import trace_reduce


def read(ctx):
    return trace_reduce.idle_share_percent(ctx["trace"])
