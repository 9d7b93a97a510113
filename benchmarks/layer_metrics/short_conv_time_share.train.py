"""Device time inside the gated short convolution's custom calls (forward
and backward kernels of every convolution layer) / device busy time.  The
family picks the kernels' calls out of the reduced trace
(``short_conv_call_seconds``: by the taps ``[K, E]`` they take)."""


def read(ctx):
    t = ctx["trace"]
    pick = getattr(ctx["cell"].family, "short_conv_call_seconds", None)
    if not t or not t["busy_s"] or pick is None:
        return None
    seconds = pick(ctx["cell"].config, t["custom_calls"])
    return 100.0 * seconds / t["busy_s"] if seconds else None
