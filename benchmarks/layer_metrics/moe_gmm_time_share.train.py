"""Device time inside the grouped product's custom calls (forward, ``dx``
and ``dW`` of the routed experts' gate, up and down matrices in every expert
layer) / device busy time.  The family picks the grouped product's calls
out of the reduced trace (``gmm_call_seconds``)."""


def read(ctx):
    t = ctx["trace"]
    pick = getattr(ctx["cell"].family, "gmm_call_seconds", None)
    if not t or not t["busy_s"] or pick is None:
        return None
    seconds = pick(ctx["cell"].config, t["custom_calls"])
    return 100.0 * seconds / t["busy_s"] if seconds else None
