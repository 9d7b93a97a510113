"""Device time inside the selective scan's custom calls (forward and
backward kernels of every Mamba layer) / device busy time.  The family
picks the scan's calls out of the reduced trace (``scan_call_seconds``)."""


def read(ctx):
    t = ctx["trace"]
    pick = getattr(ctx["cell"].family, "scan_call_seconds", None)
    if not t or not t["busy_s"] or pick is None:
        return None
    seconds = pick(ctx["cell"].config, t["custom_calls"])
    return 100.0 * seconds / t["busy_s"] if seconds else None
