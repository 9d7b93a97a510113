"""Device time inside custom calls (the Pallas kernels) / device busy time."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["busy_s"] or not t["custom_call_s"]:
        return None
    return 100.0 * t["custom_call_s"] / t["busy_s"]
