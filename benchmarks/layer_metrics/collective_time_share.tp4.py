"""Device time in all-reduce / all-gather / reduce-scatter /
collective-permute operations / device busy time, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["busy_s"] or not t["collective_s"]:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
