"""Least time the chip could take for the grouped products of the window's
steps (forward, ``dx`` and ``dW`` of gate, up and down over the *expected*
live rows of every expert layer; each the larger of operations / peak and
bytes / HBM bandwidth, from shapes) / device time of the grouped product's
custom calls.  A step whose routing sends more than the expected share of
slots to the held experts does more than is counted, never less by much:
the run prints the share that landed."""
from harness import peaks


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    pick = getattr(cell.family, "gmm_call_seconds", None)
    if not t or pick is None:
        return None
    seconds = pick(cell.config, t["custom_calls"])
    if not seconds:
        return None
    pk = peaks.peaks_for(ctx["devices"][0].device_kind)
    need = cell.family.gmm_required_per_step(cell.config, cell.traffic, pk)
    least = sum(v["min_s"] for v in need.values()) * ctx["window"]["steps"]
    return 100.0 * least / seconds
