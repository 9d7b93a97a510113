"""Least time the chip could take for the selective scans of the window's
steps (forward and backward of every Mamba layer; each the larger of
operations / peak and bytes / HBM bandwidth, from shapes) / device time of
the scan's custom calls.  By the two published peaks the scan is
bandwidth-bound; what bounds it in truth is the vector and transcendental
units, which have no published peak: a yardstick that cannot pass 100 %,
not a target."""
from harness import peaks


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    pick = getattr(cell.family, "scan_call_seconds", None)
    if not t or pick is None:
        return None
    seconds = pick(cell.config, t["custom_calls"])
    if not seconds:
        return None
    pk = peaks.peaks_for(ctx["devices"][0].device_kind)
    need = cell.family.scan_required_per_step(cell.config, cell.traffic, pk)
    least = sum(v["min_s"] for v in need.values()) * ctx["window"]["steps"]
    return 100.0 * least / seconds
