"""Least time the chip could take for the work of the Pallas kernels on the
step's path (flash forward and backward, rmsnorm, softmax cross-entropy;
each the larger of operations / peak and bytes / HBM bandwidth, from shapes)
/ device time of the custom calls, over the steps of the traced window."""
from harness import peaks


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    if not t or not t["custom_call_s"]:
        return None
    pk = peaks.peaks_for(ctx["devices"][0].device_kind)
    need = cell.family.kernels_required_per_step(cell.config, cell.traffic,
                                                 pk)
    least = sum(v["min_s"] for v in need.values()) * ctx["window"]["steps"]
    # the steps of the window over the traced window's device time
    return 100.0 * least / t["custom_call_s"]
