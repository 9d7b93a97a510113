"""Least time the chip could take for the gated short convolutions of the
window's steps (forward and backward of every convolution layer; each the
larger of operations / peak and bytes / HBM bandwidth, from shapes: bound
by the bytes, three planes read and one written forward, four read and
three written backward, at the true width) / device time of the kernels'
custom calls.  A rematerialised layer runs the forward kernel twice; the
second run is not counted, so it cannot pass 100 % by it."""
from harness import peaks


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    pick = getattr(cell.family, "short_conv_call_seconds", None)
    if not t or pick is None:
        return None
    seconds = pick(cell.config, t["custom_calls"])
    if not seconds:
        return None
    pk = peaks.peaks_for(ctx["devices"][0].device_kind)
    need = cell.family.short_conv_required_per_step(cell.config,
                                                    cell.traffic, pk)
    least = sum(v["min_s"] for v in need.values()) * ctx["window"]["steps"]
    return 100.0 * least / seconds
