"""Least time the chip could take for the window layers' flash attention
kernels of the window's steps (forward, dQ and dK/dV of every layer that
attends over a band, **over the (query, key) pairs the band keeps**; each
the larger of operations / peak and bytes / HBM bandwidth, from shapes) /
device time of those kernels' custom calls.  A band narrower than a
kernel's tile leaves most of each tile masked: that work is not counted,
so the share reads low for it, never over 100 %."""
from harness import peaks


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    pick = getattr(cell.family, "flash_band_call_seconds", None)
    if not t or pick is None:
        return None
    seconds = pick(cell.config, cell.traffic, t["custom_calls"])
    if not seconds:
        return None
    pk = peaks.peaks_for(ctx["devices"][0].device_kind)
    need = cell.family.flash_band_required_per_step(cell.config,
                                                    cell.traffic, pk)
    least = sum(v["min_s"] for v in need.values()) * ctx["window"]["steps"]
    return 100.0 * least / seconds
