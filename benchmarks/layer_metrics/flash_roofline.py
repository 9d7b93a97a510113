"""Least time the chip could take for the flash attention kernels of the
window's steps (forward, dQ and dK/dV of every attention layer, **over the
(query, key) pairs each layer's mask keeps**: the causal triangle on a full
layer, the band alone on a window layer; each the larger of operations /
peak and bytes / HBM bandwidth, from shapes) / device time of those kernels'
custom calls.  Work the kernels skip is not counted, so a kernel that
computed the whole triangle on a window layer would read low, not high."""
from harness import peaks


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    pick = getattr(cell.family, "flash_call_seconds", None)
    if not t or pick is None:
        return None
    seconds = pick(cell.config, cell.traffic, t["custom_calls"])
    if not seconds:
        return None
    pk = peaks.peaks_for(ctx["devices"][0].device_kind)
    need = cell.family.flash_required_per_step(cell.config, cell.traffic, pk)
    least = sum(v["min_s"] for v in need.values()) * ctx["window"]["steps"]
    return 100.0 * least / seconds
