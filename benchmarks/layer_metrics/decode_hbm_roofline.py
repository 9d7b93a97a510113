"""Bytes the window's decode iterations need (the weights once each, the
live keys and values of the active sequences) / HBM bandwidth / device time
of the decode programs.  Reads the same work whatever implements it."""
from harness import peaks


def read(ctx):
    t, w = ctx["trace"], ctx["window"]
    if not t or not t.get("modules"):
        return None
    dev_s = sum(s for name, s in t["modules"].items() if "decode" in name)
    turns = [lens for _a, b, lens in ctx["calls"]["decode"]
             if w["t_open"] <= b <= w["t_close"]]
    if not dev_s or not turns:
        return None
    pk = peaks.peaks_for(ctx["devices"][0].device_kind)
    cell = ctx["cell"]
    need = sum(cell.family.decode_required_bytes(cell.config, lens)
               for lens in turns)
    return 100.0 * need / pk["hbm_bytes_per_s"] / dev_s
