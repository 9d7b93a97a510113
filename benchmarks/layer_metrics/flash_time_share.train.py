"""Device time inside the flash attention kernels' custom calls (forward,
dQ and dK/dV of every attention layer, over the whole causal prefix or a
window of it) / device busy time.  The family picks the kernels' calls out
of the reduced trace (``flash_call_seconds``: by the operand it states, q
heads first)."""


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    pick = getattr(cell.family, "flash_call_seconds", None)
    if not t or not t["busy_s"] or pick is None:
        return None
    seconds = pick(cell.config, cell.traffic, t["custom_calls"])
    return 100.0 * seconds / t["busy_s"] if seconds else None
