"""Device time inside the window layers' flash attention kernels' custom
calls (forward, dQ and dK/dV of every layer that attends over a band) /
device busy time.  The family picks those calls out of the reduced trace
(``flash_band_call_seconds``: by the ``q`` operand only the window layers
take, their head count, q heads first); a family without such a hook, or a
trace without such calls, reads nothing."""


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    pick = getattr(cell.family, "flash_band_call_seconds", None)
    if not t or not t["busy_s"] or pick is None:
        return None
    seconds = pick(cell.config, cell.traffic, t["custom_calls"])
    return 100.0 * seconds / t["busy_s"] if seconds else None
