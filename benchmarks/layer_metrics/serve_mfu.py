"""2 x parameters in products x (prompt + output tokens processed in the
window) / window / bf16 peak."""
from harness import peaks


def read(ctx):
    w = ctx["window"]
    if ctx.get("rehearse"):
        return None
    inside = lambda b: w["t_open"] <= b <= w["t_close"]
    tokens = sum(n for _a, b, n in ctx["calls"]["prefill"] if inside(b))
    tokens += sum(len(lens) for _a, b, lens in ctx["calls"]["decode"]
                  if inside(b))
    if not tokens:
        return None
    pk = peaks.peaks_for(ctx["devices"][0].device_kind)
    cell = ctx["cell"]
    flops = cell.family.serve_flops(cell.config, tokens)
    return 100.0 * flops / w["seconds"] / pk["bf16_flops"]
