"""The whole step's share of the chips' bf16 peak: operations the forward
and backward passes require per item x items/s of the window / (chips x
peak).  Recomputation is not counted."""
from harness import peaks


def read(ctx):
    cell = ctx["cell"]
    if ctx.get("rehearse"):
        return None
    peak = peaks.peaks_for(ctx["devices"][0].device_kind)["bf16_flops"]
    per_item = cell.family.train_flops_per_item(cell.config, cell.traffic)
    return 100.0 * per_item * ctx["rate"] / (len(ctx["devices"]) * peak)
