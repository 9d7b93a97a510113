"""Mean active sequences / ``max_slots`` over the window's decode turns."""


def read(ctx):
    w = ctx["window"]
    xs = [len(lens) for _a, b, lens in ctx["calls"]["decode"]
          if w["t_open"] <= b <= w["t_close"]]
    return 100.0 * sum(xs) / (len(xs) * ctx["max_slots"]) if xs else None
