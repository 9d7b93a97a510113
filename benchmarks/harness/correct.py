"""The comparison that decides ``correct``: each number compared, its limit,
and the verdict.  Limits live in data (a traffic file's ``limits``), set from
readings on the chip as PERF.md records; the arithmetic lives here.
"""
import math
import statistics


def refuse_unset(limits):
    """A limit that is ``null`` was never read on the chip: such a cell is
    not run as a benchmark (``--readings`` is how its limits are read)."""
    unset = sorted(k for k, v in limits.items() if v is None)
    if unset:
        raise SystemExit("benchmark: the traffic file's limits %s were never "
                         "set from readings; see --readings" % unset)


def rel_gap(got, want):
    return abs(got - want) / max(abs(want), 1e-30)


def nought_gradient_leaves(ref_grad_norms, share=1e-3):
    """Leaves whose reference gradient is under ``share`` of the median
    leaf's: nought to rounding (a bias before a batch norm), moved by
    round-off alone, so left out of the parameters' change."""
    median = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < share * median}


def leaf_gaps(got, want, skip=()):
    """{leaf: |‖got‖ - ‖want‖| / max(‖want‖ of the leaf, ‖want‖ of the median
    leaf)} over the leaves kept: the gap between two norms, not the norm of
    a difference, measured against the larger of the leaf's own and the
    median leaf's reference norm since some leaves are all but zero."""
    names = [k for k in sorted(want) if k not in skip]
    if not names:
        return {}
    median = statistics.median(want[k] for k in names)
    return {k: abs(got[k] - want[k]) / max(want[k], median, 1e-30)
            for k in names}


def compare_training(program, reference, limits):
    """``program`` and ``reference``: {"loss": [3 floats], "grad": {leaf:
    norm}, "change": {leaf: norm}}.  Returns [(name, value, limit)]; a
    number whose key ``limits`` lacks is printed and not compared."""
    rows = []
    for i, (got, want) in enumerate(zip(program["loss"], reference["loss"]),
                                    start=1):
        rows.append(("loss%d_rel" % i, rel_gap(got, want),
                     limits.get("loss_rel")))
    # leaves whose gradient is nought to rounding in the reference (a bias
    # before a batch norm) carry round-off alone on the program's side:
    # left out of every number below, by the rule on the reference's gradient
    skip = nought_gradient_leaves(reference["grad"])
    for what, name, key in (("grad", "grad1_norm_gap", "grad_norm_gap"),
                            ("change", "change3_norm_gap",
                             "change_norm_gap")):
        gaps = leaf_gaps(program[what], reference[what], skip)
        values = list(gaps.values())
        bad = [v for v in values if not math.isfinite(v)]
        worst = float("inf") if bad else max(values, default=0.0)
        mid = float("inf") if bad else (statistics.median(values)
                                        if values else 0.0)
        rows.append((name, worst, limits.get(key)))
        rows.append((name + "_median_leaf", mid,
                     limits.get(key + "_median_leaf")))
    return rows


def verdict(rows, extra_ok=True):
    """True where every number is finite and within its limit.  A limit of
    None marks a number that is printed but not compared."""
    ok = bool(extra_ok)
    for _name, value, limit in rows:
        if limit is None:
            continue
        if not (math.isfinite(value) and value <= limit):
            ok = False
    return ok


def rows_as_dict(rows):
    return {name: {"value": value, "limit": limit}
            for name, value, limit in rows}


def print_worst_leaves(program, reference, stream):
    skip = nought_gradient_leaves(reference["grad"])
    for what in ("grad", "change"):
        got, want = program[what], reference[what]
        gaps = leaf_gaps(got, want, skip)
        for leaf in sorted(gaps, key=gaps.get, reverse=True)[:4]:
            print("[bench] %s leaf %s gap %.4g program %.6g reference %.6g"
                  % (what, leaf, gaps[leaf], got[leaf], want[leaf]),
                  file=stream, flush=True)
    print("[bench] %d leaves left out (reference gradient "
          "under a thousandth of the median leaf's)" % len(skip),
          file=stream, flush=True)


def print_rows(rows, stream):
    for name, value, limit in rows:
        print("compared %s %.6g limit %s" % (
            name, value, "none" if limit is None else "%.6g" % limit),
              file=stream, flush=True)
