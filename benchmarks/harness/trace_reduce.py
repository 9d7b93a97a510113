"""From a profiler trace to numbers.  Copied in spirit from
``mxnet_tpu.profiler.hlo_category_breakdown`` and owned here, so that every
PR reduces a trace the same way and none can move the yardstick.

Works on a neutral form of the trace, so a small recorded trace can be
checked in as JSON and the arithmetic tested without a chip::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, {stats}],
                                       ...]}]},
                {"name": "/host:CPU", "lines": [...]}]}
"""
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "engine.")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
CUSTOM_CALL = ("custom-call", "custom_call")


def from_profile_data(profile):
    """``jax.profiler.ProfileData`` -> the neutral form (device op lines and
    the host's benchmark spans only)."""
    planes = []
    for plane in profile.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for e in line.events:
                if not is_dev and not e.name.startswith(SPAN_PREFIXES):
                    continue
                stats = {}
                if is_dev:
                    for k, v in e.stats:
                        if isinstance(v, (str, int, float)):
                            stats[k] = v
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _self_times(events):
    """[(event, self_ns)]: an event's time minus what events nested inside
    it on the same line cover (a ``while`` spans its body's operations)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [ev[2] for ev in events]
    stack = []
    for i in order:
        s, d = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [(events[i], max(self_ns[i], 0.0)) for i in range(len(events))]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def parse_hlo(text):
    """(instance name, opcode, result shapes, operand shapes) of an event
    name.  On the TPU an op's event name is its whole HLO line,
    ``%name = <result shape> opcode(<operands>), attributes``; elsewhere it
    is a bare name, and the opcode and shapes come back empty."""
    if " = " not in text:
        return text.lstrip("%"), "", [], []
    name, rest = text.split(" = ", 1)
    while _LAYOUT.search(rest):
        rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, after = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, after = rest.partition(" ")
    opcode, _, operands = after.partition("(")
    operands = operands.split("), ", 1)[0]
    return (name.lstrip("%"), opcode.strip(), _SHAPE.findall(result),
            _SHAPE.findall(operands))


def _counted(shapes):
    out = []
    for s in shapes:
        if out and out[-1][0] == s:
            out[-1][1] += 1
        else:
            out.append([s, 1])
    return ",".join(s if n == 1 else "%sx%d" % (s, n) for s, n in out)


def op_identity(name, stats):
    """A short name a planning session can read with nothing else at hand.
    A custom call (a Pallas kernel) is named by what the trace tells of it
    — its target and the shapes it takes and gives — without its instance
    number, so the calls of one kernel in every unrolled layer add up; any
    other op keeps its instance name and its opcode (and fusion kind)."""
    inst, opcode, result, operands = parse_hlo(name)
    cat = str(stats.get("hlo_category", "") or "")
    if is_custom_call(name, stats):
        target = _TARGET.search(name)
        kernel = stats.get("kernel") or stats.get("tf_op") or ""
        return "custom-call:%s%s %s<-%s" % (
            target.group(1) if target else (cat or inst),
            "|" + str(kernel)[-64:] if kernel else "",
            _counted(result), _counted(operands))
    kind = _KIND.search(name)
    label = opcode or cat
    if kind:
        label += ":" + kind.group(1)
    return inst + ("|" + label if label else "")


def _what(name, stats):
    _inst, opcode, _r, _o = parse_hlo(name)
    return (opcode or name).lower() + " " + str(
        stats.get("hlo_category", "")).lower()


def is_custom_call(name, stats):
    return any(t in _what(name, stats) for t in CUSTOM_CALL)


def is_collective(name, stats):
    return any(t in _what(name, stats) for t in COLLECTIVES)


def find_window(trace):
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name == WINDOW_SPAN:
                    return start, start + dur
    return None


def host_spans(trace):
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name != WINDOW_SPAN and name.startswith(SPAN_PREFIXES):
                    spans.append((name, start, start + dur))
    return spans


def reduce(trace, top=10):
    """The numbers the per-layer readers and the result line take from a
    traced window.  Seconds throughout.  ``busy_s`` is averaged over the
    device planes; the op table and the gaps are the first device's."""
    devices = sorted((int(DEVICE_PLANE.match(p["name"]).group(1)), p)
                     for p in trace["planes"]
                     if DEVICE_PLANE.match(p["name"]))
    window = find_window(trace)
    all_events = [ev for _, p in devices for ln in p["lines"]
                  if ln["name"] == OPS_LINE for ev in ln["events"]]
    if window is None and all_events:
        window = (min(e[1] for e in all_events),
                  max(e[1] + e[2] for e in all_events))
    if window is None:
        return None
    lo, hi = window
    out = {"window_s": (hi - lo) / 1e9, "devices": len(devices)}
    busy, custom, coll, ops_total = [], [], [], []
    table, pallas = {}, {}
    first_union = None
    for n, (_idx, plane) in enumerate(devices):
        events = [ev for ln in plane["lines"] if ln["name"] == OPS_LINE
                  for ev in ln["events"]]
        if n == 0:
            modules = {}
            for ln in plane["lines"]:
                if ln["name"] != MODULES_LINE:
                    continue
                for name, start, dur, _ in ln["events"]:
                    c = _clip(start, start + dur, lo, hi)
                    if c:
                        modules[name] = modules.get(name, 0.0) \
                            + (c[1] - c[0]) / 1e9
            out["modules"] = modules
        clipped = []
        for ev in events:
            c = _clip(ev[1], ev[1] + ev[2], lo, hi)
            if c:
                clipped.append([ev[0], c[0], c[1] - c[0], ev[3]])
        union = _union((e[1], e[1] + e[2]) for e in clipped)
        if n == 0:
            first_union = union
        busy.append(sum(e - s for s, e in union) / 1e9)
        cc = co = tot = 0.0
        for ev, self_ns in _self_times(clipped):
            name, _s, _d, st = ev
            tot += self_ns
            if is_custom_call(name, st):
                cc += self_ns
                if n == 0:
                    key = op_identity(name, st)
                    pallas[key] = pallas.get(key, 0.0) + self_ns / 1e9
            if is_collective(name, st):
                co += self_ns
            if n == 0:
                key = op_identity(name, st)
                table[key] = table.get(key, 0.0) + self_ns / 1e9
        custom.append(cc / 1e9)
        coll.append(co / 1e9)
        ops_total.append(tot / 1e9)
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
    out["busy_s"] = mean(busy)
    out["custom_call_s"] = mean(custom)
    out["collective_s"] = mean(coll)
    out["ops_s"] = mean(ops_total)
    out["device_ops"] = sorted(table.items(), key=lambda kv: -kv[1])[:top]
    out["custom_calls"] = sorted(pallas.items(), key=lambda kv: -kv[1])
    out["idle_gaps"] = _gaps(first_union or [], lo, hi, host_spans(trace),
                             top)
    return out


def idle_share_percent(reduced):
    """1 - union of device-op intervals / traced window, in percent; None
    where no operation ran (a reader never reports an empty trace as idle)."""
    if not reduced or not reduced["window_s"] or not reduced["busy_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def _gaps(union, lo, hi, spans, top):
    """Idle time of the first device by what the host was doing: each gap
    goes to the shortest benchmark span that covers its middle."""
    gaps, at = [], lo
    for s, e in union:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    by = {}
    for s, e in gaps:
        mid = (s + e) / 2
        best = None
        for name, a, b in spans:
            if a <= mid <= b and (best is None or b - a < best[1]):
                best = (name, b - a)
        key = best[0] if best else "host(unattributed)"
        by[key] = by.get(key, 0.0) + (e - s) / 1e9
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]
