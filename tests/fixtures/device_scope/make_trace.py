"""python make_trace.py: writes trace.neutral.json beside step.hlo.txt, the
neutral form of a device trace of two steps of that module (times in ns;
tests/test_device_scopes.py says what each part is there for)."""
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
line = {}
with open(os.path.join(HERE, "step.hlo.txt")) as f:
    for text in f:
        m = re.match(r"^\s+(?:ROOT )?(%\S+) = (.*?)(?:, metadata=\{.*)?$",
                     text.rstrip("\n"))
        if m:       # on the TPU an event is named by its line, no metadata
            line[m.group(1).lstrip("%")] = "%s = %s" % m.groups()
ops, mods = [], []


def step(t0):
    t = t0

    def ev(name, dur, at=None):
        nonlocal t
        start = t if at is None else at
        ops.append([line.get(name, name), float(start), float(dur), {}])
        if at is None:
            t = start + dur

    ev("copy.7", 20)
    ev("fusion.1", 400)
    ev("fusion.2", 300)
    ev("custom-call.3", 500)
    t += 200                            # idle: the host was dispatching
    loop = t
    ev("while.1", 1000)
    ev("fusion.10", 400, at=loop + 50)
    ev("custom-call.5", 500, at=loop + 470)
    ev("fusion.3", 200)
    ev("fusion.4", 150)
    ev("%fusion.999 = f32[8]{0} fusion(%p), kind=kLoop, "
       "calls=%fused_computation.999", 50)
    mods.append(["jit_step(9512014523541288649)", float(t0), float(t - t0),
                 {}])
    return t


end1 = step(1000)
# another program between the steps, with an instance name the step has too
mods.append(["jit_other(77)", float(end1 + 300), 100.0, {}])
ops.append(["%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, "
            "calls=%fused_computation", float(end1 + 300), 100.0, {}])
start2 = end1 + 1400
end2 = step(start2)
host = [["engine.sync", 500.0, float(end2), {}],
        ["engine.fused.dispatch", 2170.0, 300.0, {}],
        ["engine.fused.dispatch", float(start2 + 1170), 300.0, {}],
        ["python.noise", 0.0, 10.0, {}]]
trace = {"planes": [
    {"name": "/device:TPU:0",
     "lines": [{"name": "XLA Modules", "events": mods},
               {"name": "XLA Ops", "events": ops}]},
    {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}
with open(os.path.join(HERE, "trace.neutral.json"), "w") as f:
    json.dump(trace, f, indent=0)
