"""Profiler tests (reference: tests/python/unittest/test_profiler.py —
configure, run spans, dump chrome-trace JSON, aggregate stats)."""
import collections
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from conftest import subprocess_env
from mxnet_tpu import profiler


def _load(path):
    with open(path) as f:
        return json.load(f)["traceEvents"]


def test_profiler_operator_spans(tmp_path):
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname, profile_all=True,
                        aggregate_stats=True)
    profiler.start()
    a = mx.nd.ones((8, 8))
    b = mx.nd.dot(a, a)
    c = mx.nd.relu(b)
    c.wait_to_read()
    profiler.stop()
    profiler.dump()
    names = {e["name"] for e in _load(fname) if e.get("cat") == "operator"}
    assert any("dot" in n for n in names), names
    assert any("relu" in n.lower() for n in names), names
    table = profiler.dumps()
    assert "dot" in table and "Total(ms)" in table
    # paused region records nothing
    n0 = len(_load(fname))
    profiler.start()
    profiler.pause()
    mx.nd.ones((4,)).wait_to_read()
    profiler.resume()
    profiler.stop()
    profiler.dump()
    assert all(e["ts"] is not None for e in _load(fname))


def test_profiler_module_fit(tmp_path):
    fname = str(tmp_path / "fit.json")
    profiler.set_config(filename=fname, profile_all=True)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    X = np.random.randn(32, 8).astype("float32")
    Y = np.random.randint(0, 4, (32,)).astype("float32")
    it = mx.io.NDArrayIter(X, Y, batch_size=16)
    mod = mx.mod.Module(net, data_names=["data"],
                        label_names=["softmax_label"], context=mx.cpu())
    profiler.start()
    mod.fit(it, num_epoch=1,
            optimizer_params={"learning_rate": 0.1})
    profiler.stop()
    profiler.dump()
    evts = _load(fname)
    cats = {e.get("cat") for e in evts}
    assert "symbolic" in cats, cats  # Executor spans
    names = {e["name"] for e in evts}
    # fit uses the fused fwd+bwd step, so the backward span carries it
    assert "Executor::backward" in names, names


def test_profiler_objects(tmp_path):
    fname = str(tmp_path / "obj.json")
    profiler.set_config(filename=fname)
    profiler.start()
    dom = profiler.ProfileDomain("mydomain")
    with profiler.Task(dom, "work"):
        pass
    frame = profiler.Frame(dom, "iter")
    for _ in range(3):
        with frame:
            pass
    cnt = profiler.Counter(dom, "samples", 0)
    cnt += 5
    cnt -= 2
    profiler.Marker(dom, "tick").mark()
    profiler.stop()
    profiler.dump()
    evts = _load(fname)
    names = [e["name"] for e in evts]
    assert "mydomain::work" in names
    assert names.count("mydomain::iter") == 3
    counters = [e for e in evts if e.get("ph") == "C"]
    assert counters and counters[-1]["args"]["value"] == 3
    assert any(e.get("ph") == "i" for e in evts)


def test_profiler_objects_gated_when_stopped(tmp_path):
    """Task/Counter/Marker must not record while the profiler is stopped
    (library code may be permanently instrumented)."""
    from mxnet_tpu.profiler import _events
    fname = str(tmp_path / "gated.json")
    profiler.set_config(filename=fname)
    assert profiler.state() == "stop"
    n0 = len(_events)
    dom = profiler.ProfileDomain("idle")
    with profiler.Task(dom, "t"):
        pass
    profiler.Counter(dom, "c", 1).increment()
    profiler.Marker(dom, "m").mark()
    assert len(_events) == n0


def test_profiler_dump_drains_buffer(tmp_path):
    fname = str(tmp_path / "drain.json")
    profiler.set_config(filename=fname)
    profiler.start()
    mx.nd.relu(mx.nd.ones((2, 2))).wait_to_read()
    profiler.stop()
    profiler.dump()
    n1 = len(_load(fname))
    assert n1 > 0
    profiler.dump()  # second dump: buffer drained, no stale history
    assert len(_load(fname)) == 0


def test_profiler_unknown_option():
    import pytest
    with pytest.raises(ValueError):
        profiler.set_config(bogus_option=1)


# -- layer spans: always recorded, in a bounded ring on perf_counter() ------
@pytest.fixture
def small_ring(monkeypatch):
    """A ring of four in the real one's place, and the lost mark as new."""
    ring = collections.deque(maxlen=4)
    monkeypatch.setattr(profiler, "_spans", ring)
    monkeypatch.setattr(profiler, "_spans_lost", None)
    return ring


def test_span_is_recorded_with_no_session_and_no_trace():
    assert profiler.state() == "stop"
    t0 = time.perf_counter()
    with profiler.span("engine.fused.gather", batch=7):
        pass
    t1 = time.perf_counter()
    (got,) = profiler.spans(t0, t1, "engine.fused.gather")
    name, start, end, thread, parent, args = got
    assert name == "engine.fused.gather" and t0 <= start <= end <= t1
    assert thread == threading.get_ident()
    assert parent is None and args == {"batch": 7}


def test_span_ring_is_bounded_and_drops_the_oldest(small_ring):
    dropped = mx.telemetry.registry().counter("profiler.events_dropped")
    before = dropped.value
    assert profiler.spans_lost_before() is None
    for i in range(6):
        with profiler.span("engine.sync", i=i):
            pass
    assert len(small_ring) == 4
    assert [s[5]["i"] for s in profiler.spans()] == [2, 3, 4, 5]
    assert dropped.value - before == 2
    # the start of the newest one lost: what began before it is a part only
    lost = profiler.spans_lost_before()
    assert lost is not None and lost < profiler.spans()[0][1]
    assert profiler.SPAN_RING >= 65536


def test_span_parent_and_thread_under_nesting_and_across_threads():
    t0 = time.perf_counter()
    seen = {}

    def other():
        with profiler.span("engine.gen.turn"):
            with profiler.span("engine.gen.decode"):
                seen["ident"] = threading.get_ident()

    with profiler.span("engine.fused.step"):
        worker = threading.Thread(target=other)
        worker.start()
        worker.join()
        with profiler.span("engine.fused.dispatch"):
            with profiler.span("engine.jit.execute:toy"):
                pass
    got = {s[0]: s for s in profiler.spans(t0, None, "engine.")}
    step = got["engine.fused.step"]
    dispatch = got["engine.fused.dispatch"]
    assert step[4] is None
    assert dispatch[4] == (step[0], step[1])
    assert got["engine.jit.execute:toy"][4] == (dispatch[0], dispatch[1])
    # the other thread's spans do not hang under this thread's open span
    turn = got["engine.gen.turn"]
    assert turn[3] == seen["ident"] != step[3]
    assert turn[4] is None
    assert got["engine.gen.decode"][4] == (turn[0], turn[1])
    # oldest first, a parent before its children
    order = [s[0] for s in profiler.spans(t0, None, "engine.")]
    assert order.index("engine.fused.step") < order.index(
        "engine.fused.dispatch") < order.index("engine.jit.execute:toy")


def test_spans_selects_by_interval_and_prefix(small_ring):
    profiler.record_interval("engine.compile.trace:f", 1.0, 2.0)
    profiler.record_interval("engine.compile.backend:f", 2.0, 3.5, hit=1)
    profiler.record_interval("engine.fused.step", 3.0, 4.0)
    names = lambda *a: [s[0] for s in profiler.spans(*a)]  # noqa: E731
    assert names() == ["engine.compile.trace:f", "engine.compile.backend:f",
                       "engine.fused.step"]
    assert names(None, None, "engine.compile.") == [
        "engine.compile.trace:f", "engine.compile.backend:f"]
    # wholly inside: one that straddles an end is left out
    assert names(None, 3.2) == ["engine.compile.trace:f"]
    assert names(1.5, None) == ["engine.compile.backend:f",
                                "engine.fused.step"]
    assert names(2.0, 3.5) == ["engine.compile.backend:f"]
    assert profiler.spans(2.0, 3.5)[0][5] == {"hit": 1}


def test_span_enters_a_trace_annotation_of_the_same_name(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("exit " + self.name)

    monkeypatch.setattr(profiler, "_annotation", Annotation)
    with profiler.span("engine.sync"):
        assert entered == ["engine.sync"]
    assert entered == ["engine.sync", "exit engine.sync"]


def test_a_session_still_gets_the_chrome_event_of_a_span(tmp_path):
    fname = str(tmp_path / "spans.json")
    profiler.set_config(filename=fname, profile_all=True)
    profiler.start()
    try:
        with profiler.span("engine.gen.decode", active=3):
            pass
        profiler.record_interval("engine.compile.lower:f",
                                 time.perf_counter() - 0.25,
                                 time.perf_counter())
    finally:
        profiler.stop()
        profiler.dump()
    evts = {e["name"]: e for e in _load(fname) if e.get("cat") == "engine"}
    assert evts["engine.gen.decode"]["ph"] == "X"
    assert evts["engine.gen.decode"]["args"] == {"active": 3}
    assert 0.2e6 < evts["engine.compile.lower:f"]["dur"] < 0.4e6
    # and outside a session a span writes no chrome event
    n = len(profiler.recent_events(10**6))
    with profiler.span("engine.sync"):
        pass
    assert len(profiler.recent_events(10**6)) == n


def test_op_spans_stay_gated_on_the_session_and_off_the_span_ring():
    assert profiler.op_span("dot") is profiler._NULL
    t0 = time.perf_counter()
    profiler.start()
    try:
        sp = profiler.op_span("dot")
        assert isinstance(sp, profiler._Span) and not sp.ring
        with sp:
            pass
    finally:
        profiler.stop()
        profiler.dump()
    assert profiler.spans(t0) == []


def test_a_dropped_span_leaves_nothing_and_keeps_the_stack_right():
    t0 = time.perf_counter()
    with profiler.span("engine.gen.turn") as turn:
        with profiler.span("engine.gen.admit") as admit:
            admit.drop()
        turn.drop()
    with profiler.span("engine.gen.turn"):
        pass
    (kept,) = profiler.spans(t0, None, "engine.gen.")
    assert kept[0] == "engine.gen.turn" and kept[4] is None


def test_the_import_is_a_span():
    """In a process of its own: in this one the ring may have turned over
    since the import (which files a worker ran before is xdist's choice)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import mxnet_tpu\n"
         "from mxnet_tpu import profiler\n"
         "(imp,) = profiler.spans(prefix='engine.import')\n"
         "assert imp[0] == 'engine.import' and imp[2] > imp[1], imp\n"
         "assert profiler.spans_lost_before() is None\n"],
        env=subprocess_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr


def test_debug_bundle_holds_the_last_spans(tmp_path):
    from mxnet_tpu import debug

    with profiler.span("engine.sync", why="bundle"):
        pass
    spans = debug._collect("test", None, None)["spans"]
    assert spans and spans[-1][0] == "engine.sync"
    assert spans[-1][5] == {"why": "bundle"}
    json.dumps(spans)


@pytest.mark.parametrize("n, held", [(0, 0), (-3, 0), (2, 2)])
def test_debug_bundle_holds_no_more_spans_than_asked_for(monkeypatch, n, held):
    """0 is "embed none", not ``[-0:]``'s whole ring; the newest are kept."""
    from mxnet_tpu import debug

    for i in range(3):
        with profiler.span("engine.sync", i=i):
            pass
    monkeypatch.setenv("MXTPU_DEBUG_BUNDLE_EVENTS", str(n))
    spans = debug._collect("test", None, None)["spans"]
    assert len(spans) == held
    assert [s[5]["i"] for s in spans] == [1, 2][:held]
    assert profiler.spans(last=n) == spans


def test_every_span_the_package_opens_is_named_in_SPANS():
    """Grep the package: a literal name given to span()/record_interval()
    must be in profiler.SPANS (less its ':<label>') and start with 'engine.',
    or the benchmark's trace reduction would drop it and a reader asking
    for it by name would find nothing."""
    root = os.path.dirname(os.path.abspath(mx.__file__))
    call = re.compile(
        r"(?:\bspan|\brecord_interval)\(\s*[\"']([^\"']+)[\"']")
    opened = set()
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(dirpath, f)) as fh:
                src = fh.read()
            for name in call.findall(src):
                opened.add(name.split(":", 1)[0].split("%", 1)[0])
    # names built at run time in dispatch.py (listener table, TrackedJit)
    from mxnet_tpu import dispatch

    opened |= set(dispatch._COMPILE_SPANS.values())
    opened.add(dispatch.TrackedJit(lambda x: x, label="t")._span_name
               .split(":", 1)[0])
    opened.discard("")                          # the "%s:%s" they build
    assert opened, "the grep found no span at all"
    assert all(n.startswith("engine.") for n in opened), sorted(opened)
    assert opened <= set(profiler.SPANS), sorted(opened - set(profiler.SPANS))
    assert set(profiler.SPANS) <= opened, \
        "named in SPANS, opened nowhere: %s" % sorted(
            set(profiler.SPANS) - opened)
