"""The trace reduction on a small recorded trace checked in beside it."""
import json
import os

import pytest

import bench_paths  # noqa: F401  (puts the benchmark on the path)
from harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def red():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return trace_reduce.reduce(json.load(f))


def test_window_is_the_bench_window_span(red):
    assert red["window_s"] == pytest.approx(12000e-9)
    assert red["devices"] == 2


def test_busy_is_the_union_clipped_to_the_window_and_averaged(red):
    # device 0: [1000,7000] + [9000,9500] + [11500,12000] = 7000 ns
    # device 1: [1000,5000] + [6000,8000] = 6000 ns
    assert red["busy_s"] == pytest.approx(6500e-9)


def test_self_time_takes_nested_operations_out_of_their_parent(red):
    table = dict(red["device_ops"])
    assert table["while.2|while"] == pytest.approx((4000 - 1500 - 1000) * 1e-9)
    # every op's self time adds up to the union where nothing else overlaps
    assert red["ops_s"] == pytest.approx((7000e-9 + 6000e-9) / 2)


def test_custom_call_and_collective_shares(red):
    assert red["custom_call_s"] == pytest.approx(1500e-9 / 2)
    assert red["collective_s"] == pytest.approx((1000e-9 + 2000e-9) / 2)
    name, seconds = red["custom_calls"][0]
    assert "flash_fwd" in name and seconds == pytest.approx(1500e-9)


def test_idle_gaps_go_to_the_shortest_span_covering_their_middle(red):
    gaps = dict(red["idle_gaps"])
    # [0,1000] -> bench.step_call (mid 500); [7000,9000] mid 8000 lies in
    # bench.inner (shorter than bench.fetch_loss); [9500,11500] -> nobody
    assert gaps["bench.step_call"] == pytest.approx(1000e-9)
    assert gaps["bench.inner"] == pytest.approx(2000e-9)
    assert gaps["host(unattributed)"] == pytest.approx(2000e-9)
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - 7000e-9)


def test_module_times_of_the_first_device(red):
    assert red["modules"]["jit_step(123)"] == pytest.approx(6000e-9)
    assert red["modules"]["jit__decode_fn(9)"] == pytest.approx(500e-9)


@pytest.mark.parametrize("name,stats,custom,coll", [
    ("fusion.9", {"hlo_category": "loop fusion"}, False, False),
    ("closed_call.25", {"hlo_category": "custom-call"}, True, False),
    ("tpu_custom_call.1", {}, True, False),
    ("all-reduce-start.2", {}, False, True),
    ("reduce-scatter.1", {"hlo_category": "reduce-scatter"}, False, True),
    ("collective-permute-done", {}, False, True),
])
def test_classification(name, stats, custom, coll):
    assert trace_reduce.is_custom_call(name, stats) is custom
    assert trace_reduce.is_collective(name, stats) is coll


def test_no_device_plane_gives_nothing_to_read():
    assert trace_reduce.reduce({"planes": []}) is None


# -- a trace recorded on the v5e (pythia14_train, PR 25): the head of it ----
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_v5e_lm_train_head.json")) as f:
        return json.load(f)


def _sweep_union(intervals):
    """Covered length by counting starts and ends: another way round than
    the reducer's merge."""
    points = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    depth, covered, last = 0, 0.0, None
    for at, step in points:
        if depth > 0:
            covered += at - last
        depth += step
        last = at
    return covered


def test_recorded_trace_reduces_to_what_a_sweep_gives(recorded):
    red = trace_reduce.reduce(recorded)
    lo, hi = trace_reduce.find_window(recorded)
    ops = [e for p in recorded["planes"] if p["name"] == "/device:TPU:0"
           for ln in p["lines"] if ln["name"] == "XLA Ops"
           for e in ln["events"]]
    assert len(ops) == 220 and red["devices"] == 1
    spans = [(max(s, lo), min(s + d, hi)) for _n, s, d, _ in ops]
    assert red["busy_s"] == pytest.approx(_sweep_union(spans) / 1e9)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    # self times add up to the union: nothing is counted twice
    assert red["ops_s"] == pytest.approx(red["busy_s"], rel=1e-6)
    custom = sum(d for n, _s, d, _ in ops if "custom-call(" in n) / 1e9
    # a few short operations start inside a kernel's span and are taken out
    # of its self time
    assert 0 < red["custom_call_s"] <= custom
    assert red["custom_call_s"] == pytest.approx(custom, rel=2e-3)
    assert red["collective_s"] == 0.0
    idle = sum(v for _k, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])


def test_recorded_custom_calls_are_named_by_target_and_shapes(recorded):
    red = trace_reduce.reduce(recorded)
    names = [k for k, _ in red["custom_calls"]]
    flash = [k for k in names if "f32[4,16,2048,1]" in k]
    assert flash and flash[0].startswith("custom-call:tpu_custom_call ")
    assert "bf16[4,16,2048,128]x3" in flash[0]
    # every layer's call of one kernel lands on one row, whatever its number
    assert len(names) == len(set(names))
    assert all(len(k) < 200 for k, _ in red["device_ops"])
    assert any(k.startswith("jit_step(") for k in red["modules"])


def test_hlo_line_parsing():
    text = ('%closed_call.49 = (bf16[4,16,2048,128]{3,2,1,0:T(8,128)(2,1)S(1)}'
            ', f32[4,16,2048,1]{3,2,1,0:T(8,128)}) custom-call(bf16[4,16,2048,'
            '128]{3,2,1,0} %bitcast.4071, bf16[4,16,2048,128]{3,2,1,0} %b.2), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={bf16[4,16,2048,128]{3,2,1,0}}')
    inst, opcode, result, operands = trace_reduce.parse_hlo(text)
    assert (inst, opcode) == ("closed_call.49", "custom-call")
    assert result == ["bf16[4,16,2048,128]", "f32[4,16,2048,1]"]
    assert operands == ["bf16[4,16,2048,128]", "bf16[4,16,2048,128]"]
    assert trace_reduce.parse_hlo("fusion.7") == ("fusion.7", "", [], [])
    fused = "%fusion.215 = bf16[8192,2048]{1,0} fusion(bf16[8192,8192]{1,0} %x), kind=kOutput, calls=%f"
    assert trace_reduce.op_identity(fused, {}) == "fusion.215|fusion:kOutput"
    ar = "%all-reduce.4 = bf16[4,2048,4096]{2,1,0} all-reduce(bf16[4,2048,4096]{2,1,0} %y), replica_groups={}"
    assert trace_reduce.is_collective(ar, {}) and not trace_reduce.is_custom_call(ar, {})
