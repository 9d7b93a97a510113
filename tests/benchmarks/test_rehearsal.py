"""``run.py`` end to end at toy sizes on the CPU: every cell prints a last
line of exactly the contract's keys and no device metric; the plain
references agree with the program in float32 (transformer loss, gradient
and update; prefill-then-decode through the paged cache against a full
forward; ResNet-50 loss, gradient and update) and the same comparison one
precision down fails; without ``--rehearse`` a run on the CPU ends non-zero
naming ``cpu``.  The cells are those ``bench_paths`` finds: none is named
here."""
import json
import os
import subprocess
import sys

import pytest

import bench_paths as bp

DEVICE_METRICS = sorted({m["name"] for key in ("end_to_end", "per_layer")
                         for _i, _e, m in bp.metric_entries(key)})
# a cell BENCHMARK.json holds goes through the command line as the driver
# gives it; one it does not hold yet is handed to the same ``main`` as data
UNPROVEN_MAIN = (
    "import json, sys; sys.path.insert(0, %r); import run; "
    "sys.exit(run.main(sys.argv[1:], bench=json.load(open(%r))))"
    % (bp.BENCH_DIR, bp.UNPROVEN_FILE))


def run(cell, *argv, timeout=600):
    chips = bp.cell(cell).chips if cell in bp.HOLDS else 1
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=bp.ROOT)
    env.pop("XLA_FLAGS", None)
    if chips > 1:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                            % chips)
    env["BENCH_RUN"] = "ignored"
    entry = ["-c", UNPROVEN_MAIN] if bp.HOLDS.get(cell) is bp.UNPROVEN \
        else [os.path.join(bp.BENCH_DIR, "run.py")]
    return subprocess.run(
        [sys.executable, *entry, "--workload", cell, *argv],
        cwd=bp.ROOT, env=env, capture_output=True, text=True,
        timeout=timeout)


def last_json(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", bp.ALL)
def test_rehearsal_run_agrees_with_the_reference(cell, trace):
    proc = run(cell, "--seed", str(2**31 + 11), "--seconds", "1", "--trace",
               str(trace), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = last_json(proc)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    assert set(out) <= {"correct", "attempted", "failed", "metrics",
                        "device", "breakdown", "compared"}
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == bp.cell(cell).chips
    for name in DEVICE_METRICS:
        assert name not in proc.stdout, "%s printed by a CPU run" % name
    # each number compared is printed beside its limit, last on stderr
    tail = [ln for ln in proc.stderr.splitlines() if ln.strip()][-len(
        out["compared"]):]
    assert all(ln.startswith("compared ") for ln in tail), tail


@pytest.mark.parametrize("cell", bp.cells_of_kind("train_fixed"))
def test_the_control_one_precision_down_fails(cell):
    proc = run(cell, "--seed", "5", "--rehearse", "--readings", "control")
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = last_json(proc)
    assert out["correct"] is False, out["compared"]
    failed = [k for k, v in out["compared"].items()
              if v["limit"] is not None and v["value"] > v["limit"]]
    assert failed


@pytest.mark.parametrize("name", bp.cells_of_kind("serve_closed"))
def test_serving_control_reads_a_wider_gap_than_the_limit(name):
    """The control need not decode: at each position of the same tokens it
    reads the gap of the token the lower precision puts first.  Over a
    thousand positions of the toy model that is a fixed number from the
    seed, and it has to lie over the limit the served tokens are held to
    (a served run reads 0 here: the first test of this file)."""
    import numpy as np

    from harness import ref_transformer, weights

    cell = bp.cell(name)
    cell.rehearse()
    model = cell.config["model"]
    params = weights.lm_init(model, 5)
    forward = ref_transformer.make_forward(model)
    control = ref_transformer.make_forward(
        model, cell.config["precision"]["control"])
    rng = np.random.default_rng(5)
    widest = 0.0
    for _ in range(8):
        ids = rng.integers(0, model["vocab_size"], size=128, dtype=np.int32)
        widest = max(widest, float(ref_transformer.served_token_gaps(
            forward, params, ids[:8], ids[8:],
            control_forward=control).max()))
    assert widest > 3 * cell.traffic["limits"]["served_gap"]


def test_without_rehearse_a_cpu_run_ends_nonzero_naming_cpu():
    proc = run(bp.PROVEN[0], "--seed", "1", "--seconds", "1", "--trace", "0",
               timeout=120)
    assert proc.returncode != 0
    assert "cpu" in proc.stderr and "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_an_unknown_workload_ends_nonzero():
    proc = run("no_such_cell", "--seed", "1", timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
