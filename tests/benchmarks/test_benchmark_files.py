"""BENCHMARK.json against the files the harness finds by name: adding a
cell, a configuration, a traffic mix, a traffic kind, a model family or a
per-layer metric is adding files and entries, never editing a file that
exists.  Nothing here names a cell, a kind or a family."""
import os

import pytest

import bench_paths as bp
from harness import cells, correct

PER_LAYER = bp.metric_entries("per_layer")
HARNESS = os.path.join(cells.BENCH_DIR, "harness")


def test_paths_and_command_stay_inside_the_benchmark():
    bench = bp.BENCH
    assert bench["paths"] == ["benchmarks", "tests/benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"] + bp.UNPROVEN["configs"]:
        assert c["file"].startswith("benchmarks/configs/")
        assert os.path.exists(os.path.join(cells.ROOT, c["file"]))


@pytest.mark.parametrize("name", bp.ALL)
def test_every_cell_finds_its_files_and_reports_enough(name):
    cell = bp.cell(name)
    assert os.path.exists(os.path.join(
        HARNESS, "kind_%s.py" % cell.traffic["kind"]))
    assert os.path.exists(os.path.join(
        HARNESS, "family_%s.py" % cell.config["family"]))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(cell.per_layer) >= 1
    entry = {c["name"]: c for c in bp.HOLDS[name]["configs"]}[
        cell.entry["config"]]
    assert cell.config["reduced"] == entry["reduced"]
    assert cell.config["source"] == entry["source"]
    assert cell.traffic["limits"]


@pytest.mark.parametrize("name", bp.PROVEN)
def test_a_cell_of_the_benchmark_has_every_limit_and_bound_set(name):
    cell = bp.cell(name)
    correct.refuse_unset(cell.traffic["limits"])
    assert all(m["bound"] is not None for m in cell.end_to_end)


@pytest.mark.parametrize("name", bp.cells_of_kind("train_fixed"))
def test_a_training_cell_compares_loss_gradient_and_change(name):
    # a number may go uncompared (its key left out, PERF.md says why), but
    # the loss, a gradient number and a change number stay compared
    limits = set(bp.cell(name).traffic["limits"])
    assert "loss_rel" in limits
    assert {"grad_norm_gap", "grad_norm_gap_median_leaf"} & limits
    assert {"change_norm_gap", "change_norm_gap_median_leaf"} & limits


@pytest.mark.parametrize("name", bp.cells_of_kind("train_fixed"))
def test_a_training_cells_family_says_what_an_item_requires(name):
    """The whole step's share of the peak divides this by a measured time:
    it comes from the family's own file, so a new family brings its own."""
    cell = bp.cell(name)
    per_item = cell.family.train_flops_per_item(cell.config, cell.traffic)
    # forward and backward over every parameter in a product: 6 an item at
    # the least for a dense model, and far under a PFLOP
    assert 1e6 < per_item < 1e15


@pytest.mark.parametrize("name", sorted(set(bp.ALL) - set(bp.PROVEN)))
def test_an_unproven_cell_is_not_run_as_a_benchmark(name):
    """Its bounds or its limits are still ``null``, and the harness then
    refuses it, or a run of it would look like a measurement."""
    cell = bp.cell(name)
    unset_bound = any(m["bound"] is None for m in cell.end_to_end)
    unset_limit = any(v is None for v in cell.traffic["limits"].values())
    assert unset_bound or unset_limit
    if unset_limit:
        with pytest.raises(SystemExit):
            correct.refuse_unset(cell.traffic["limits"])


@pytest.mark.parametrize("ident,entries,metric", PER_LAYER,
                         ids=[i for i, _e, _m in PER_LAYER])
def test_every_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(
        ident, entries, metric):
    assert callable(cells.load_reader(metric["name"]))
    e2e = {m["name"]: m for m in entries["end_to_end"]}
    assert metric["moves"] in e2e
    moved = e2e[metric["moves"]]
    held = {w["name"] for w in entries["workloads"]}
    for w in metric["workloads"]:
        assert w in held
        assert "workloads" not in moved or w in moved["workloads"], \
            "%s is read in %s, which does not report %s" % (
                metric["name"], w, metric["moves"])
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_no_reader_without_an_entry():
    files = {f[:-3] for f in os.listdir(os.path.join(
        cells.BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert files == {m["name"] for _i, _e, m in PER_LAYER}
    assert {m["name"] for m in bp.BENCH["per_layer"]} <= files


# a count is left out: nought is a reading
NOT_COUNTS = [e for e in PER_LAYER if e[2]["unit"] != "count"]


@pytest.mark.parametrize("ident,entries,metric", NOT_COUNTS,
                         ids=[i for i, _e, _m in NOT_COUNTS])
def test_a_reader_that_finds_nothing_returns_nothing(ident, entries, metric):
    empty = {"cell": cells.Cell(entries, metric["workloads"][0]),
             "trace": None,
             "window": {"t_open": 0.0, "t_close": 1.0, "seconds": 1.0},
             "calls": {"prefill": [], "decode": []}, "recompiles": 0,
             "counters": {}, "rate": 0.0, "devices": [], "max_slots": 1,
             "rehearse": True}
    assert cells.load_reader(metric["name"])(empty) is None


@pytest.mark.parametrize("name", bp.ALL)
def test_the_rehearsal_sizes_overlay_the_real_ones(name):
    cell = bp.cell(name)
    real_config, real_traffic = dict(cell.config), dict(cell.traffic)
    cell.rehearse()
    assert cell.config != real_config and cell.traffic != real_traffic
    # what the toy does not name stays as the cell has it
    for key in set(real_traffic) - set(real_traffic.get("rehearsal", {})):
        assert cell.traffic[key] == real_traffic[key]
