"""Puts the benchmark on the path of the tests beside this file, and finds
the cells they try: those of BENCHMARK.json, and those whose entries wait in
``cells_unproven.json`` (plain data, shaped as BENCHMARK.json) until a chip
run has proven them.  A cell that BENCHMARK.json holds is taken from there,
so a PR that proves a cell adds its entries to BENCHMARK.json and edits
nothing here.  Not a conftest.py: the tests above import ``conftest`` by that
name, and a second module of the name would shadow it."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cells  # noqa: E402

BENCH = cells.load_benchmark()
UNPROVEN_FILE = os.path.join(HERE, "cells_unproven.json")
UNPROVEN = cells.load_json(UNPROVEN_FILE)
PROVEN = [w["name"] for w in BENCH["workloads"]]
# cell -> the entries that hold it
HOLDS = {w["name"]: UNPROVEN for w in UNPROVEN["workloads"]}
HOLDS.update({name: BENCH for name in PROVEN})
ALL = sorted(HOLDS)


def cell(name):
    return cells.Cell(HOLDS[name], name)


def cells_of_kind(kind):
    return [n for n in ALL if cell(n).traffic["kind"] == kind]


def metric_entries(key):
    """[(id, entries, metric)] of ``end_to_end`` or ``per_layer``: every
    metric of BENCHMARK.json, then those of the unproven cells."""
    out = [(m["name"], BENCH, m) for m in BENCH[key]]
    taken = {m["name"] for m in BENCH[key]}
    for m in UNPROVEN[key]:
        out.append((m["name"] + ("@unproven" if m["name"] in taken else ""),
                    UNPROVEN, m))
    return out
