"""Finding a cell's files by the names in BENCHMARK.json."""
import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic and
    the metrics it reports."""

    def __init__(self, bench, name):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit("no workload %r in BENCHMARK.json (have: %s)"
                             % (name, ", ".join(sorted(by_name))))
        self.entry = w = by_name[name]
        self.name = name
        self.chips = int(w["chips"])
        cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
        self.config = load_json(os.path.join(ROOT, cfg["file"]))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", w["traffic"] + ".json"))
        self.run_seconds = bench["run_seconds"]

        def mine(metric):
            return "workloads" not in metric or name in metric["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if mine(m) and m["moves"] in reported]

    @property
    def family(self):
        """harness/family_<family>.py of the configuration: how the model
        is driven, its reference, and the work its shapes require."""
        return importlib.import_module(
            "harness.family_" + self.config["family"])

    def rehearse(self):
        """Swap in the toy sizes each file keeps under ``rehearsal``."""
        self.config = _merged(self.config, self.config.get("rehearsal", {}))
        self.traffic = _merged(self.traffic,
                               self.traffic.get("rehearsal", {}))


def _merged(base, over):
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merged(out[k], v)
        else:
            out[k] = v
    return out


def load_reader(metric_name):
    """The ``read(ctx)`` of benchmarks/layer_metrics/<metric>.py."""
    path = os.path.join(BENCH_DIR, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
