"""One run of one cell:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each run.  The last line of standard output is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run, and the numbers compared under ``compared``,
last).  Everything else goes to standard error or earlier lines.  See
benchmarks/README.md.
"""
import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                         # noqa: E402
import importlib                        # noqa: E402
import os                               # noqa: E402
import sys                              # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import cells, runtime      # noqa: E402


def main(argv=None, bench=None):
    """``bench``: what BENCHMARK.json holds, for a caller (a test) that has
    read it already or tries entries it does not hold yet."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever backend there is; prints "
                         "counts and the verdict, never a device metric")
    ap.add_argument("--readings", default=None,
                    help="not a benchmark run: put the reference in a lower "
                         "precision ('control') or with a planted fault "
                         "('half_batch', 'no_exchange') in the program's "
                         "place and print what the comparison reads")
    args = ap.parse_args(argv)

    cell = cells.Cell(bench or cells.load_benchmark(), args.workload)
    if args.seconds is None:
        args.seconds = float(cell.run_seconds)
    runtime.place_compile_cache()
    if args.rehearse:
        cell.rehearse()
    devices = runtime.find_devices(cell.chips, args.rehearse)
    runtime.configure_jax()
    kind = importlib.import_module("harness.kind_" + cell.traffic["kind"])
    result = kind.run(cell, args, devices, T_START)
    runtime.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
