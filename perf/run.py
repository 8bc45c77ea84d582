#!/usr/bin/env python3
"""The benchmark's one command: one cell, one seed, one measured window.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It needs a TPU with the chips the cell asks for and exits non-zero without
one. ``--rehearsal`` alone allows another backend: the cell's traffic at the
tiny widths of its configuration's ``rehearsal_config``, to debug the
harness on the CPU; it never prints a time under a metric's name.
``--control <name>`` switches on a path that has to come out as not
correct (see PERF.md); the driver never passes it.

The last line of standard output is the result: one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
in a traced run, ``breakdown``. What else was observed goes on earlier lines.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--control", default=None)
    args = parser.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"perf: no cell {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2

    sys.path.insert(0, ROOT)
    from perf import harness

    return harness.run_cell(manifest, cells[args.workload], args, T_PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
