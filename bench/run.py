"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name through
``BENCHMARK.json`` at the checkout's root. Needs a TPU, with as many chips
as the cell asks for: anywhere else it exits non-zero and prints no result.
With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from counters over the window
and a profiler trace of the window's last seconds. Each number the output
check compares is printed beside its limit, last on standard error and last
in the result line (``checks``).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    result = harness.serve_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_START
    )
    print(f"bench: info: {json.dumps(result['info'])}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"bench: check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
