"""Readings that set a cell's output-check limit, on the chip.

    python bench/control.py --workload <name> --seeds 1 2 3 ... --seconds 20

For each seed, one control run of the cell as the benchmark runs it (a
shorter window): the output check with the control's tokens in the served
tokens' place, the tokens that the reference computed with int8 weights
and inputs puts first at each served position. The run's ``correct`` is the
harness's own verdict on the control, and should read false. Beside it, over
the same seeded sample, the program's own widest gap. The limit goes between
the largest program reading and the smallest control reading (``PERF.md``).
Prints one JSON line per seed and a summary line; needs the chip like
``run.py``.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default="int8")
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = harness.serve_cell(
            cell, seed, args.seconds, False, t0, control=args.control
        )
        row = {
            "seed": seed,
            "program_gap": res["info"]["program_gap"],
            "control_gap": res["checks"]["logit_gap"]["value"],
            "limit": res["checks"]["logit_gap"]["limit"],
            "positions": res["info"]["checked_positions"],
            "exact": res["info"]["checked_exact"],
            "correct": res["correct"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "program_max": max(r["program_gap"] for r in rows),
        "control_min": min(r["control_gap"] for r in rows),
        "control_correct": [r["correct"] for r in rows],
        "seeds": len(rows),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
