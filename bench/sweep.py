"""The knee of an open-loop cell: the highest offered rate the system serves
without a growing backlog, found once on the chip.

    python bench/sweep.py --workload olmo-1b.chat --rates 0.4 0.5 0.6 0.7

One engine, warmed once, and one stream whose rate steps up: at each rate
the stream settles for ``--settle`` seconds and is measured for
``--seconds``; the sweep stops at the first rate that fails. A point reports
the offered and admitted rates, the backlog (requests due but not admitted)
at the window's start and end, and the first-token and inter-token tails.
The knee is the highest rate below the first that fails: a rate fails when
the backlog at the window's end exceeds its start by more than
``max(4, 5% of the requests due)``, or when requests still wait at the end
while admissions ran below 95% of the rate. The cell then offers 0.8 of it
(``arrivals.rate_hz`` in the mix file, with the points beside it). Needs the chip
like ``run.py``; prints one JSON line per point, then the knee.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import weights  # noqa: E402


def level(drv, cb, rate: float, settle: float, seconds: float) -> dict:
    """Offer ``rate`` from now on: settle, then measure a window."""
    drv.arrivals.rate_scale = rate / drv.mix["arrivals"]["rate_hz"]
    t = time.perf_counter()
    while time.perf_counter() < t + settle:
        drv.tick()
    t_w0 = time.perf_counter()
    q0 = len(drv.waiting)
    active = []
    while time.perf_counter() < t_w0 + seconds:
        drv.tick()
        active.append(cb.active_count)
    t_w1 = time.perf_counter()
    q1 = len(drv.waiting)
    due = [s for s in drv.all if t_w0 <= s.due < t_w1]
    admitted = [s for s in drv.all if s.admitted and t_w0 <= s.admitted < t_w1]
    ttft = [s.stamps[0] - s.due for s in due if s.stamps]
    itl = [b - a for s in drv.all for a, b in zip(s.stamps, s.stamps[1:])
           if t_w0 < b <= t_w1]
    out = {
        "rate_hz": rate,
        "due": len(due),
        "admitted_per_s": len(admitted) / (t_w1 - t_w0),
        "backlog_start": q0,
        "backlog_end": q1,
        "mean_active_slots": float(np.mean(active)) if active else 0.0,
        "first_token_by_close": len(ttft),
        "ttft_p90_ms": float(np.percentile(ttft, 90) * 1e3) if ttft else None,
        "itl_p95_ms": float(np.percentile(itl, 95) * 1e3) if itl else None,
        "output_tok_s": sum(1 for s in drv.all for x in s.stamps
                            if t_w0 < x <= t_w1) / (t_w1 - t_w0),
    }
    # the backlog grows when it gains, or when requests wait at the close
    # while admissions fell behind the offered rate
    out["growing"] = q1 - q0 > max(4, 0.05 * len(due)) or (
        q1 > 0 and out["admitted_per_s"] < 0.95 * rate
    )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--settle", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = harness.resolve(harness.load_spec(), args.workload)
    harness.device_info(cell.chips)
    params = weights.make_params(cell.model, args.seed)
    _, eng, disagg = harness.build_engine(cell, params)
    cb = eng.paged_continuous(seed=args.seed, disagg=disagg)
    drv = harness.Driver(cb, eng, cell.mix, cell.model, args.seed)
    drv.start(time.perf_counter())
    points = []
    for rate in sorted(args.rates):
        p = level(drv, cb, rate, args.settle, args.seconds)
        points.append(p)
        print(json.dumps(p), flush=True)
        if p["growing"]:
            break
    knee = None
    for p in points:  # the highest rate below the first that grows
        if p["growing"]:
            break
        knee = p["rate_hz"]
    print(json.dumps({"knee_hz": knee,
                      "offered_hz": 0.8 * knee if knee else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
