"""The program's phase spans on the device trace's clock, and the chip's idle
time put down to the layer whose span was open.

The serving program annotates each phase of its step as
``serve.<layer>.<phase>`` (``core/telemetry.py``); while ``jax.profiler``
collects, those spans land on the trace's host plane beside the benchmark's
own ``bench.*`` annotations. ``read_program`` collects them from a
``.xplane.pb``. ``program_idle`` gives each idle gap of each chip to the
innermost program span open at that instant (``devtrace.attribute_gaps``)
and sums by layer; ``"none"`` is idle time outside every program span, which
is the benchmark's own host work and sleep. ``program_host`` splits the host
time inside the program's steps the same way, by innermost phase.

    python bench/phases.py --workload <name> --seed <n> --seconds <s>

runs one traced run of a cell as ``run.py --trace 1`` does, whose slice
already holds ``program_idle`` (``harness._reduce_trace``), and prints its
result line with ``program_idle`` and ``program_host`` added to it. Needs a
TPU, as ``run.py`` does. ``--save <file>`` also writes the trace's last
steps (every event, program spans included) as JSON of at most 300 KB, the
form of ``testdata/`` (nothing, where one step alone is larger).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import devtrace  # noqa: E402

PREFIX = "serve."
LAYERS = ("batcher", "dispatch", "cache")
STEP = "serve.batcher.step"
SAVE_BYTES = 300_000


def layer_of(name: str) -> str:
    """The layer of a ``serve.<layer>.<phase>`` span name."""
    parts = name.split(".")
    if len(parts) != 3 or parts[0] != "serve" or parts[1] not in LAYERS:
        raise ValueError(f"not a serve.<layer>.<phase> span: {name!r}")
    return parts[1]


def read_program(path) -> list:
    """The program's ``serve.*`` host spans of a ``.xplane.pb``, as sorted
    (name, start_s, end_s) on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    out.append((e.name, s, s + e.duration_ns * 1e-9))
    out.sort(key=lambda e: e[1])
    return out


def _by_span(intervals: list, spans: list) -> dict[str, float]:
    """``devtrace.attribute_gaps`` over sorted, disjoint ``intervals``,
    handing each interval only the spans that overlap it (the same result,
    without scanning every span for every interval)."""
    out: dict[str, float] = {}
    i, open_ = 0, []
    for g0, g1 in intervals:
        while i < len(spans) and spans[i][1] < g1:
            open_.append(spans[i])
            i += 1
        open_ = [e for e in open_ if e[2] > g0]
        for k, v in devtrace.attribute_gaps([(g0, g1)], open_).items():
            out[k] = out.get(k, 0.0) + v
    return out


def program_idle(ops: dict, program: list, chips: list, lo: float,
                 hi: float) -> dict:
    """Idle seconds of the chips in [lo, hi], averaged over ``chips``, by
    the layer of the innermost program span open (``"none"``: no program
    span open), with ``steps``, the ``serve.batcher.step`` spans inside
    [lo, hi]."""
    out = dict.fromkeys((*LAYERS, "none"), 0.0)
    for c in chips:
        gaps = devtrace.idle_gaps(ops.get(c, []), lo, hi)
        for name, sec in _by_span(gaps, program).items():
            key = name if name == "none" else layer_of(name)
            out[key] += sec / len(chips)
    out["steps"] = sum(
        1 for n, s, e in program if n == STEP and lo <= s and e <= hi
    )
    return out


def program_host(program: list, lo: float, hi: float) -> dict:
    """Host seconds inside the ``serve.batcher.step`` spans of [lo, hi], by
    the innermost phase span open, with ``steps``."""
    steps = [
        (s, e) for n, s, e in program if n == STEP and lo <= s and e <= hi
    ]
    out = _by_span(steps, program)
    out["steps"] = len(steps)
    return out


def save_steps(path, ev: devtrace.Events, program: list) -> None:
    """Write the last ``bench.step`` spans of a trace, as many as fit in
    ``SAVE_BYTES``, with every event that starts inside them."""
    steps = [e for e in ev.host if e[0] == "bench.step"]
    for n in range(min(len(steps), 16), 0, -1):
        lo, hi = steps[-n][1], steps[-1][2]
        keep = ev.clip(lo, hi)
        keep.host = [e for e in keep.host if e[0] == "bench.step"]
        text = json.dumps({
            **keep.to_json(),
            "program": [e for e in program if lo <= e[1] < hi],
        })
        if len(text) <= SAVE_BYTES:
            Path(path).write_text(text)
            return


def probe(cell, seed: int, seconds: float, save=None, **kw) -> dict:
    """One traced run of ``cell`` (``harness.serve_cell``), its result with
    the traced slice's ``program_idle`` and ``program_host`` added."""
    import harness

    reduce_trace = harness._reduce_trace
    slices = []

    def reduce_with_program(trace_dir, steps, chips_used, run):
        sl = reduce_trace(trace_dir, steps, chips_used, run)
        if sl is not None:
            path = devtrace.find_xplane(trace_dir)
            program = read_program(path)
            sl["program_host"] = program_host(program, sl["lo"], sl["hi"])
            slices.append(sl)
            if save:
                save_steps(save, devtrace.read_xplane(path), program)
        return sl

    harness._reduce_trace = reduce_with_program
    try:
        result = harness.serve_cell(cell, seed, seconds, True, T_START, **kw)
    finally:
        harness._reduce_trace = reduce_trace
    for sl in slices:
        result["program_idle"] = sl["program_idle"]
        result["program_host"] = sl["program_host"]
    return result


def main(argv=None) -> int:
    import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    result = probe(cell, args.seed, args.seconds, args.save)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
