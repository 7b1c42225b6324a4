"""Chrome trace-event / Perfetto export of the flight recorder (DESIGN.md §14).

Maps the ``core.telemetry.FlightRecorder`` ring buffer onto the Chrome
trace-event JSON object format — the dialect ui.perfetto.dev and
chrome://tracing both open directly:

* one *track* (thread) per event source: ``dispatcher`` for compile /
  rebind / eviction activity, ``page-pool`` for KV page lifecycle and
  occupancy counters, ``scheduler`` for request lifecycle, the async
  issue/park/commit pipeline and the serving step's phase spans;
* ``ph:"X"`` complete spans (compiles, and the phase spans
  ``serve.<layer>.<phase>`` of ``core.telemetry.span``: the step, its
  planning, packing, bookkeeping and emit, each upload, executable call
  and device pull, and the cache manager's reservation, upkeep, prefix
  publish and migration, nested as the host ran them), ``ph:"i"``
  instants (rebinds, admits, preemptions, spec rollbacks), ``ph:"C"``
  counter samples (pool occupancy) — all timestamps in µs relative to the
  recorder's epoch;
* ``ph:"M"`` metadata events naming the process and each track.

The recorder keeps its own ``perf_counter_ns`` clock, which is not the
device trace's. The same phase spans land on ``jax.profiler``'s host plane,
on the device trace's clock, whenever the profiler collects; that trace,
not this export, is where an idle gap of the chip is put down to a phase.
With neither the recorder nor the profiler on, a phase span costs one
check: 0.35–0.38 µs a span with a note, about 7 µs for the 18 spans of a
step that both prefills and decodes (measured on an 8-core Intel Xeon
x86-64 host, jax 0.9.0).

Capture with ``python -m repro.launch.serve ... --trace-out trace.json``
and drop the file on https://ui.perfetto.dev.
"""

from __future__ import annotations

import json
from typing import IO

from repro.core.telemetry import (
    PH_COUNTER,
    PH_INSTANT,
    PH_SPAN,
    SPAN_TRACK,
    Event,
    FlightRecorder,
)

__all__ = [
    "TRACK_DISPATCH",
    "TRACK_POOL",
    "TRACK_SCHED",
    "chrome_trace",
    "write_trace",
]

# Canonical track names — the instrumentation in core/dispatch.py,
# runtime/scheduler.py, and runtime/kvcache.py all emit onto these.
TRACK_DISPATCH = "dispatcher"
TRACK_POOL = "page-pool"
TRACK_SCHED = SPAN_TRACK

# Fixed tids keep track ordering stable across runs; other tracks follow.
_PINNED_TIDS = {TRACK_DISPATCH: 1, TRACK_SCHED: 2, TRACK_POOL: 3}
_OTHER_TID_BASE = 10
_PID = 1


def _track_ids(events: list[Event]) -> dict[str, int]:
    tids = dict(_PINNED_TIDS)
    nxt = _OTHER_TID_BASE
    for ev in events:
        if ev.track not in tids:
            tids[ev.track] = nxt
            nxt += 1
    return tids


def chrome_trace(recorder: FlightRecorder) -> dict:
    """Render the ring buffer as a Chrome trace-event JSON object."""
    events = recorder.events()
    tids = _track_ids(events)
    base_ns = min((ev.ts_ns for ev in events), default=recorder.t0_ns)

    out: list[dict] = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": _PID,
            "tid": 0,
            "args": {"name": "repro-serving"},
        }
    ]
    for track, tid in sorted(tids.items(), key=lambda kv: kv[1]):
        out.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": _PID,
                "tid": tid,
                "args": {"name": track},
            }
        )

    for ev in events:
        rec: dict = {
            "name": ev.name,
            "ph": ev.ph,
            "ts": (ev.ts_ns - base_ns) / 1e3,  # µs
            "pid": _PID,
            "tid": tids[ev.track],
        }
        if ev.ph == PH_SPAN:
            rec["dur"] = ev.dur_ns / 1e3
        if ev.ph == PH_INSTANT:
            rec["s"] = "t"  # thread-scoped instant
        if ev.args:
            rec["args"] = ev.args
        out.append(rec)

    return {
        "traceEvents": out,
        "displayTimeUnit": "ms",
        "otherData": {
            "emitted": recorder.emitted,
            "dropped": recorder.dropped,
            "capacity": recorder.capacity,
        },
    }


def write_trace(path: str | IO[str], recorder: FlightRecorder) -> dict:
    """Write ``chrome_trace(recorder)`` as JSON; returns the trace dict."""
    trace = chrome_trace(recorder)
    if hasattr(path, "write"):
        json.dump(trace, path)
    else:
        with open(path, "w") as fh:
            json.dump(trace, fh)
    return trace


def validate_trace(trace: dict) -> list[str]:
    """Schema sanity for a rendered trace; returns a list of problems.

    Used by tests and scripts/check_trace.py — empty list means the file
    will open in ui.perfetto.dev.
    """
    problems: list[str] = []
    evs = trace.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return ["traceEvents missing or empty"]
    valid_ph = {PH_SPAN, PH_INSTANT, PH_COUNTER, "M", "B", "E"}
    for i, ev in enumerate(evs):
        for field in ("name", "ph", "pid", "tid"):
            if field not in ev:
                problems.append(f"event {i} missing {field!r}")
        if ev.get("ph") not in valid_ph:
            problems.append(f"event {i} bad ph {ev.get('ph')!r}")
        if ev.get("ph") != "M" and "ts" not in ev:
            problems.append(f"event {i} missing ts")
        if ev.get("ph") == PH_SPAN and "dur" not in ev:
            problems.append(f"event {i} span missing dur")
    return problems
