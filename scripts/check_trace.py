#!/usr/bin/env python
"""Validate telemetry artifacts from ``launch/serve.py`` (DESIGN.md §14).

Usage:
    python scripts/check_trace.py TRACE.json [METRICS.prom]

Checks the Chrome trace-event JSON the flight recorder exports (schema
validity, minimum event-type diversity, the dispatcher track, the step's
phase spans) and — when given —
the Prometheus text exposition (parses, carries per-lane latency
histograms). Exit 0 on pass, 1 with a reason on fail; ``make
smoke-telemetry`` runs this against a fresh capture.
"""
from __future__ import annotations

import json
import sys

# The acceptance bar: a capture of the full serving stack shows at least
# this many distinct event types, spread over the dispatcher and
# scheduler/page-pool tracks, with the step's phase spans on the scheduler
# track.
MIN_EVENT_TYPES = 5


def check_trace(path: str) -> list[str]:
    problems: list[str] = []
    try:
        with open(path) as fh:
            trace = json.load(fh)
    except (OSError, ValueError) as exc:
        return [f"{path}: unreadable or invalid JSON: {exc}"]

    sys.path.insert(0, "src")
    from repro.runtime.tracing import validate_trace

    problems += [f"{path}: {p}" for p in validate_trace(trace)]

    events = [e for e in trace.get("traceEvents", []) if e.get("ph") != "M"]
    names = {e["name"] for e in events}
    if len(names) < MIN_EVENT_TYPES:
        problems.append(
            f"{path}: only {len(names)} event types {sorted(names)}; "
            f"need >= {MIN_EVENT_TYPES}"
        )
    meta = [e for e in trace.get("traceEvents", []) if e.get("ph") == "M"]
    tracks = {
        e.get("args", {}).get("name")
        for e in meta
        if e.get("name") == "thread_name"
    }
    if "dispatcher" not in tracks:
        problems.append(f"{path}: no dispatcher track in {sorted(tracks)}")
    if "serve.batcher.step" not in names:
        problems.append(f"{path}: no serve.batcher.step phase spans")
    return problems


def check_prometheus(path: str) -> list[str]:
    problems: list[str] = []
    try:
        with open(path) as fh:
            text = fh.read()
    except OSError as exc:
        return [f"{path}: unreadable: {exc}"]
    types: dict[str, str] = {}
    for i, line in enumerate(text.splitlines(), 1):
        if not line or line.startswith("#"):
            if line.startswith("# TYPE "):
                parts = line.split()
                if len(parts) != 4:
                    problems.append(f"{path}:{i}: malformed TYPE line")
                else:
                    types[parts[2]] = parts[3]
            continue
        # sample line: name{labels} value  |  name value
        body = line.rsplit(" ", 1)
        if len(body) != 2:
            problems.append(f"{path}:{i}: malformed sample line: {line!r}")
            continue
        try:
            float(body[1])
        except ValueError:
            problems.append(f"{path}:{i}: non-numeric value: {body[1]!r}")
    if types.get("lane_step_ms") != "histogram":
        problems.append(
            f"{path}: no per-lane latency histogram family "
            f"(lane_step_ms); TYPEs seen: {types}"
        )
    if 'lane_step_ms_bucket{lane="' not in text:
        problems.append(f"{path}: lane_step_ms has no lane-labelled buckets")
    return problems


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__)
        return 1
    problems = check_trace(argv[0])
    if len(argv) > 1:
        problems += check_prometheus(argv[1])
    for p in problems:
        print(f"[check_trace] FAIL: {p}")
    if not problems:
        print(f"[check_trace] OK: {', '.join(argv)}")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
