"""From a profiler trace to device busy time, per-program time and idle gaps.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain events: per device, the ``XLA Ops`` line (every operation the chip
ran) and the ``XLA Modules`` line (one event per executable call); on the
host, the benchmark's own ``bench.*`` annotations. The reduction below works
on those events alone, so it is tested on a small recorded trace
(``testdata/``) without a chip. Times are seconds on the trace's clock.
"""

from __future__ import annotations

import glob
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench."
NAME_CHARS = 160  # an op's name starts with its HLO name and output shape


@dataclass
class Events:
    """Intervals as (name, start_s, end_s)."""

    ops: dict[int, list] = field(default_factory=dict)  # chip -> op events
    modules: dict[int, list] = field(default_factory=dict)  # chip -> calls
    host: list = field(default_factory=list)  # bench.* annotations

    def to_json(self) -> dict:
        return {
            "ops": {str(k): v for k, v in self.ops.items()},
            "modules": {str(k): v for k, v in self.modules.items()},
            "host": self.host,
        }

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(
            ops={int(k): [tuple(e) for e in v] for k, v in d["ops"].items()},
            modules={
                int(k): [tuple(e) for e in v] for k, v in d["modules"].items()
            },
            host=[tuple(e) for e in d["host"]],
        )

    def clip(self, lo: float, hi: float) -> "Events":
        """Only the events that start inside [lo, hi)."""
        keep = lambda evs: [e for e in evs if lo <= e[1] < hi]  # noqa: E731
        return Events(
            ops={k: keep(v) for k, v in self.ops.items()},
            modules={k: keep(v) for k, v in self.modules.items()},
            host=keep(self.host),
        )


def find_xplane(log_dir: str | Path) -> Path:
    hits = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(hits[-1])


def read_xplane(path: str | Path) -> Events:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ev = Events()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dest = ev.ops.setdefault(chip, [])
                elif line.name == "XLA Modules":
                    dest = ev.modules.setdefault(chip, [])
                else:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dest.append(
                        (e.name[:NAME_CHARS], s, s + e.duration_ns * 1e-9)
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        s = e.start_ns * 1e-9
                        ev.host.append((e.name, s, s + e.duration_ns * 1e-9))
    for d in (ev.ops, ev.modules):
        for v in d.values():
            v.sort(key=lambda e: e[1])
    ev.host.sort(key=lambda e: e[1])
    return ev


def union(intervals: list, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of the intervals, clipped to [lo, hi], as sorted spans."""
    spans = sorted(
        (max(s, lo), min(e, hi)) for _, s, e in intervals if e > lo and s < hi
    )
    out: list[list[float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(intervals: list, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def idle_gaps(intervals: list, lo: float, hi: float) -> list[tuple[float, float]]:
    """Spans of [lo, hi] in which no interval is running."""
    gaps, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def attribute_gaps(gaps: list, host: list) -> dict[str, float]:
    """Seconds of idle device time by what the host was doing: the innermost
    ``bench.*`` annotation open at each instant of a gap ("none" where no
    annotation was open)."""
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        cuts = {g0, g1}
        for _, s, e in host:
            if g0 < s < g1:
                cuts.add(s)
            if g0 < e < g1:
                cuts.add(e)
        pts = sorted(cuts)
        for a, b in zip(pts, pts[1:]):
            mid = 0.5 * (a + b)
            inner = [
                (e - s, name) for name, s, e in host if s <= mid < e
            ]
            name = min(inner)[1] if inner else "none"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def module_calls(modules: list, pattern: str) -> list:
    """Executable calls whose module name contains ``pattern``."""
    return [e for e in modules if pattern in e[0]]


def top_ops(ops: dict[int, list], chips: list[int], n: int = 10) -> list:
    """The ``n`` operations that took the most device time, summed over
    ``chips`` and divided by their number."""
    tot: dict[str, float] = {}
    for c in chips:
        for name, s, e in ops.get(c, []):
            tot[name] = tot.get(name, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(chips), 1)] for k, v in ranked]


def load_json(path: str | Path) -> Events:
    return Events.from_json(json.loads(Path(path).read_text()))
