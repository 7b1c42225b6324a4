"""The reduction from profiler events to busy time, per-program time and
idle gaps, on a small trace recorded on a v5e chip (``testdata/``) and on
hand-made events."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import devtrace as tracing  # noqa: E402

RECORDED = BENCH / "testdata" / "trace_olmo_chat.json"


def test_union_and_gaps_by_hand():
    evs = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0), ("d", 5.0, 9.0)]
    assert tracing.union(evs, 0.0, 6.0) == [(0.0, 2.0), (3.0, 4.0), (5.0, 6.0)]
    assert tracing.busy_seconds(evs, 0.0, 6.0) == pytest.approx(4.0)
    assert tracing.idle_gaps(evs, -1.0, 6.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def test_gaps_go_to_the_innermost_annotation():
    host = [("bench.window", 0.0, 10.0), ("bench.step", 1.0, 2.5),
            ("bench.admit", 2.5, 3.0)]
    got = tracing.attribute_gaps([(0.5, 3.5)], host)
    assert got == pytest.approx({
        "bench.window": 1.0, "bench.step": 1.5, "bench.admit": 0.5,
    })
    assert tracing.attribute_gaps([(11.0, 12.0)], host) == {"none": 1.0}


def test_recorded_trace_has_both_lanes_inside_steps():
    ev = tracing.load_json(RECORDED)
    steps = [e for e in ev.host if e[0] == "bench.step"]
    assert steps
    calls = {
        lane: [e for mods in ev.modules.values()
               for e in tracing.module_calls(mods, pattern)]
        for lane, pattern in (("decode", harness.DECODE_MODULE),
                              ("prefill", harness.PREFILL_MODULE))
    }
    assert calls["decode"] and calls["prefill"]
    for lane in calls.values():
        for _, s, e in lane:
            assert e > s
            # every lane call runs inside the host step that issued it
            assert any(s0 <= s < s1 for _, s0, s1 in steps)


def test_recorded_trace_busy_share_and_breakdown():
    ev = tracing.load_json(RECORDED)
    lo = min(e[1] for e in ev.host)
    hi = max(e[2] for e in ev.host)
    busy = tracing.busy_seconds(ev.ops[0], lo, hi)
    assert 0 < busy <= hi - lo
    gaps = tracing.idle_gaps(ev.ops[0], lo, hi)
    assert sum(b - a for a, b in gaps) == pytest.approx(hi - lo - busy)
    by_host = tracing.attribute_gaps(gaps, ev.host)
    assert sum(by_host.values()) == pytest.approx(hi - lo - busy)
    top = tracing.top_ops(ev.ops, [0], n=10)
    assert len(top) <= 10 and all(v > 0 for _, v in top)
    assert top == sorted(top, key=lambda kv: -kv[1])


def test_events_round_trip_through_json(tmp_path):
    ev = tracing.load_json(RECORDED)
    back = tracing.Events.from_json(ev.to_json())
    assert back.modules == ev.modules and back.host == ev.host


def test_read_xplane_keeps_the_bench_annotations(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("not.ours"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tracing.read_xplane(tracing.find_xplane(tmp_path))
    names = [e[0] for e in ev.host]
    assert "bench.step" in names and "not.ours" not in names
