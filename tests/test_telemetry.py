"""Flight recorder + metrics registry tests (DESIGN.md §14): ring-buffer
bounds and overflow accounting, emission ordering, the zero-event guarantee
when disabled, histogram percentile math against numpy, Prometheus text
exposition validity, Chrome-trace schema via chrome_trace/validate_trace,
the latency_report-derives-from-registry regression, the warmup rollover
boundary, burst/continuous report parity, per-DispatchKey compile reports,
and page-pool / d2h event emission."""

import json

import jax
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.core import reset_entry_points
from repro.core.telemetry import (
    DEFAULT_MS_BUCKETS,
    NO_SPAN,
    FlightRecorder,
    Histogram,
    MetricsRegistry,
    Telemetry,
    span,
)
from repro.runtime.scheduler import Request, latency_report
from repro.runtime.serve import (
    Engine,
    EngineConfig,
    run_burst_stream,
    run_continuous_stream,
)
from repro.runtime.steps import pull_host
from repro.runtime.tracing import chrome_trace, validate_trace, write_trace


# ------------------------------------------------------------ flight recorder
def test_ring_buffer_bounds_and_overflow():
    rec = FlightRecorder(capacity=8, enabled=True)
    for i in range(20):
        rec.emit(f"e{i}", "scheduler", ts_ns=1000 + i)
    assert len(rec) == 8
    assert rec.emitted == 20
    assert rec.dropped == 12
    names = [e.name for e in rec.events()]
    assert names == [f"e{i}" for i in range(12, 20)]  # oldest survivors first
    ts = [e.ts_ns for e in rec.events()]
    assert ts == sorted(ts)  # emission order preserved across the wrap


def test_disabled_recorder_emits_nothing():
    rec = FlightRecorder(capacity=8, enabled=False)
    rec.emit("x", "scheduler")
    rec.complete("y", "scheduler", t0_ns=0)
    rec.counter("z", "page-pool", v=1.0)
    assert len(rec) == 0 and rec.emitted == 0
    tel = Telemetry()  # disabled is the default
    assert tel.trace_or_none() is None
    tel.enable()
    assert tel.trace_or_none() is tel.recorder
    tel.disable()
    assert tel.trace_or_none() is None


def test_recorder_clear_and_capacity_validation():
    rec = FlightRecorder(capacity=4, enabled=True)
    for i in range(6):
        rec.emit(f"e{i}", "scheduler")
    rec.clear()
    assert len(rec) == 0 and rec.emitted == 0 and rec.dropped == 0
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


# ----------------------------------------------------------------- histograms
def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    samples = rng.uniform(0.06, 9.5, size=5000)  # spans several buckets
    h = Histogram(DEFAULT_MS_BUCKETS)
    for s in samples:
        h.observe(float(s))
    assert h.count == len(samples)
    assert h.sum == pytest.approx(samples.sum())
    assert h.mean == pytest.approx(samples.mean())
    for p in (50, 95, 99):
        est = h.percentile(p)
        exact = float(np.percentile(samples, p))
        # linear interpolation is exact to within the containing bucket width
        idx = int(np.searchsorted(DEFAULT_MS_BUCKETS, exact))
        lo = 0.0 if idx == 0 else DEFAULT_MS_BUCKETS[idx - 1]
        hi = DEFAULT_MS_BUCKETS[min(idx, len(DEFAULT_MS_BUCKETS) - 1)]
        assert abs(est - exact) <= (hi - lo) + 1e-9, (p, est, exact)


def test_histogram_overflow_and_cumulative():
    h = Histogram((1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.counts == [1, 1, 1, 1]  # last is the +Inf overflow bucket
    cum = h.cumulative()
    assert cum == [(1.0, 1), (2.0, 2), (4.0, 3), (float("inf"), 4)]
    assert h.percentile(100) == 4.0  # overflow clamps to the last bound
    with pytest.raises(ValueError):
        Histogram((2.0, 1.0))


# ------------------------------------------------------------------- registry
def test_registry_instruments_and_labeled_values():
    reg = MetricsRegistry()
    reg.inc("lane_calls_total", lane="cb")
    reg.inc("lane_calls_total", 2, lane="pf")
    reg.set("pool_pages_free", 7.0)
    reg.observe("lane_step_ms", 1.5, lane="cb")
    assert reg.value("lane_calls_total", lane="cb") == 1
    assert reg.labeled_values("lane_calls_total", "lane") == {"cb": 1, "pf": 2}
    with pytest.raises(ValueError):  # kind mismatch on an existing family
        reg.gauge("lane_calls_total")
    snap = reg.snapshot()
    assert snap["counters"]["lane_calls_total"]
    assert snap["histograms"]["lane_step_ms"][0]["count"] == 1


def test_registry_rollover_keeps_cached_handles():
    reg = MetricsRegistry()
    c = reg.counter("lane_calls_total", lane="cb")
    h = reg.histogram("lane_step_ms", lane="cb")
    c.inc(5)
    h.observe(2.0)
    snap = reg.rollover("warmup")
    assert snap["counters"]["lane_calls_total"][0]["value"] == 5
    assert reg.sections["warmup"] is snap
    # instruments are reset *in place*: the cached handles stay live
    assert c.value == 0 and h.count == 0
    c.inc()
    h.observe(1.0)
    assert reg.value("lane_calls_total", lane="cb") == 1
    assert reg.snapshot()["sections"]["warmup"] is snap


def test_prometheus_exposition_parses():
    reg = MetricsRegistry()
    reg.inc("lane_calls_total", 3, lane="cb")
    reg.set("pool_pages_free", 5.0)
    for v in (0.5, 1.5, 30.0):
        reg.observe("lane_step_ms", v, lane='c"b\\x')  # label escaping
    text = reg.to_prometheus()
    lines = text.strip().splitlines()
    types = {}
    for line in lines:
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
            continue
        assert not line.startswith("#")
        body, value = line.rsplit(" ", 1)
        float(value)  # every sample line ends in a number
    assert types == {
        "lane_calls_total": "counter",
        "pool_pages_free": "gauge",
        "lane_step_ms": "histogram",
    }
    # histogram export: cumulative buckets, +Inf == _count, _sum present
    bucket_lines = [l for l in lines if l.startswith("lane_step_ms_bucket")]
    cums = [int(l.rsplit(" ", 1)[1]) for l in bucket_lines]
    assert cums == sorted(cums)
    assert 'le="+Inf"' in bucket_lines[-1] and cums[-1] == 3
    assert any(l.startswith("lane_step_ms_sum") for l in lines)
    assert any(
        l.startswith("lane_step_ms_count") and l.endswith(" 3") for l in lines
    )


# ------------------------------------------------------------- trace exporter
def test_chrome_trace_schema(tmp_path):
    rec = FlightRecorder(capacity=64, enabled=True)
    rec.emit("rebind", "dispatcher", args={"key": "k"})
    rec.complete("lane_step", "lane:cb", t0_ns=rec.t0_ns)
    rec.counter("pool_occupancy", "page-pool", pages_in_use=3)
    trace = chrome_trace(rec)
    assert validate_trace(trace) == []
    tracks = {
        e["args"]["name"]
        for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {"dispatcher", "scheduler", "page-pool", "lane:cb"} <= tracks
    span = next(e for e in trace["traceEvents"] if e["ph"] == "X")
    assert "dur" in span and span["ts"] >= 0
    # round-trips through the file writer as valid JSON
    out = tmp_path / "trace.json"
    write_trace(str(out), rec)
    assert validate_trace(json.loads(out.read_text())) == []
    assert trace["otherData"]["emitted"] == 3


def test_validate_trace_flags_problems():
    assert validate_trace({"traceEvents": []}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                            "ts": 0.0}]}
    assert any("dur" in p for p in validate_trace(bad))


# ------------------------------------------------------------- serving stack
@pytest.fixture(scope="module")
def smoke_setup():
    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(n, tokens=4):
    return [
        Request(rid=i, first_token=1 + i, new_tokens=tokens, arrival_s=0.0)
        for i in range(n)
    ]


def test_continuous_report_derives_from_registry(smoke_setup):
    cfg, params = smoke_setup
    reset_entry_points()
    tel = Telemetry(enabled=True)
    eng = Engine(
        cfg, params, EngineConfig(max_len=32, batch_quantum=2, max_batch=2),
        telemetry=tel,
    )
    rep = run_continuous_stream(eng, _requests(4), slots=2)
    reg = tel.registry

    # latency_report's lane_calls IS the registry family (no parallel dict)
    assert rep["lane_calls"] == reg.labeled_values("lane_calls_total", "lane")
    assert rep["lane_calls"]["cb"] > 0

    # request-phase histograms cover every finished request
    for fam in ("queue_wait_ms", "ttft_ms", "request_latency_ms"):
        hist = reg.histogram(fam)
        assert hist.count == rep["finished"], fam
    assert reg.histogram("lane_step_ms", lane="cb").count > 0

    # warmup boundary: compiles happened, but all before the rollover
    assert rep["compiles_after_warmup"] == 0
    assert eng.post_warmup_compiles == 0
    assert "warmup" in reg.sections
    assert rep["compiles_total"] > 0

    # the flight recorder saw the full taxonomy on the dense stack
    names = {e.name for e in tel.recorder.events()}
    assert {"compile", "serve.dispatch.issue", "admit", "finish",
            "serve.dispatch.pull", "serve.batcher.step",
            "warm_boundary"} <= names

    # second stream on the same engine: the boundary rolls again, so the
    # new report reads only its own stream's counters
    first_cb = rep["lane_calls"]["cb"]
    rep2 = run_continuous_stream(eng, _requests(2), slots=2)
    assert rep2["compiles_after_warmup"] == 0
    assert 0 < rep2["lane_calls"]["cb"] < first_cb + 1
    assert rep2["lane_calls"] == reg.labeled_values(
        "lane_calls_total", "lane"
    )
    eng.close()


def test_disabled_engine_records_zero_events(smoke_setup):
    cfg, params = smoke_setup
    reset_entry_points()
    tel = Telemetry()  # recording disabled (production default)
    eng = Engine(
        cfg, params, EngineConfig(max_len=32, batch_quantum=2, max_batch=2),
        telemetry=tel,
    )
    rep = run_continuous_stream(eng, _requests(3), slots=2)
    assert rep["finished"] == 3
    assert len(tel.recorder) == 0 and tel.recorder.emitted == 0
    # ...but the always-on registry still backed the report
    assert rep["lane_calls"]["cb"] > 0
    eng.close()


def test_burst_report_parity(smoke_setup):
    cfg, params = smoke_setup
    reset_entry_points()
    tel = Telemetry()
    eng = Engine(
        cfg, params, EngineConfig(max_len=16, batch_quantum=2, max_batch=2),
        telemetry=tel,
    )
    rep = run_burst_stream(eng, _requests(2, tokens=3))
    # the burst engine reports through the same registry namespace
    assert rep["lane_calls"] == {"burst": 3}
    assert tel.registry.histogram("lane_step_ms", lane="burst").count == 3
    assert tel.registry.value("mode_switches_total") == rep["mode_switches"]
    eng.close()


def test_compile_report_per_dispatch_key(smoke_setup):
    cfg, params = smoke_setup
    reset_entry_points()
    tel = Telemetry(compile_analysis=True)
    eng = Engine(
        cfg, params, EngineConfig(max_len=16, batch_quantum=2, max_batch=2),
        telemetry=tel,
    )
    eng.continuous(slots=2)
    assert tel.compile_reports
    for rep in tel.compile_reports:
        assert rep["key"] and rep["lane"]
        assert rep["build_ms"] > 0
        assert "error" in rep or (rep["flops"] >= 0 and rep["bytes"] > 0)
    keys = [r["key"] for r in tel.compile_reports]
    assert len(keys) == len(set(keys))  # one report per DispatchKey
    eng.close()


def test_pull_host_emits_d2h_span():
    rec = FlightRecorder(capacity=16, enabled=True)
    out, dt_ns = pull_host(np.arange(6, dtype=np.int32).reshape(2, 3), rec)
    assert out.shape == (2, 3) and dt_ns >= 0
    (ev,) = rec.events()
    assert ev.name == "serve.dispatch.pull" and ev.ph == "X"
    assert ev.track == "scheduler" and ev.dur_ns >= dt_ns
    assert ev.args == {"bytes": out.nbytes}
    # disabled recorder: same result, no events
    out2, _ = pull_host(np.zeros(3), None)
    assert out2.shape == (3,)


def test_phase_span_lands_on_the_recorder_with_its_notes():
    rec = FlightRecorder(capacity=8, enabled=True)
    with span("serve.batcher.emit", rec, step=3) as sp:
        sp.note(tokens=2, finished=0)
    (ev,) = rec.events()
    assert ev.name == "serve.batcher.emit" and ev.ph == "X"
    assert ev.track == "scheduler" and ev.dur_ns >= 0
    assert ev.args == {"step": 3, "tokens": 2, "finished": 0}
    # with the recorder off and no profiler collecting: the shared no-op
    rec.enabled = False
    sp = span("serve.batcher.emit", rec, step=4)
    assert sp is NO_SPAN and span("serve.batcher.emit") is NO_SPAN
    with sp as inner:
        inner.note(tokens=1)
    assert rec.emitted == 1


def test_page_pool_events():
    from repro.runtime.kvcache import PagePool

    tel = Telemetry(enabled=True)
    pool = PagePool(2, 4, telemetry=tel)
    p0 = pool.alloc()
    p1 = pool.alloc()
    assert pool.alloc() is None  # dry pool
    pool.decref(p0)
    pool.decref(p1)
    names = [e.name for e in tel.recorder.events()]
    assert names.count("page_alloc") == 2
    assert names.count("page_free") == 2
    assert "alloc_failure" in names
    assert "pool_occupancy" in names
    occ = [e for e in tel.recorder.events() if e.name == "pool_occupancy"]
    assert occ[-1].args == {"pages_in_use": 0, "pages_free": 2}


def test_latency_report_registry_only_path():
    # the batcher-less burst path: lane_calls derived straight from a registry
    reg = MetricsRegistry()
    reg.inc("lane_calls_total", 7, lane="burst")
    rep = latency_report([], registry=reg)
    assert rep == {"finished": 0, "lane_calls": {"burst": 7}}
    assert latency_report([]) == {"finished": 0}
