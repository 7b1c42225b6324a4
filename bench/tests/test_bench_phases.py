"""The program's phase spans, read from a profiler trace of the paged batcher
served on the CPU, and the attribution of idle chip time to their layers,
on hand-made events. The recorded chip trace's ``bench.*`` breakdown is
pinned, so reading program spans beside it changes none of its numbers."""

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import phases  # noqa: E402

RECORDED = BENCH / "testdata" / "trace_olmo_chat.json"
# two steps of deepseek-67b.code on a v5e chip, with the program's spans
RECORDED_PHASES = BENCH / "testdata" / "trace_code_phases.json"

# The direct children of one synchronous paged step, by phase: the lane
# plan, then (while some slot prefills) the chunk plan, its page
# reservation, the chunk call and its bookkeeping; the decode slots' page
# upkeep; then (while some slot decodes) the decode call, its three pulls
# and the token emit.
STEP = re.compile(
    r"^batcher\.plan"
    r"( batcher\.plan cache\.reserve batcher\.pack dispatch\.upload"
    r" dispatch\.issue dispatch\.issue dispatch\.pull batcher\.commit)?"
    r" cache\.upkeep"
    r"( batcher\.pack dispatch\.upload dispatch\.issue"
    r" dispatch\.pull dispatch\.pull dispatch\.pull batcher\.emit)?$"
)


@pytest.fixture(scope="module")
def engine():
    import jax

    from repro import models
    from repro.configs import get_config
    from repro.core import reset_entry_points
    from repro.runtime.serve import Engine, EngineConfig

    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    reset_entry_points()
    eng = Engine(cfg, params, EngineConfig(
        max_len=64, batch_quantum=2, max_batch=4, page_size=8,
        num_pages=40, prefill_chunk=16,
    ))
    yield eng
    eng.close()


def _requests(vocab):
    """Six prompts, the later three repeating the earlier ones' first 16
    tokens, so the prefix cache publishes and lends pages."""
    from repro.runtime.scheduler import Request

    rng = np.random.default_rng(7)
    heads = [
        tuple(int(t) for t in rng.integers(0, vocab, 16)) for _ in range(3)
    ]
    out = []
    for i in range(6):
        tail = tuple(int(t) for t in rng.integers(0, vocab, 8 + 4 * i))
        out.append(Request(rid=i, new_tokens=5, greedy=True, arrival_s=0.0,
                           prompt=heads[i % 3] + tail))
    return out


def _serve(eng, requests):
    """Admit in two waves and step until every request finishes."""
    cb = eng.paged_continuous(slots=4)
    waiting = list(requests)
    while waiting or cb.has_work:
        if waiting and cb.free_slots:
            batch, waiting = waiting[:cb.free_slots], waiting[cb.free_slots:]
            waiting = cb.admit(batch) + waiting
        cb.step()
    return [list(r.tokens) for r in requests]


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    import jax

    reqs = _requests(engine.cfg.vocab_size)
    d = tmp_path_factory.mktemp("phases")
    tel = engine.telemetry
    tel.enable()  # both sinks at once: the profiler and the recorder
    jax.profiler.start_trace(str(d))
    try:
        tokens = _serve(engine, reqs)
    finally:
        jax.profiler.stop_trace()
        tel.disable()
    recorded = [
        e for e in tel.recorder.events() if e.name.startswith("serve.")
    ]
    tel.recorder.clear()
    return tokens, phases.read_program(devtrace.find_xplane(d)), recorded


def _children(parent, spans):
    """The spans directly inside ``parent``, in order."""
    _, p0, p1 = parent
    inside = [
        e for e in spans if e is not parent and p0 <= e[1] and e[2] <= p1
    ]
    return [e for e in inside if not any(
        o is not e and o[1] <= e[1] and e[2] <= o[2] for o in inside
    )]


def test_every_span_names_a_known_layer(traced):
    _, spans, _ = traced
    assert spans
    for name, s, e in spans:
        assert phases.layer_of(name) in phases.LAYERS
        assert e >= s


def test_spans_nest_and_each_step_runs_its_phases_in_order(traced):
    _, spans, _ = traced
    for a in spans:  # one host thread: spans nest or are disjoint
        for b in spans:
            overlap = a[1] < b[2] and b[1] < a[2]
            nested = (a[1] <= b[1] and b[2] <= a[2]) or (
                b[1] <= a[1] and a[2] <= b[2]
            )
            assert not overlap or nested, (a, b)
    steps = [e for e in spans if e[0] == phases.STEP]
    assert len(steps) > 5
    shapes = set()
    for step in steps:
        seq = " ".join(n[len("serve."):] for n, _, _ in _children(step, spans))
        assert STEP.match(seq), seq
        shapes.add(("commit" in seq, "emit" in seq))
    assert {(True, True), (False, True)} <= shapes
    # the prefix cache publishes under the prefill commit or the emit
    for pub in (e for e in spans if e[0] == "serve.cache.publish"):
        parent = min((e for e in spans if e[1] <= pub[1] and pub[2] <= e[2]
                      and e is not pub), key=lambda e: e[2] - e[1])
        assert parent[0] in ("serve.batcher.commit", "serve.batcher.emit")
    # admission looks up the prefix cache and reserves pages inside its span
    admits = [e for e in spans if e[0] == "serve.batcher.admit"]
    assert admits
    inner = {n for a in admits for n, _, _ in _children(a, spans)}
    assert {"serve.cache.match", "serve.cache.reserve"} <= inner


def test_token_streams_are_bitwise_equal_with_the_profiler_on_and_off(
    engine, traced
):
    tokens, _, _ = traced
    assert tokens == _serve(engine, _requests(engine.cfg.vocab_size))
    assert all(len(t) == 5 for t in tokens)


def test_the_recorder_holds_the_same_phases_as_the_profiler(traced):
    _, spans, recorded = traced
    assert all(e.ph == "X" and e.track == "scheduler" for e in recorded)
    def order(xs):  # parents before the children they open with
        return [n for n, _, _ in sorted(xs, key=lambda x: (x[1], -x[2]))]

    assert order([(e.name, e.ts_ns, e.dur_ns) for e in recorded]) == order(
        [(n, s, e - s) for n, s, e in spans]
    )
    steps = [e for e in recorded if e.name == phases.STEP]
    assert [e.args["step"] for e in steps] == list(range(len(steps)))


def test_nothing_is_recorded_with_the_profiler_and_recorder_off(engine):
    from repro.core.telemetry import NO_SPAN, Telemetry, span

    assert span("serve.batcher.step") is NO_SPAN
    assert Telemetry().span("serve.batcher.step", step=1) is NO_SPAN
    _serve(engine, _requests(engine.cfg.vocab_size)[:2])
    assert engine.telemetry.recorder.emitted == 0


def test_idle_time_goes_to_the_layer_of_the_innermost_span():
    ops = {0: [("op", 0.0, 1.0), ("op", 4.0, 5.0), ("op", 9.0, 10.0)]}
    program = [
        ("serve.batcher.step", 0.5, 8.0),
        ("serve.batcher.plan", 1.0, 2.0),
        ("serve.cache.upkeep", 2.0, 3.5),
        ("serve.dispatch.issue", 3.5, 4.5),
        ("serve.dispatch.pull", 4.5, 5.0),
        ("serve.batcher.emit", 5.0, 6.0),
    ]
    got = phases.program_idle(ops, program, [0], 0.0, 10.0)
    # idle: [1, 4] and [5, 9]; [1, 2] plan, [2, 3.5] upkeep, [3.5, 4]
    # issue, [5, 6] emit, [6, 8] the step itself, [8, 9] outside any span
    assert got == pytest.approx({
        "batcher": 1.0 + 1.0 + 2.0, "cache": 1.5, "dispatch": 0.5,
        "none": 1.0, "steps": 1,
    })
    idle = 10.0 - devtrace.busy_seconds(ops[0], 0.0, 10.0)
    assert sum(got[k] for k in (*phases.LAYERS, "none")) == pytest.approx(idle)
    host = phases.program_host(program, 0.0, 10.0)
    assert host == pytest.approx({
        "serve.batcher.step": 0.5 + 2.0, "serve.batcher.plan": 1.0,
        "serve.cache.upkeep": 1.5, "serve.dispatch.issue": 1.0,
        "serve.dispatch.pull": 0.5, "serve.batcher.emit": 1.0, "steps": 1,
    })


def test_attribution_by_layer_sums_to_the_idle_time_on_many_gaps():
    rng = np.random.default_rng(3)
    t, ops, program = 0.0, [], []
    for _ in range(200):  # steps of host phases, device work in between
        step0 = t
        for name in ("serve.batcher.plan", "serve.cache.upkeep",
                     "serve.dispatch.issue", "serve.batcher.emit"):
            d = float(rng.uniform(1e-4, 1e-3))
            program.append((name, t, t + d))
            t += d
        dev = float(rng.uniform(1e-3, 5e-3))
        ops.append(("op", t - 1e-4, t + dev))
        t += dev
        program.append(("serve.batcher.step", step0, t))
        t += float(rng.uniform(0, 1e-3))
    program.sort(key=lambda e: e[1])
    lo, hi = 0.0, t
    got = phases.program_idle({0: ops, 1: ops}, program, [0, 1], lo, hi)
    idle = hi - lo - devtrace.busy_seconds(ops, lo, hi)
    total = sum(got[k] for k in (*phases.LAYERS, "none"))
    assert total == pytest.approx(idle, rel=1e-9)
    assert got["steps"] == 200 and min(got.values()) > 0
    # the overlap filter gives what attribute_gaps gives over every span
    gaps = devtrace.idle_gaps(ops, lo, hi)
    full = devtrace.attribute_gaps(gaps, program)
    by_layer = dict.fromkeys((*phases.LAYERS, "none"), 0.0)
    for name, sec in full.items():
        by_layer["none" if name == "none" else phases.layer_of(name)] += sec
    for k, v in by_layer.items():
        assert got[k] == pytest.approx(v, rel=1e-9)


def test_layer_of_refuses_other_names():
    for bad in ("bench.step", "serve.step", "serve.planner.plan",
                "serve.batcher.plan.x"):
        with pytest.raises(ValueError):
            phases.layer_of(bad)


def test_recorded_trace_breakdown_is_unchanged():
    """The ``bench.*`` idle breakdown and busy time of the recorded chip
    trace, as the benchmark has computed them since it was recorded."""
    ev = devtrace.load_json(RECORDED)
    steps = [e for e in ev.host if e[0] == "bench.step"]
    lo, hi = steps[0][1], steps[-1][2]
    busy = devtrace.busy_seconds(ev.ops[0], lo, hi)
    gaps = devtrace.attribute_gaps(devtrace.idle_gaps(ev.ops[0], lo, hi),
                                   ev.host)
    assert len(steps) == PINNED["steps"]
    assert hi - lo == pytest.approx(PINNED["window_s"], rel=1e-12)
    assert busy == pytest.approx(PINNED["busy_s"], rel=1e-12)
    assert gaps == pytest.approx(PINNED["idle_gaps"], rel=1e-9)
    # a trace without program spans reads no program idle time
    got = phases.program_idle(ev.ops, [], [0], lo, hi)
    assert got["none"] == pytest.approx(hi - lo - busy)
    assert got["steps"] == 0


PINNED = {
    "steps": 2,
    "window_s": 0.665236238,
    "busy_s": 0.321985754,
    "idle_gaps": {"bench.step": 0.34277575399999993,
                  "none": 0.0004747300000000343},
}


def test_recorded_chip_trace_puts_its_idle_time_down_by_layer():
    ev = devtrace.load_json(RECORDED_PHASES)
    saved = json.loads(RECORDED_PHASES.read_text())
    program = [tuple(e) for e in saved["program"]]
    steps = [e for e in ev.host if e[0] == "bench.step"]
    lo, hi = steps[0][1], steps[-1][2]
    idle = hi - lo - devtrace.busy_seconds(ev.ops[0], lo, hi)
    got = phases.program_idle(ev.ops, program, [0], lo, hi)
    assert got["steps"] == 2
    assert sum(got[k] for k in (*phases.LAYERS, "none")) == pytest.approx(
        idle, rel=1e-9
    )
    # the cache manager's chunk reservation leads, then the device calls
    assert got == pytest.approx({
        "batcher": 0.001446840999999477, "dispatch": 0.014324915999997856,
        "cache": 0.017087647999999955, "none": 0.0004433200000000248,
        "steps": 2,
    }, rel=1e-9)
    for step in (e for e in program if e[0] == phases.STEP):
        seq = " ".join(
            n[len("serve."):] for n, _, _ in _children(step, program)
        )
        assert STEP.match(seq), seq


def test_probe_adds_the_program_slice_to_a_traced_run(tmp_path):
    """The traced run on the CPU: no chip ops, so the whole slice is idle,
    and the idle time by layer adds up to it."""
    import harness

    sys.path.insert(0, str(BENCH / "tests"))
    from test_bench_check import SEED, tiny_cell

    save = tmp_path / "steps.json"
    res = phases.probe(tiny_cell(), SEED, 1.5, save=str(save),
                       require_tpu=False)
    assert harness._reduce_trace.__name__ == "_reduce_trace"  # restored
    assert res["correct"] is True
    idle, host = res["program_idle"], res["program_host"]
    assert idle["steps"] > 0 and host["steps"] == idle["steps"]
    window = res["device"]["window_s"]
    assert sum(idle[k] for k in (*phases.LAYERS, "none")) == pytest.approx(
        window, rel=1e-9
    )
    assert idle["batcher"] > 0 and idle["dispatch"] > 0 and idle["cache"] > 0
    assert 0 < save.stat().st_size <= phases.SAVE_BYTES
    saved = devtrace.load_json(save)
    assert {e[0] for e in saved.host} == {"bench.step"}
    program = json.loads(save.read_text())["program"]
    assert {phases.STEP, "serve.dispatch.issue"} <= {e[0] for e in program}
