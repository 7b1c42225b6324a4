"""A patterned decoder served through the harness on the CPU: three
windowed layers and a full one, every layer routed over 4 experts (the
layout of Mellum 2 at a test size). The configuration file alone carries
the pattern into the engine, the weights, the counts and the reference; a
sound run is correct, and the control and a program that serves another
window or another top-k are not."""

import dataclasses
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402

SEED = 2**31 + 211


def patterned_cell():
    config = json.loads((DATA / "tiny-patterned.json").read_text())
    mix = json.loads((DATA / "tiny-traffic.json").read_text())
    spec = harness.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "olmo-1b.chat" in m.get("workloads", []):  # what the chat cell reads
            m["workloads"].append("tiny-patterned.chat")
    return harness.Cell("tiny-patterned.chat", 1, config, mix, spec, BENCH)


def serve(**kw):
    return harness.serve_cell(
        patterned_cell(), SEED, 1.5, kw.pop("trace", False),
        time.perf_counter(), require_tpu=False, **kw,
    )


def test_sound_run_is_correct():
    res = serve()
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 10
    assert res["info"]["compiles_in_window"] == 0
    # prompts up to 96 tokens: the 24-token window is crossed
    assert res["info"]["checked_positions"] > 200


@pytest.mark.parametrize("control", ["bf16", "int8"])
def test_lower_precision_control_fails_the_limit(control):
    res = serve(control=control)
    gap = res["checks"]["logit_gap"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"] >= res["info"]["program_gap"]


@pytest.mark.parametrize("fault", [
    {"sliding_window": None},  # every windowed layer attends the whole context
    {"top_k": 1},  # each token through one expert, not two
])
def test_a_program_serving_another_pattern_is_caught(monkeypatch, fault):
    arch_config = harness.arch_config
    monkeypatch.setattr(
        harness, "arch_config",
        lambda config: dataclasses.replace(arch_config(config), **fault),
    )
    res = serve()
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_traced_run_reports_every_per_layer_metric(monkeypatch):
    """The CPU has no device plane, so each step gets one decode and one
    prefill call planted in the trace; the harness then reads every
    per-layer metric of the cell, its work counted by layer kind."""
    read_xplane = devtrace.read_xplane

    def with_calls(path):
        ev = read_xplane(path)
        for _, s0, s1 in (e for e in ev.host if e[0] == "bench.step"):
            d = (s1 - s0) / 4
            calls = [(f"jit_{harness.DECODE_MODULE}", s0 + d, s0 + 2 * d),
                     (f"jit_{harness.PREFILL_MODULE}", s0 + 2 * d, s0 + 3 * d)]
            ev.modules.setdefault(0, []).extend(calls)
            ev.ops.setdefault(0, []).extend(calls)
        return ev

    monkeypatch.setattr(devtrace, "read_xplane", with_calls)
    monkeypatch.setattr(harness, "device_info", lambda chips, require_tpu: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    })
    cell = patterned_cell()
    res = harness.serve_cell(cell, SEED, 1.5, True, time.perf_counter())
    assert res["correct"] is True
    wanted = {m["name"] for m in cell.per_layer()}
    assert set(res["metrics"]) == wanted
    for name, m in res["metrics"].items():
        assert m["value"] > 0, name
    # the idle by layer, times the steps, and outside every span: the idle
    idle = res["info"]["program_idle"]
    total = sum(
        res["metrics"][f"{k}_idle_ms"]["value"] * idle["steps"] / 1e3
        for k in ("batcher", "dispatch", "cache")
    ) + idle["none"]
    share = res["metrics"]["device_idle_pct.chat"]["value"] / 100
    assert total == pytest.approx(share * res["device"]["window_s"], rel=1e-9)
