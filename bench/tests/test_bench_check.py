"""The output check at a test size on the CPU: a sound run is correct, the
control and a broken timed path are not. The test model is float32, so its
control is the reference computed in bfloat16.

Each test drives the rest of a run (weights, engine, traffic, window,
drain, reference) with the harness's look for a chip skipped."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SEED = 2**31 + 101


def tiny_cell(**serving):
    config = json.loads((DATA / "tiny.json").read_text())
    config["serving"].update(serving)
    mix = json.loads((DATA / "tiny-traffic.json").read_text())
    spec = harness.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "olmo-1b.chat" in m.get("workloads", []):  # what the chat cell reads
            m["workloads"].append("tiny.chat")
    return harness.Cell("tiny.chat", 1, config, mix, spec, BENCH)


def serve(cell, **kw):
    return harness.serve_cell(
        cell, SEED, 1.5, kw.pop("trace", False), time.perf_counter(),
        require_tpu=False, **kw,
    )


@pytest.fixture(scope="module")
def sound():
    return serve(tiny_cell())


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["failed"] == 0 and sound["attempted"] > 10
    gap = sound["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert sound["info"]["compiles_in_window"] == 0
    # an open loop below its knee reports tails, not the rates it is offered
    assert set(sound["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert list(sound)[-1] == "checks"  # the compared numbers come last


@pytest.mark.parametrize("control", ["bf16", "int8"])
def test_lower_precision_control_fails_the_limit(control):
    """The control's tokens in the served tokens' place: the harness's own
    comparison reads false, while the program's gap on the same sample
    stays within the limit."""
    res = serve(tiny_cell(), control=control)
    gap = res["checks"]["logit_gap"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"] >= res["info"]["program_gap"]


def test_traced_run_reports_per_layer_metrics():
    res = serve(tiny_cell(), trace=True)
    assert res["correct"] is True
    for name in ("host_ms_per_step", "queue_wait_p90_ms",
                 "enqueue_ms_per_call", "pages_peak_pct", "batcher_idle_ms",
                 "dispatch_idle_ms", "cache_idle_ms"):
        assert res["metrics"][name]["value"] > 0, name
    assert 0 < res["metrics"]["pages_peak_pct"]["value"] <= 100
    assert res["device"]["window_s"] > 0
    assert "idle_gaps" in res["breakdown"]
    # no chip ops on the CPU: the layers' idle, times the steps, and the
    # idle outside every program span make up the whole slice
    idle = res["info"]["program_idle"]
    layers = sum(res["metrics"][f"{k}_idle_ms"]["value"]
                 for k in ("batcher", "dispatch", "cache"))
    assert layers * idle["steps"] / 1e3 + idle["none"] == pytest.approx(
        res["metrics"]["device_idle_pct.chat"]["value"] / 100
        * res["device"]["window_s"], rel=1e-9)


def alter_tokens(cb):
    """A token altered where it is produced: every decode step's sampled
    ids shift by one before the batcher emits them."""
    emit = cb._emit_decode
    vocab = 4096  # the test model's

    def bad(nxt_host, pos_host, now):
        return emit((np.asarray(nxt_host) + 1) % vocab, pos_host, now)

    cb._emit_decode = bad


def test_altered_token_is_caught():
    res = serve(tiny_cell(), fault=alter_tokens)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


SPLIT_SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {bench!r})
import harness
config = json.load(open({tiny!r}))
config["serving"]["disagg"] = "1x1@1"
mix = json.load(open({traffic!r}))
cell = harness.Cell("tiny.chat-split", 1, config, mix, harness.load_spec(),
                    harness.BENCH)

def drop_exchange(cb):
    # the pages a prompt filled on the prefill chip never reach the decode chip
    cb._transport = lambda src, dst, *a, **k: dst

faults = {{"none": None, "exchange": drop_exchange}}
res = harness.serve_cell(cell, {seed}, 1.5, False, time.perf_counter(),
                         require_tpu=False, fault=faults[sys.argv[1]])
print(json.dumps({{"correct": res["correct"], "checks": res["checks"]}}))
"""


@pytest.mark.parametrize("fault,expect", [("none", True), ("exchange", False)])
def test_split_cell_exchange_between_chips(tmp_path, fault, expect):
    script = tmp_path / "split.py"
    script.write_text(SPLIT_SCRIPT.format(
        bench=str(BENCH), tiny=str(DATA / "tiny.json"),
        traffic=str(DATA / "tiny-traffic.json"), seed=SEED,
    ))
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    }
    p = subprocess.run([sys.executable, str(script), fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is expect, res
