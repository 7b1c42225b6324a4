"""The harness finds every configuration, mix and per-layer metric by name,
a new one needs only new files, and a run refuses anything but a TPU."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.-")


def test_every_cell_resolves_with_its_files():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        cell = harness.resolve(spec, w["name"])
        assert cell.chips in (1, 4)
        assert {"num_layers", "d_model", "context", "dtype"} <= set(cell.model)
        assert cell.mix["arrivals"]["process"] in ("poisson", "gamma", "closed")
        assert cell.config["limits"]["logit_gap"] > 0
        assert (BENCH / "reference" / f"{cell.config['reference']}.py").exists()
        assert cell.per_layer(), w["name"]


def test_every_per_layer_metric_has_a_reader():
    spec = harness.load_spec()
    for m in spec["per_layer"]:
        assert callable(harness.load_reader(BENCH, m["name"]))


def test_spec_names_and_keys_keep_to_the_contract():
    spec = harness.load_spec()
    assert set(spec) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert set(n) <= NAME_CHARS and len(n) <= 64
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
        for w in m["workloads"]:  # each cell it reads in reports what it moves
            assert w in next(e.get("workloads", cells) for e in spec["end_to_end"]
                             if e["name"] == m["moves"]), (m["name"], w)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def _digest(root: Path) -> dict:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file()
    }


def test_a_new_configuration_mix_and_metric_are_new_files_only(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(bench)
    data = BENCH / "tests" / "data"
    shutil.copy(data / "tiny.json", bench / "configs" / "tiny.json")
    shutil.copy(data / "tiny-traffic.json", bench / "traffic" / "tiny-chat.json")
    (bench / "metrics" / "admitted_in_window.py").write_text(
        "def read(run):\n"
        "    w0, w1 = run.window\n"
        "    return sum(1 for s in run.served\n"
        "               if s.admitted is not None and w0 <= s.admitted < w1)\n"
    )
    spec = harness.load_spec()
    spec["configs"].append({
        "name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
        "reduced": [], "why": "test",
    })
    spec["workloads"].append({
        "name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
        "chips": 1, "why": "test",
    })
    spec["per_layer"].append({
        "name": "admitted_in_window", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "batcher (runtime/scheduler.py)",
        "moves": "ttft_p90_ms", "workloads": ["tiny.chat"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(bench)
    assert all(after[k] == v for k, v in before.items())  # nothing edited
    cell = harness.resolve(
        harness.load_spec(tmp_path), "tiny.chat", root=tmp_path, bench=bench
    )
    assert cell.model["d_model"] == 64
    assert cell.mix["arrivals"]["rate_hz"] == 40.0
    assert "admitted_in_window" in [m["name"] for m in cell.per_layer()]
    assert harness.load_reader(bench, "admitted_in_window") is not None


def test_a_bursty_shared_prefix_mix_is_a_new_file_only(tmp_path):
    """Gamma arrivals and a shared system prompt, written as one new mix
    file, run through the harness as it stands and reuse cached pages."""
    import time

    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(bench)
    mix = json.loads((BENCH / "tests" / "data" / "tiny-traffic.json").read_text())
    mix.update(arrivals={"process": "gamma", "rate_hz": 40.0, "cv": 3.0},
               prefix={"tokens": 32, "groups": 2})
    (bench / "traffic" / "tiny-sessions.json").write_text(json.dumps(mix))
    shutil.copy(BENCH / "tests" / "data" / "tiny.json", bench / "configs")
    spec = harness.load_spec()
    spec["configs"].append({
        "name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
        "reduced": [], "why": "test",
    })
    spec["workloads"].append({
        "name": "tiny.sessions", "config": "tiny", "traffic": "tiny-sessions",
        "chips": 1, "why": "test",
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(_digest(bench)[k] == v for k, v in before.items())
    cell = harness.resolve(harness.load_spec(tmp_path), "tiny.sessions",
                           root=tmp_path, bench=bench)
    res = harness.serve_cell(cell, 2**31 + 77, 1.5, False, time.perf_counter(),
                             require_tpu=False)
    assert res["correct"] is True and res["failed"] == 0
    assert res["info"]["prefix_hit_tokens_in_window"] > 0


def _run(args, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300,
    )


def test_run_refuses_a_cpu():
    p = _run(["bench/run.py", "--workload", "olmo-1b.chat", "--seed",
              str(2**31 + 3), "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout


def test_run_needs_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(["bench/run.py", "--workload", "olmo-1b.chat", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("flag", ["--workload", "--seed", "--seconds"])
def test_run_requires_its_arguments(flag):
    args = {"--workload": "olmo-1b.chat", "--seed": "1", "--seconds": "1"}
    del args[flag]
    argv = [x for kv in args.items() for x in kv]
    p = _run(["bench/run.py", *argv], ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


# Each benchmark configuration's ArchConfig as the harness built it from a
# fixed list of dense keys, before it took every field from the file.
BUILT_BEFORE = {
    "olmo-1b": dict(
        family="dense", num_layers=16, d_model=2048, num_heads=16,
        num_kv_heads=16, head_dim=128, d_ff=8192, vocab_size=50304,
        norm="ln_nonparam", norm_eps=1e-05, rope_theta=10000.0,
        tie_embeddings=True, act="silu", dtype="bfloat16",
    ),
    "deepseek-67b": dict(
        family="dense", num_layers=4, d_model=8192, num_heads=64,
        num_kv_heads=8, head_dim=128, d_ff=22016, vocab_size=102400,
        norm="rmsnorm", norm_eps=1e-06, rope_theta=10000.0,
        tie_embeddings=False, act="silu", dtype="bfloat16",
    ),
}


@pytest.mark.parametrize("name", sorted(BUILT_BEFORE))
def test_arch_config_is_field_for_field_as_before(name):
    from repro.configs import ArchConfig

    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert harness.arch_config(config) == ArchConfig(
        name=name, **BUILT_BEFORE[name]
    )
    ecfg = harness.engine_config(config)
    assert ecfg.moe_policy == "drop"  # the program's default, untouched
    assert ecfg.max_len == config["model"]["context"]


def test_patterned_file_reaches_the_program():
    config = json.loads((BENCH / "tests" / "data" / "tiny-patterned.json").read_text())
    arch = harness.arch_config(config)
    assert arch.layer_pattern == ("attn_local",) * 3 + ("attn",)
    assert arch.mlp_pattern == ("moe",) and arch.period == 4
    assert (arch.sliding_window, arch.num_experts, arch.top_k,
            arch.expert_d_ff) == (24, 4, 2, 32)
    assert harness.engine_config(config).moe_policy == "dense"


def test_an_unknown_model_key_is_refused_by_name():
    config = json.loads((BENCH / "tests" / "data" / "tiny.json").read_text())
    config["model"]["slidng_window"] = 24
    with pytest.raises(ValueError, match="slidng_window"):
        harness.arch_config(config)
