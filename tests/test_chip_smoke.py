"""chip_smoke.py's phases, rehearsed on the CPU at olmo-1b.smoke() size.

The script itself refuses the CPU; these tests run its phase functions in
process (serve, stream checks, the reference check and the report fields)
and run the script only to see it refuse.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.runtime.scheduler import Request

REPO = Path(__file__).resolve().parents[1]

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", REPO / "chip_smoke.py"
)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

SMALL = dict(requests=6, prompt_len=24, tokens_mean=4, max_len=64)


def _small_config(**overrides):
    return chip_smoke.engine_config(
        max_len=SMALL["max_len"], max_batch=4, page_size=8, prefill_chunk=16,
        **overrides,
    )


@pytest.fixture(scope="module")
def model():
    cfg = get_config(chip_smoke.ARCH).smoke()
    return cfg, models.init_params(cfg, jax.random.PRNGKey(chip_smoke.SEED))


@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    reqs = chip_smoke.make_stream(cfg, **SMALL)
    report = chip_smoke.serve(cfg, params, _small_config(), reqs)
    return reqs, report


def test_stream_is_seeded_greedy_and_distinct(model):
    cfg, _ = model
    a = chip_smoke.make_stream(cfg, **SMALL)
    b = chip_smoke.make_stream(cfg, **SMALL)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert [r.new_tokens for r in a] == [r.new_tokens for r in b]
    assert all(r.greedy for r in a)
    assert len({r.prompt for r in a}) == len(a)
    assert all(len(r.prompt) == SMALL["prompt_len"] for r in a)
    assert all(
        len(r.prompt) + r.new_tokens <= SMALL["max_len"] for r in a
    )


def test_serve_report_fields(model, served):
    cfg, _ = model
    reqs, report = served
    chip_smoke.check_stream(cfg, reqs, report)
    assert report["finished"] == len(reqs)
    assert report["compiles_after_warmup"] == 0
    assert report["prefill_chunks"] > 0
    assert report["compile_s"] > 0
    assert 0 < report["warmup_s"] < report["wall_s"]
    assert report["tok_per_s"] > 0


def test_reference_check_passes_on_the_served_stream(model, served):
    cfg, params = model
    reqs, _ = served
    ref = chip_smoke.reference_check(cfg, params, reqs)
    assert ref["requests"] == sorted(r.rid for r in reqs)[: chip_smoke.CHECKED]
    checked = [r for r in reqs if r.rid in ref["requests"]]
    assert ref["positions"] == sum(len(r.tokens) for r in checked)
    # fp32 on the CPU: the engine and the reference agree to rounding
    assert ref["worst_margin_sigma"] < 1e-3
    assert ref["exact"] == ref["positions"]


def test_reference_check_catches_a_wrong_token(model, served):
    """A token the reference ranks last fails the check."""
    cfg, params = model
    reqs, _ = served
    bad = [
        Request(
            rid=r.rid, new_tokens=r.new_tokens, prompt=r.prompt,
            tokens=list(r.tokens),
        )
        for r in reqs
    ]
    r = min(bad, key=lambda r: r.rid)
    logits, _ = models.forward(
        cfg, params, np.asarray([list(r.prompt)], np.int32)
    )
    r.tokens[0] = int(np.argmin(np.asarray(logits)[0, -1]))
    with pytest.raises(AssertionError, match="deviations below"):
        chip_smoke.reference_check(cfg, params, bad)


def test_check_stream_refuses_a_short_stream(model, served):
    cfg, _ = model
    reqs, report = served
    with pytest.raises(AssertionError, match="requests finished"):
        chip_smoke.check_stream(cfg, reqs, dict(report, finished=1))
    with pytest.raises(AssertionError, match="compiles after warmup"):
        chip_smoke.check_stream(
            cfg, reqs, dict(report, compiles_after_warmup=1)
        )


def test_device_info_and_cpu_refusal():
    info = chip_smoke.device_info()
    assert info == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    if info["platform"] != "tpu":
        with pytest.raises(SystemExit, match="needs a TPU"):
            chip_smoke.require_tpu()


def _script_env(tmp_path):
    return dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        PYTHONPATH="",
    )


def test_script_refuses_the_cpu(tmp_path):
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=_script_env(tmp_path), capture_output=True, text=True,
        timeout=300, cwd=tmp_path,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        env=_script_env(tmp_path), capture_output=True, text=True,
        timeout=300, cwd=tmp_path,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_four_chip_arms_on_virtual_devices():
    """The --four-chips arms at smoke size on four virtual CPU devices:
    placements span the devices they name, and the 2x2 mesh and the
    1x1 + 1x1@1 split both pass the reference check."""
    code = textwrap.dedent(f"""
        import functools, importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(REPO / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro import models
        from repro.configs import get_config
        cfg = get_config(cs.ARCH).smoke()
        params = models.init_params(cfg, jax.random.PRNGKey(cs.SEED))
        small = dict(max_len=64, max_batch=4, page_size=8, prefill_chunk=16)
        cs.check_mesh_plans(cfg, cs.engine_config(mesh="2x2", **small))
        stream = functools.partial(
            cs.make_stream, cfg, requests=6, prompt_len=24, tokens_mean=4,
            max_len=64)
        for name, ecfg, disagg in (
            ("2x2", cs.engine_config(mesh="2x2", **small), None),
            ("disagg", cs.engine_config(meshes=("1x1@1",), **small),
             "1x1@1"),
        ):
            rep = cs.run_arm(name, cfg, params, ecfg, stream, disagg=disagg)
            assert rep["mesh"] == ("2x2" if disagg is None else "1x1")
            assert rep["reference"]["worst_margin_sigma"] < 1e-3
        assert rep["migrations"] > 0  # the split moved KV pages
        print("OK")
    """)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=str(REPO / "src"),
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=900, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
