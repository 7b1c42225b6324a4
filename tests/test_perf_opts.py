"""Every §Perf knob must preserve model semantics (within dtype tolerance)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models, perf
from repro.configs import get_config

KEY = jax.random.PRNGKey(0)


def _logits(cfg, params, tok, opts):
    with perf.use_perf_opts(opts):
        out, _ = models.forward(cfg, params, tok, remat=False)
    return np.asarray(out, np.float32)


@pytest.fixture(scope="module")
def bf16_model():
    cfg = dataclasses.replace(get_config("gemma2-27b").smoke(), dtype="bfloat16")
    params = models.init_params(cfg, KEY)
    tok = jax.random.randint(jax.random.fold_in(KEY, 1), (2, 32), 0,
                             cfg.vocab_size)
    base = _logits(cfg, params, tok, perf.PerfOpts())
    return cfg, params, tok, base


@pytest.mark.parametrize(
    "opts,atol",
    [
        (perf.PerfOpts(impl="chunked"), 5e-2),
        (perf.PerfOpts(impl="chunked", attn_block=8), 5e-2),
        (perf.PerfOpts(score_dtype="bfloat16"), 2e-1),
        (perf.PerfOpts(probs_dtype="bfloat16"), 5e-2),
        (perf.PerfOpts(norm_bf16=True), 2e-1),
        (perf.PerfOpts(remat_policy="dots"), 5e-2),
    ],
    ids=["chunked", "chunked-small-block", "score-bf16", "probs-bf16",
         "norm-bf16", "remat-dots"],
)
def test_perf_opt_preserves_semantics(bf16_model, opts, atol):
    cfg, params, tok, base = bf16_model
    got = _logits(cfg, params, tok, opts)
    np.testing.assert_allclose(got, base, atol=atol)


def test_moe_hints_preserve_semantics():
    cfg = get_config("granite-moe-1b-a400m").smoke()
    params = models.init_params(cfg, KEY)
    tok = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    base = _logits(cfg, params, tok, perf.PerfOpts())
    for opts in (
        perf.PerfOpts(moe_hints=True),
        perf.PerfOpts(moe_hints=True, moe_weight_gather=True),
    ):
        got = _logits(cfg, params, tok, opts)
        np.testing.assert_allclose(got, base, atol=1e-4)


def test_chunked_equals_naive_all_attention_archs():
    for arch in ("olmo-1b", "gemma2-27b", "qwen3-14b", "musicgen-medium"):
        cfg = get_config(arch).smoke()
        params = models.init_params(cfg, KEY)
        if cfg.input_kind == "tokens":
            inp = jax.random.randint(KEY, (1, 32), 0, cfg.vocab_size)
        else:
            inp = jax.random.normal(KEY, (1, 32, cfg.d_model))
        base = _logits(cfg, params, inp, perf.PerfOpts())
        got = _logits(cfg, params, inp, perf.PerfOpts(impl="chunked",
                                                      attn_block=8))
        np.testing.assert_allclose(got, base, atol=1e-3, err_msg=arch)


def test_seq_fallback_semantics_on_mesh():
    """seq-shard fallback must not change results (subprocess, 8 devices)."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=os.path.join(repo, "src"),
    )
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro import models, perf
        from repro.configs import get_config, ShapeSpec
        from repro.runtime import steps

        # qwen3 family: heads (4) don't divide the model axis (8)
        cfg = dataclasses.replace(get_config('qwen3-14b').smoke(),
                                  num_heads=4, num_kv_heads=2)
        mesh = jax.make_mesh((1, 8), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeSpec('p', 'prefill', 32, 8)
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                 cfg.vocab_size)
        outs = []
        for opts in (None, perf.PerfOpts(seq_shard_fallback=True)):
            exe = steps.lower_for(cfg, mesh, shape, opts=opts).compile()
            logits, _ = exe(params, tok)
            outs.append(np.asarray(logits, np.float32))
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-4)
        print('OK')
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=900, cwd=repo,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
