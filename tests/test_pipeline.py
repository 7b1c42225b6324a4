"""Pipeline parallelism: shard_map GPipe == sequential oracle (subprocess)."""

import os
import subprocess
import sys
import textwrap

from repro.distributed.pipeline import bubble_fraction

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == 3 / 15
    assert bubble_fraction(1, 8) == 0.0


def test_pipeline_matches_sequential_oracle():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.join(REPO, "src"),
    )
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import (
            pipeline_forward, reference_forward)

        mesh = jax.make_mesh((4,), ('stage',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        S, M, mb, d = 4, 8, 2, 16

        def stage_fn(sp, x):
            return jnp.tanh(x @ sp['w'] + sp['b'])

        key = jax.random.PRNGKey(0)
        params = {
            'w': jax.random.normal(key, (S, d, d)) * 0.3,
            'b': jax.random.normal(jax.random.fold_in(key, 1), (S, d)) * 0.1,
        }
        # shard_map slices the stage-major [S, ...] leaves to [1, ...]
        x = jax.random.normal(jax.random.fold_in(key, 2), (M, mb, d))
        got = pipeline_forward(stage_fn, params, x, mesh=mesh)
        want = reference_forward(stage_fn, params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        print('OK')
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
