"""Multi-device integration: run in subprocesses with fake host devices
(XLA_FLAGS must be set before jax initialises, so these can't share the
pytest process, which deliberately sees 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.path.join(REPO, "src"),
    )
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """Loss on a (4,2) mesh == loss on 1 device (same params/batch)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import models
        from repro.configs import get_config, ShapeSpec
        from repro.runtime import steps
        from repro.optim import adamw
        from repro.distributed import sharding as shd

        cfg = get_config('olmo-1b').smoke()
        mesh = jax.make_mesh((4, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeSpec('t', 'train', 32, 8)
        lowered = steps.lower_for(cfg, mesh, shape, donate=False)
        exe = lowered.compile()

        params = models.init_params(cfg, jax.random.PRNGKey(0))
        state = steps.TrainState(params=params, opt=adamw.init(params))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                 cfg.vocab_size)
        batch = {'inputs': tok, 'labels': tok}
        _, m_sharded = exe(state, batch)

        step1 = jax.jit(steps.make_train_fn(cfg))
        _, m_single = step1(state, batch)
        print('SHARDED', float(m_sharded['loss']))
        print('SINGLE', float(m_single['loss']))
        np.testing.assert_allclose(float(m_sharded['loss']),
                                   float(m_single['loss']), rtol=2e-4)
        print('OK')
    """)
    assert "OK" in out


def test_decode_step_sharded_cache():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import models
        from repro.configs import get_config, ShapeSpec
        from repro.runtime import steps

        cfg = get_config('gemma2-27b').smoke()
        mesh = jax.make_mesh((4, 2), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeSpec('d', 'decode', 32, 8)
        exe = steps.lower_for(cfg, mesh, shape, donate=False).compile()
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        cache = models.init_cache(cfg, 8, 32)
        tok = jnp.zeros((8, 1), jnp.int32)
        logits, new_cache = exe(params, cache, tok, jnp.int32(3))
        ref_logits, _ = models.decode_step(cfg, params, cache, tok,
                                           jnp.int32(3))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits), atol=2e-4)
        print('OK')
    """)
    assert "OK" in out


def test_compressed_psum_int8_wire():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_psum

        mesh = jax.make_mesh((8,), ('pod',),
                             axis_types=(jax.sharding.AxisType.Auto,))
        @jax.jit
        def f(x):
            return jax.shard_map(
                lambda s: compressed_psum(s, 'pod'),
                mesh=mesh, in_specs=P('pod'), out_specs=P('pod'),
            )(x)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        got = f(x)
        want = jnp.broadcast_to(x.sum(0), (8, 64)).reshape(8, 64)
        # int8 quantisation error bound: 8 shards * half-step each
        step = float(jnp.max(jnp.abs(x))) / 127
        assert float(jnp.max(jnp.abs(got.reshape(8,64) - jnp.tile(x.sum(0), (8,1))))) <= 8 * step
        # the wire really is int8
        txt = f.lower(x).compile().as_text()
        assert 's8[' in txt and 'all-gather' in txt
        print('OK')
    """)
    assert "OK" in out


def test_multipod_mesh_axes():
    out = _run("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh(multi_pod=True)
        assert m.axis_names == ('pod', 'data', 'model')
        assert m.devices.shape == (2, 16, 16)
        m1 = make_production_mesh()
        assert m1.axis_names == ('data', 'model')
        assert m1.devices.shape == (16, 16)
        print('OK')
    """, devices=512)
    assert "OK" in out


def test_dryrun_cell_end_to_end_small_arch(tmp_path):
    """The actual dry-run entry point, production mesh, real arch. The
    output directory is fresh: the dry run skips cells it finds on disk."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src")
    )
    out = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "mamba2-370m", "--shape", "decode_32k",
            "--mesh", "multi", "--out", str(tmp_path),
            "--tag", "pytest",
        ],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(
        (tmp_path / "mamba2-370m--decode_32k--multi-pytest.json").read_text()
    )
    assert rec["status"] == "ok"
    assert rec["chips"] == 512
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")


def test_elastic_remesh_checkpoint_restore():
    """Save on an 8-device mesh, restore + re-place on a 4-device mesh
    (simulating the loss of half the fleet), continue training."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import models
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.configs import get_config, ShapeSpec
        from repro.optim import adamw
        from repro.runtime import steps

        cfg = get_config('olmo-1b').smoke()
        big = jax.make_mesh((4, 2), ('data', 'model'),
                            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        small = jax.make_mesh((2, 2), ('data', 'model'),
                              axis_types=(jax.sharding.AxisType.Auto,) * 2,
                              devices=jax.devices()[:4])

        params = models.init_params(cfg, jax.random.PRNGKey(0))
        state = steps.TrainState(params=params, opt=adamw.init(params))
        state_big = steps.place_train_state(cfg, state, big)

        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                 cfg.vocab_size)
        batch = {'inputs': tok, 'labels': tok}
        exe_big = steps.lower_for(
            cfg, big, ShapeSpec('t', 'train', 32, 8), donate=False).compile()
        state_big, m1 = exe_big(state_big, batch)

        mgr = CheckpointManager('/tmp/elastic-ck', async_write=False)
        mgr.save(1, state_big)
        _, restored = mgr.restore(jax.eval_shape(lambda: state))
        state_small = steps.place_train_state(cfg, restored, small)
        exe_small = steps.lower_for(
            cfg, small, ShapeSpec('t', 'train', 32, 8), donate=False).compile()
        state_small, m2 = exe_small(state_small, batch)
        assert np.isfinite(float(m2['loss']))
        # the re-meshed continuation matches a never-interrupted run
        step1 = jax.jit(steps.make_train_fn(cfg))
        s_ref = steps.TrainState(params=params, opt=adamw.init(params))
        s_ref, _ = step1(s_ref, batch)
        _, m_ref = step1(s_ref, batch)
        np.testing.assert_allclose(float(m2['loss']), float(m_ref['loss']),
                                   rtol=2e-4)
        print('OK')
    """)
    assert "OK" in out
