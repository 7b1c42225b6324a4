"""Sharding rules: every assigned arch resolves on the production meshes.

Uses AbstractMesh (no devices needed) to validate the rule system: every
param/cache spec must respect divisibility, use each mesh axis at most once
per tensor, and give the big weights both a TP and an FSDP dim whenever the
arch's dims divide.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import models
from repro.configs import ASSIGNED, get_config
from repro.distributed import sharding as shd
from repro.runtime import steps

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axes_of(spec_entry):
    if spec_entry is None:
        return ()
    if isinstance(spec_entry, str):
        return (spec_entry,)
    return tuple(spec_entry)


def _check_tree(spec_tree, shape_tree, mesh):
    leaves_spec = jax.tree.leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P)
    )
    leaves_shape = jax.tree.leaves(shape_tree)
    assert len(leaves_spec) == len(leaves_shape)
    for spec, leaf in zip(leaves_spec, leaves_shape):
        used = []
        for dim, entry in enumerate(spec):
            axes = _axes_of(entry)
            for a in axes:
                assert a in mesh.axis_names, (spec, leaf.shape)
                used.append(a)
            if axes:
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                assert leaf.shape[dim] % size == 0, (
                    spec, leaf.shape, dim, size,
                )
        assert len(used) == len(set(used)), f"axis reused: {spec}"


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
def test_param_specs_valid(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))
    )
    specs = shd.param_pspec_tree(shapes, mesh)
    _check_tree(specs, shapes, mesh)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_cache_specs_valid(arch):
    cfg = get_config(arch)
    for batch, seq in ((128, 32768), (1, 524288)):
        shapes = jax.eval_shape(
            lambda: models.init_cache(cfg, batch, seq)
        )
        specs = shd.cache_pspec_tree(cfg, shapes, MULTI)
        _check_tree(specs, shapes, MULTI)


def test_big_weights_get_tp_and_fsdp():
    cfg = get_config("deepseek-67b")
    shapes = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))
    )
    specs = shd.param_pspec_tree(shapes, SINGLE)
    mlp = list(specs["blocks"][0]["mlp"]["w_gate"])  # [m, D, F]
    assert "model" in mlp and "data" in mlp


def test_qwen3_heads_fall_back_to_replicated():
    """40 heads don't divide 16 -> attention weights keep FSDP only."""
    cfg = get_config("qwen3-14b")
    shapes = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))
    )
    specs = shd.param_pspec_tree(shapes, SINGLE)
    wq = specs["blocks"][0]["attn"]["wq"]  # [m, D, H=40, dh]
    flat = list(wq)
    assert "model" not in [a for a in flat if isinstance(a, str)]
    assert "data" in [a for a in flat if isinstance(a, str)]


def test_zero_over_pod_upgrades_moments():
    cfg = get_config("grok-1-314b")  # zero_over_pod=True
    shapes = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))
    )
    p_spec = shd.param_pspec_tree(shapes, MULTI)
    o_spec = shd.opt_pspec_tree(cfg, p_spec, shapes, MULTI)
    flat = jax.tree.leaves(o_spec, is_leaf=lambda x: isinstance(x, P))
    assert any(
        any("pod" in _axes_of(e) for e in spec) for spec in flat
    ), "no moment dim picked up the pod axis"


def test_data_pspec_batch_fallbacks():
    assert shd.data_pspec((256, 128), MULTI)[0] == ("pod", "data")
    assert shd.data_pspec((16, 128), MULTI)[0] == "data"  # 16 % 32 != 0
    assert shd.data_pspec((1, 128), MULTI)[0] is None


def test_hint_noop_without_mesh_context():
    x = jnp.ones((4, 4))
    assert shd.hint(x, "batch", None) is x


# ===================================================================
# Serving-mesh coordinate (DESIGN.md §16): "DPxMP" names, MeshPlan
# validation, and the in-process faces of the mesh dispatch axis.
# Multi-device rebind/identity runs live in subprocesses below (the
# pytest process deliberately sees 1 device).
# ===================================================================
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro import models
from repro.core import reset_entry_points
from repro.runtime.scheduler import Request
from repro.runtime.serve import Engine, EngineConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 4) -> str:
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


def test_mesh_name_parse_and_canonical():
    assert shd.parse_mesh_name("1x2") == (1, 2)
    assert shd.parse_mesh_name("2,2") == (2, 2)  # CLI comma form
    assert shd.mesh_name(2, 2) == "2x2"
    assert shd.mesh_name(*shd.parse_mesh_name("4,2")) == "4x2"
    with pytest.raises(ValueError):
        shd.parse_mesh_name("2x2x2")
    with pytest.raises(ValueError):
        shd.parse_mesh_name("0x2")
    with pytest.raises(ValueError):
        shd.parse_mesh_name("banana")


def test_mesh_plan_1x1_is_single_and_needs_no_devices():
    plan = shd.MeshPlan("1x1")
    assert plan.single and plan.num_devices == 1
    # a plan bigger than the visible fleet refuses to build its Mesh
    big = shd.MeshPlan("8x8")
    with pytest.raises(ValueError, match="devices"):
        _ = big.mesh


@pytest.fixture(scope="module")
def mesh_engine():
    reset_entry_points()
    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        cfg,
        params,
        EngineConfig(
            max_len=32, batch_quantum=2, max_batch=4, page_size=8,
            num_pages=20, prefill_chunk=8,
        ),
    )
    yield cfg, eng
    eng.close()


def test_unwarmed_mesh_is_rejected(mesh_engine):
    """A mesh outside the warm ladder must be refused at construction —
    a cold topology would compile mid-stream, which the semi-static
    contract forbids."""
    cfg, eng = mesh_engine
    with pytest.raises(ValueError, match="warmed set"):
        eng.continuous(mesh="1x2")
    with pytest.raises(ValueError, match="warmed set"):
        eng.paged_continuous(mesh="2x2")


def test_set_mesh_validation_and_noop_flip(mesh_engine):
    cfg, eng = mesh_engine
    cb = eng.paged_continuous(slots=4)
    assert cb.mesh == "1x1" and cb.pool.shards == 1
    # same-topology flip (comma spelling): canonicalised, counted as no-op
    assert cb.set_mesh("1,1") == "1x1"
    assert cb.mesh == "1x1"
    assert eng.telemetry.registry.value("mesh_rebinds_total") == 0
    # a topology outside the warm ladder is refused mid-stream too
    with pytest.raises(ValueError, match="warmed set"):
        cb.set_mesh("2x2")
    assert eng.post_warmup_compiles == 0


def test_set_mesh_without_control_surface_raises(mesh_engine):
    cfg, eng = mesh_engine
    cb = eng.paged_continuous(slots=4)
    cb._mesh_ctl = None  # simulate a directly-constructed batcher
    with pytest.raises(RuntimeError, match="mesh control surface"):
        cb.set_mesh("1x1")


def _mesh_reqs_src(n=6, new_tokens=4, prompt_len=12):
    """Source snippet: deterministic greedy requests for subprocess runs.

    Indented to match the 8-space test snippets so textwrap.dedent in
    ``_run`` still strips a uniform prefix.
    """
    return f"""
        reqs = [Request(rid=i, new_tokens={new_tokens}, greedy=True,
                        arrival_s=0.0,
                        prompt=tuple(int(x) for x in rng.integers(
                            0, cfg.vocab_size, {prompt_len})))
                for i in range({n})]
"""


def test_paged_mesh_ladder_rebind_zero_compiles():
    """Tentpole acceptance: warm the 1x1/1x2/2x2 ladder, serve at 1x2,
    scale out to 2x2 mid-stream, then failover-shrink to 1x1 — every flip
    a hot-slot rebind, zero post-warmup compiles, all requests finish."""
    out = _run("""
        import jax, numpy as np
        from repro import models
        from repro.configs import get_config
        from repro.core import lanes as lanes_mod
        from repro.runtime.scheduler import Request
        from repro.runtime.serve import Engine, EngineConfig

        cfg = get_config('olmo-1b').smoke()
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        eng = Engine(cfg, params, EngineConfig(
            max_len=32, batch_quantum=2, max_batch=4, page_size=8,
            num_pages=20, prefill_chunk=8,
            mesh='1x2', meshes=('1x1', '2x2')))
        cb = eng.paged_continuous(slots=4)
        assert cb.mesh == '1x2'
        assert cb.pool.shards == 2  # max dp over the warm ladder (2x2)

        # round-trip coverage: every paged lane warmed at every mesh
        for m in ('1x1', '1x2', '2x2'):
            assert ('cbp', 4, 1, 'fp32', m) in eng._decode, m
            assert ('pf', 4, 8, 'fp32', m) in eng._decode, m

        rng = np.random.default_rng(0)
    """ + _mesh_reqs_src() + """
        done = []
        cb.admit(reqs[:2], now=0.0)
        for i in range(2):
            done += cb.step(now=0.1 * (i + 1))
        assert cb.set_mesh('2x2', now=0.3) == '2x2'  # scale out
        cb.admit(reqs[2:4], now=0.3)
        for i in range(12):
            if not cb.has_work:
                break
            done += cb.step(now=0.4 + 0.1 * i)
        assert cb.set_mesh('1x1', now=2.0) == '1x1'  # failover shrink
        cb.admit(reqs[4:], now=2.0)
        while cb.has_work:
            done += cb.step(now=3.0)
        assert len(done) == 6, len(done)
        assert all(len(r.tokens) == 4 for r in reqs)
        assert eng.post_warmup_compiles == 0, eng.post_warmup_compiles
        assert eng.telemetry.registry.value('mesh_rebinds_total') == 2
        # a sharded pool keeps every prefill call at every slot
        assert cb._prefill_rows_dispatch is None
        st = cb.stats
        assert st.prefill_calls > 0
        assert st.prefill_rows_padded == 4 * st.prefill_calls
        print('OK')
    """, devices=4)
    assert "OK" in out


def test_dense_mesh_rebind_zero_compiles():
    """The dense engine's cb/pfd lanes carry the same mesh coordinate:
    1x1 <-> 1x2 flips mid-stream rebind the step executable without a
    compile, and every admitted request still finishes."""
    out = _run("""
        import jax, numpy as np
        from repro import models
        from repro.configs import get_config
        from repro.runtime.scheduler import Request
        from repro.runtime.serve import Engine, EngineConfig

        cfg = get_config('olmo-1b').smoke()
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        eng = Engine(cfg, params, EngineConfig(
            max_len=32, batch_quantum=2, max_batch=4, prefill_chunk=8,
            mesh='1x1', meshes=('1x2',)))
        cb = eng.continuous(slots=4)
        assert cb.mesh == '1x1'
        rng = np.random.default_rng(0)
    """ + _mesh_reqs_src(n=4) + """
        done = []
        cb.admit(reqs[:2], now=0.0)
        done += cb.step(now=0.1)
        assert cb.set_mesh('1x2', now=0.2) == '1x2'
        cb.admit(reqs[2:], now=0.2)
        for i in range(12):
            if not cb.has_work:
                break
            done += cb.step(now=0.3 + 0.1 * i)
        assert cb.set_mesh('1x1', now=2.0) == '1x1'
        while cb.has_work:
            done += cb.step(now=3.0)
        assert len(done) == 4, len(done)
        assert all(len(r.tokens) == 4 for r in reqs)
        assert eng.post_warmup_compiles == 0, eng.post_warmup_compiles
        print('OK')
    """, devices=2)
    assert "OK" in out


def test_1x1_greedy_bitwise_identity_vs_unsharded():
    """Acceptance: a 1x1-active engine whose warm ladder includes a
    dp-sharded standby (so the page pool is physically 2-sharded) emits
    byte-for-byte the same greedy streams as the plain unsharded engine."""
    out = _run("""
        import jax, numpy as np
        from repro import models
        from repro.configs import get_config
        from repro.core import reset_entry_points
        from repro.runtime.scheduler import Request
        from repro.runtime.serve import (
            Engine, EngineConfig, run_paged_stream,
        )

        cfg = get_config('olmo-1b').smoke()
        params = models.init_params(cfg, jax.random.PRNGKey(0))

        def reqs():
            rng = np.random.default_rng(0)
            return [Request(rid=i, new_tokens=4, greedy=True,
                            arrival_s=0.0,
                            prompt=tuple(int(x) for x in
                                         rng.integers(0, cfg.vocab_size, 12)))
                    for i in range(4)]

        streams, shards = {}, {}
        for tag, meshes in (('plain', ()), ('sharded', ('2x1',))):
            reset_entry_points()
            eng = Engine(cfg, params, EngineConfig(
                max_len=32, batch_quantum=2, max_batch=4, page_size=8,
                num_pages=20, prefill_chunk=8, mesh='1x1', meshes=meshes))
            rs = reqs()
            rep = run_paged_stream(eng, rs, slots=4)
            assert rep['compiles_after_warmup'] == 0
            streams[tag] = [r.tokens for r in rs]
            shards[tag] = rep['pool_shards']
            eng.close()
        assert shards == {'plain': 1, 'sharded': 2}, shards
        assert streams['sharded'] == streams['plain']
        print('OK')
    """, devices=2)
    assert "OK" in out
