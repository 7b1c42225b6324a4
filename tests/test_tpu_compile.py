"""Compile for one described TPU v5e chip, with no chip attached.

The TPU compiler is installed wherever jaxlib's TPU support is, and compiles
for a described topology: these tests catch what the chip's compiler refuses
(a block shape off the tiling, a program that does not fit HBM) before any
chip time is spent. Shapes only, never arrays. The topology is described
inside a fixture, never at import, so every xdist worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""

import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import models
from repro.configs import get_config
from repro.kernels import (
    decode_attention,
    flash_attention,
    paged_decode_attention,
    paged_prefill_attention,
)
from repro.runtime import steps
from repro.runtime.serve import Engine

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py"
)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

# What XLA reports as usable of the v5e's 16 GB ("Used ... of 15.75G hbm").
HBM_LIMIT = 15.75e9
# The paged kernels read pages [P, page_size, KH, dh] one head at a time:
# a (1, page_size, 1, dh) block, whose last two dims are neither divisible
# by (8, 128) nor equal to the array's (KH, dh). Interpret mode accepts it;
# Mosaic does not. Fixing it means a head-major page layout.
PAGED_BLOCK_REFUSAL = (
    "Mosaic refuses the (1, page_size, 1, dh) page block: its last two dims "
    "must be divisible by (8, 128) or equal the array's (KH, dh)"
)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # What this file compiles cannot be read back from the persistent cache
    # without a chip: keep it out of any cache the environment placed.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke_shapes(one_chip):
    """olmo-1b at published widths: params and the smoke's page pool as
    shapes on one chip, plus the pool geometry the smoke's engine uses."""
    cfg = get_config(chip_smoke.ARCH)
    params = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))
    )
    with Engine(cfg, params, chip_smoke.engine_config()) as eng:
        pages = eng.pool_physical_pages
        page_cap = eng.max_pages_per_req
        pages_bucket = eng._pages_buckets()[-1]
        chunk = eng._chunk_buckets()[-1]
    ecfg = chip_smoke.engine_config()
    cache = jax.eval_shape(
        lambda: models.init_paged_cache(cfg, pages, ecfg.page_size)
    )

    def place(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree,
        )

    return dict(
        cfg=cfg, params=place(params), cache=place(cache), slots=ecfg.max_batch,
        page_cap=page_cap, pages_bucket=pages_bucket, chunk=chunk,
        place=place,
    )


def _resident_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )


def _rows(s, *shapes):
    return s["place"](tuple(jax.ShapeDtypeStruct(sh, dt) for sh, dt in shapes))


def test_paged_decode_step_fits_one_chip(smoke_shapes):
    """The cbp lane at its widest pages bucket, cache donated."""
    s = smoke_shapes
    n, pb = s["slots"], s["pages_bucket"]
    rows = _rows(
        s, ((n, 1), jnp.int32), ((n,), jnp.int32), ((n, pb), jnp.int32),
        ((n,), jnp.bool_), ((n,), jnp.float32), ((n,), jnp.bool_),
        ((n, 2), jnp.uint32),
    )
    step = steps.make_paged_slot_decode_fn(s["cfg"])
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        s["params"], s["cache"], *rows
    ).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    assert _resident_bytes(compiled) < HBM_LIMIT


def test_paged_prefill_step_fits_one_chip(smoke_shapes):
    """The pf lane at the smoke's 256-token chunk, cache donated."""
    s = smoke_shapes
    n, c, cap = s["slots"], s["chunk"], s["page_cap"]
    assert c == chip_smoke.PREFILL_CHUNK
    rows = _rows(
        s, ((n, c), jnp.int32), ((n,), jnp.int32), ((n, cap), jnp.int32),
        ((n,), jnp.int32), ((n,), jnp.float32), ((n,), jnp.bool_),
        ((n, 2), jnp.uint32),
    )
    step = steps.make_paged_prefill_fn(s["cfg"])
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        s["params"], s["cache"], *rows
    ).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    assert _resident_bytes(compiled) < HBM_LIMIT


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dense_decode_kernel_lowers_to_mosaic(one_chip):
    b, h, s, dh = 8, 16, 2048, 128
    compiled = decode_attention.lower(
        _sds(one_chip, (b, h, dh)),
        _sds(one_chip, (b, h, s, dh)),
        _sds(one_chip, (b, h, s, dh)),
        _sds(one_chip, (), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_kernel_lowers_to_mosaic(one_chip):
    b, h, s, dh = 1, 16, 512, 128
    qkv = [_sds(one_chip, (b, h, s, dh)) for _ in range(3)]
    compiled = flash_attention.lower(*qkv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _paged_operands(one_chip, pages=1025, ps=16, kh=16, dh=128, n=8, pb=128):
    return (
        _sds(one_chip, (pages, ps, kh, dh)),
        _sds(one_chip, (pages, ps, kh, dh)),
        _sds(one_chip, (n, pb), jnp.int32),
        _sds(one_chip, (n,), jnp.int32),
    )


@pytest.mark.xfail(strict=True, raises=ValueError, reason=PAGED_BLOCK_REFUSAL)
def test_paged_decode_kernel_lowers_to_mosaic(one_chip):
    k, v, bt, pos = _paged_operands(one_chip)
    compiled = jax.jit(paged_decode_attention).lower(
        _sds(one_chip, (8, 16, 128)), k, v, bt, pos
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=PAGED_BLOCK_REFUSAL)
def test_paged_prefill_kernel_lowers_to_mosaic(one_chip):
    k, v, bt, start = _paged_operands(one_chip)
    compiled = jax.jit(paged_prefill_attention).lower(
        _sds(one_chip, (8, 256, 16, 128)), k, v, bt, start
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
