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
import re
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


def _lane(s, lane):
    """One paged lane's step function and its row inputs at smoke size:
    cbp at the widest pages bucket, pf at the 256-token chunk, pfr at one
    row of that chunk."""
    n, c, cap, pb = s["slots"], s["chunk"], s["page_cap"], s["pages_bucket"]
    tail = (((n,), jnp.float32), ((n,), jnp.bool_), ((n, 2), jnp.uint32))
    if lane == "cbp":
        return steps.make_paged_slot_decode_fn(s["cfg"]), _rows(
            s, ((n, 1), jnp.int32), ((n,), jnp.int32), ((n, pb), jnp.int32),
            ((n,), jnp.bool_), *tail,
        )
    if lane == "pf":
        return steps.make_paged_prefill_fn(s["cfg"]), _rows(
            s, ((n, c), jnp.int32), ((n,), jnp.int32), ((n, cap), jnp.int32),
            ((n,), jnp.int32), *tail,
        )
    r = 1
    return steps.make_paged_prefill_fn(s["cfg"], by_rows=True), _rows(
        s, ((r, c), jnp.int32), ((n,), jnp.int32), ((r, cap), jnp.int32),
        ((r,), jnp.int32), *tail, ((r,), jnp.int32),
    )


@pytest.fixture(scope="module")
def compiled_lane(smoke_shapes):
    """Each paged lane compiled once for the chip, cache donated."""
    s, done = smoke_shapes, {}

    def get(lane):
        if lane not in done:
            step, rows = _lane(s, lane)
            done[lane] = jax.jit(step, donate_argnums=(1,)).lower(
                s["params"], s["cache"], *rows
            ).compile()
        return done[lane]

    return get


# An HLO instruction's result shape and opcode: ``%x = bf16[16,1025,...]{..}
# dynamic-update-slice(...``. The layout braces may hold tiling, not spaces.
_HLO_OP = re.compile(r"=\s*\w+\[([\d,]*)\]\S*\s+([\w-]+)\(")
_POOL_TRAFFIC = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


def _pool_traffic(hlo: str, pool_shape) -> list[str]:
    """Instructions (fused ones included) that copy, slice out or write
    back the whole stacked pool or one layer's pool."""
    stacked, layer = tuple(pool_shape), tuple(pool_shape[1:])
    found = []
    for line in hlo.splitlines():
        m = _HLO_OP.search(line)
        if not m or m.group(2) not in _POOL_TRAFFIC or not m.group(1):
            continue
        dims = tuple(int(d) for d in m.group(1).split(","))
        while len(dims) > len(layer) and dims[0] == 1:
            dims = dims[1:]  # a layer sliced out keeps a leading 1
        if dims in (stacked, layer):
            found.append(line.strip())
    return found


def test_paged_decode_step_fits_one_chip(compiled_lane):
    """The cbp lane at its widest pages bucket, cache donated."""
    compiled = compiled_lane("cbp")
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    assert _resident_bytes(compiled) < HBM_LIMIT


def test_paged_prefill_step_fits_one_chip(smoke_shapes, compiled_lane):
    """The pf lane at the smoke's 256-token chunk, cache donated."""
    assert smoke_shapes["chunk"] == chip_smoke.PREFILL_CHUNK
    compiled = compiled_lane("pf")
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    assert _resident_bytes(compiled) < HBM_LIMIT


def test_paged_prefill_rows_step_fits_one_chip(compiled_lane):
    """The pfr lane at one row of the smoke's 256-token chunk: the per-slot
    gather and scatter around the chunk tower, cache donated, in less
    memory than the full-width pf call."""
    compiled = compiled_lane("pfr")
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    assert _resident_bytes(compiled) < HBM_LIMIT


@pytest.mark.parametrize("lane", ["cbp", "pf", "pfr"])
def test_paged_lane_writes_pool_in_place(smoke_shapes, compiled_lane, lane):
    """The pool rides the layer scan's carry and is written in place: the
    lane's temporaries stay below one stacked pool leaf, and no copy,
    dynamic-slice or dynamic-update-slice of the pool or of one layer's
    pool is left in the compiled program (DESIGN.md §9)."""
    leaf = smoke_shapes["cache"][0]["k"]
    compiled = compiled_lane(lane)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < leaf.size * leaf.dtype.itemsize
    assert _pool_traffic(compiled.as_text(), leaf.shape) == []


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
