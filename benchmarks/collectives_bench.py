"""Cross-pod gradient-compression microbenchmark (distributed-optim feature).

Measures, in an 8-fake-device subprocess, the HLO wire bytes of a plain f32
psum vs the int8 compressed_psum, plus the host-side quantise/dequantise cost
of the error-feedback grad compressor. Evidence for DESIGN.md §5's cross-pod
compression claim (4× wire reduction, bounded error per
tests/test_substrate.py).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.collectives import make_grad_compressor

from .common import Dist, measure

_SUBPROCESS = """
import jax, jax.numpy as jnp, re
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import compressed_psum

shard_map = jax.shard_map
# the production pod axis
mesh = jax.make_mesh((2,), ('pod',), axis_types=(jax.sharding.AxisType.Auto,))
x = jax.ShapeDtypeStruct((2, 4096), jnp.float32)

def wire_bytes(fn):
    txt = jax.jit(fn).lower(x).compile().as_text()
    total = 0
    for line in txt.splitlines():
        for op in ('all-reduce(', 'all-gather(', 'reduce-scatter('):
            if ' ' + op in line or '-start(' in line and op[:-1] in line:
                for dt, dims in re.findall(r'(\\w+)\\[([\\d,]*)\\]', line.split('=',1)[1].split(op[:-1])[0]):
                    sz = {'f32':4,'bf16':2,'s8':1,'s32':4,'u32':4,'pred':1}.get(dt)
                    if sz:
                        n = 1
                        for d in dims.split(','):
                            if d: n *= int(d)
                        total += n * sz
                break
    return total

plain = lambda x: shard_map(lambda s: jax.lax.psum(s, 'pod'), mesh=mesh,
                            in_specs=P('pod'), out_specs=P('pod'))(x)
comp = lambda x: shard_map(lambda s: compressed_psum(s, 'pod'), mesh=mesh,
                           in_specs=P('pod'), out_specs=P('pod'))(x)
print('PLAIN', wire_bytes(plain))
print('COMP', wire_bytes(comp))
"""


def run(reps: int = 200) -> list[Dist]:
    out = []
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PYTHONPATH=os.path.join(repo, "src"),
    )
    try:
        res = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_SUBPROCESS)],
            env=env, capture_output=True, text=True, timeout=600, cwd=repo,
        )
        vals = dict(
            line.split() for line in res.stdout.splitlines() if line
        )
        plain = float(vals.get("PLAIN", 0))
        comp = float(vals.get("COMP", 1))
        out.append(Dist("collectives/plain-psum-wire-bytes", np.array([plain])))
        out.append(Dist("collectives/int8-psum-wire-bytes", np.array([comp])))
        out.append(
            Dist("collectives/wire-reduction-x", np.array([plain / max(comp, 1)]))
        )
    except Exception:
        pass

    # host-side compressor cost (per 1M-element gradient leaf)
    compress, init_res = make_grad_compressor(bits=8)
    g = {"w": jnp.ones((1 << 20,), jnp.float32)}
    r = init_res(g)
    cjit = jax.jit(compress)
    cjit(g, r)  # warm

    def call():
        gh, _ = cjit(g, r)
        jax.block_until_ready(gh)

    out.append(measure("collectives/ef-int8-compress-1M", call, reps=reps))
    return out
