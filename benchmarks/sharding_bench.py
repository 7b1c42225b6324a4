"""Sharded multi-device serving benchmark (DESIGN.md §16) -> BENCH_sharding.json.

Runs the paged engine across the warmed mesh ladder (1x1 / 1x2 / 2x2) in a
subprocess with fake host devices (XLA_FLAGS must precede jax init), plus the
two scenario gates the tentpole promises:

- every topology crossing — cross-stream *and* mid-stream ``set_mesh`` (scale
  out 1x2 -> 2x2, failover shrink -> 1x1) — is a hot-slot rebind with zero
  post-warmup compiles;
- greedy streams on the 1x1 mesh are bitwise identical to the plain
  unsharded engine, even with a dp-sharded standby in the warm ladder (so
  the page pool is physically sharded).

Honest framing: the fake devices all live on one host CPU, so mesh>1 *adds*
collective and partitioning overhead instead of adding FLOPs — per-device
throughput here measures GSPMD partitioning cost, not the paper-level "~85%
of 1-device per-chip throughput" claim, which needs real multi-chip hardware.
The JSON records both the raw numbers and a conservative sanity floor
(``scripts/bench_check.py`` gates structure, zero-compiles, identity, and
that sharded serving still moves tokens), and folds in the collectives
microbenchmark (wire bytes + compressor cost) as the transport-cost face of
the same story.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_SUBPROCESS = """
import json
import jax, numpy as np
from repro import models
from repro.configs import get_config
from repro.core import reset_entry_points
from repro.runtime.scheduler import Request
from repro.runtime.serve import Engine, EngineConfig, run_paged_stream
from repro.distributed import sharding as shd

N = {n}
cfg = get_config('olmo-1b').smoke()
params = models.init_params(cfg, jax.random.PRNGKey(0))
ECFG = dict(max_len=32, batch_quantum=2, max_batch=4, page_size=8,
            num_pages=20, prefill_chunk=8)
KEEP = ('tok_per_s', 'proc_tok_per_s', 'p50_ms', 'p95_ms', 'finished',
        'compiles_after_warmup', 'rebinds', 'pool_shards', 'mesh')


def reqs(seed=0, n=N, new_tokens=6):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, new_tokens=new_tokens, greedy=True, arrival_s=0.0,
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, cfg.vocab_size, 12)))
            for i in range(n)]


out = {{'meshes': {{}}}}

# --- plain unsharded reference (identity + throughput baseline) ---
reset_entry_points()
eng0 = Engine(cfg, params, EngineConfig(**ECFG))
rs0 = reqs()
rep0 = run_paged_stream(eng0, rs0, slots=4)
ref_stream = [list(r.tokens) for r in rs0]
out['unsharded'] = {{k: rep0.get(k) for k in KEEP}}
eng0.close()

# --- the laddered engine: one warmup, every topology a rebind ---
reset_entry_points()
eng = Engine(cfg, params, EngineConfig(
    mesh='1x1', meshes=('1x2', '2x2'), **ECFG))
for m in ('1x1', '1x2', '2x2'):
    rs = reqs()
    rep = run_paged_stream(eng, rs, slots=4, mesh=m)
    row = {{k: rep.get(k) for k in KEEP}}
    dev = shd.parse_mesh_name(m)
    row['devices'] = dev[0] * dev[1]
    row['per_device_proc_tok_per_s'] = (
        row['proc_tok_per_s'] / row['devices'])
    out['meshes'][m] = row
    if m == '1x1':
        out['identity_1x1_vs_unsharded'] = (
            [list(r.tokens) for r in rs] == ref_stream)

# --- mid-stream ladder: scale out, then failover shrink ---
cb = eng.paged_continuous(slots=4, mesh='1x2')
rebind_reqs = reqs(seed=3, n=6, new_tokens=4)
done = []
cb.admit(rebind_reqs[:2], now=0.0)
for i in range(2):
    done += cb.step(now=0.1 * (i + 1))
cb.set_mesh('2x2', now=0.3)
cb.admit(rebind_reqs[2:4], now=0.3)
for i in range(12):
    if not cb.has_work:
        break
    done += cb.step(now=0.4 + 0.1 * i)
cb.set_mesh('1x1', now=2.0)  # failover: the fleet shrank under us
cb.admit(rebind_reqs[4:], now=2.0)
while cb.has_work:
    done += cb.step(now=3.0)
out['rebind'] = {{
    'finished': len(done),
    'expected': len(rebind_reqs),
    'mesh_rebinds': int(
        eng.telemetry.registry.value('mesh_rebinds_total')),
    'compiles_after_warmup': eng.post_warmup_compiles,
}}
eng.close()
print('RESULT ' + json.dumps(out))
"""


def sharding_comparison(
    fast: bool = True, devices: int = 4, n_requests: int | None = None
) -> dict:
    """Run the mesh-ladder scenario in a fake-device subprocess and fold
    in the collectives microcosts; returns the BENCH_sharding.json dict."""
    n = n_requests or (8 if fast else 16)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.path.join(repo, "src"),
    )
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SUBPROCESS.format(n=n))],
        env=env, capture_output=True, text=True, timeout=1800, cwd=repo,
    )
    if res.returncode != 0:
        raise RuntimeError(f"sharding subprocess failed: {res.stderr[-2000:]}")
    line = next(
        l for l in res.stdout.splitlines() if l.startswith("RESULT ")
    )
    out = json.loads(line[len("RESULT "):])

    # Satellite: the collectives microbenchmark rides in the same record —
    # wire bytes per psum flavour and the grad-compressor host cost are the
    # transport half of the sharded-serving cost model.
    from . import collectives_bench

    out["collectives"] = {
        d.name.split("/", 1)[1]: {
            "median_us": d.median,
            "p99_us": d.p99,
        }
        for d in collectives_bench.run(reps=40 if fast else 200)
    }

    ladder_compiles = [
        r["compiles_after_warmup"] for r in out["meshes"].values()
    ]
    base = out["meshes"]["1x1"]["proc_tok_per_s"] or 1.0
    out["acceptance"] = {
        # hard gates (scripts/bench_check.py)
        "zero_compile_topologies": all(c == 0 for c in ladder_compiles),
        "zero_compile_rebinds": out["rebind"]["compiles_after_warmup"] == 0,
        "mesh_rebinds": out["rebind"]["mesh_rebinds"],
        "rebind_all_finished": (
            out["rebind"]["finished"] == out["rebind"]["expected"]
        ),
        "identity_1x1_vs_unsharded": out["identity_1x1_vs_unsharded"],
        "pool_shards": out["meshes"]["1x1"]["pool_shards"],
        # recorded, softly gated: on fake same-host devices mesh>1 only
        # adds partitioning overhead (see module docstring); the ~85%
        # per-device target is a real-hardware claim.
        "sharded_vs_1x1_throughput_frac": round(
            min(
                r["proc_tok_per_s"] / base
                for m, r in out["meshes"].items()
                if m != "1x1"
            ),
            4,
        ),
    }
    return out
