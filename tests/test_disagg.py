"""Disaggregated prefill/decode serving (DESIGN.md §17).

The disagg coordinate needs two visible devices (the decode mesh ``1x1``
plus the pinned prefill slice ``1x1@1``), so the functional matrix runs in
a fake-device subprocess — the pytest process deliberately sees one
device.  One subprocess warms everything and emits a JSON blob; the test
functions below assert on different slices of it:

- **bitwise matrix** — disagg vs shared greedy streams are token-for-token
  identical across {sync, async} x {fp32, int8}, with the migration path
  exercised and zero post-warmup compiles in every cell;
- **trie hit after a migrated fork** — a prompt whose KV pages were
  written on the prefill slice and live-migrated decode-ward must still
  land in the prefix trie, so a later identical prompt adopts the pages
  (``shared_prompt_tokens`` > 0) and decodes the same tail;
- **split -> collapse -> split** — both mid-stream ``set_disagg`` crossings
  are semi-static rebinds (``disagg_rebinds_total`` == 2), never compiles.

In-process unit coverage (``set_disagg`` validation, shadow-table
bookkeeping, ``migrate_pages`` refcount algebra) lives in
``test_scheduler.py`` / ``test_properties.py``; this file owns the
end-to-end two-device contract.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 2) -> str:
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


_SUBPROCESS = """
import json
import jax, numpy as np
from repro import models
from repro.configs import get_config
from repro.core import reset_entry_points
from repro.runtime.scheduler import Request
from repro.runtime.serve import Engine, EngineConfig, run_paged_stream

cfg = get_config('olmo-1b').smoke()
params = models.init_params(cfg, jax.random.PRNGKey(0))
BASE = dict(max_len=48, batch_quantum=2, max_batch=4, page_size=8,
            num_pages=40, prefill_chunk=8, token_budget=8,
            mesh='1x1', meshes=('1x1@1',))


def mixed(seed=0, n_long=4, n_decode=1):
    # One decode-heavy request holding a slot plus a backlog of long
    # prompts: every long prompt crosses PREFILL -> DECODE, so the
    # disagg arms must exercise live page migration.
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=0, new_tokens=24, greedy=True, arrival_s=0.0,
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, cfg.vocab_size, 8)))
            for _ in range(n_decode)]
    for _ in range(n_long):
        reqs.append(Request(
            rid=len(reqs), new_tokens=3, greedy=True, arrival_s=0.0,
            prompt=tuple(int(x) for x in
                         rng.integers(0, cfg.vocab_size, 24))))
    return reqs


def matrix_arm(eng, dt, async_steps):
    rs_shared = mixed()
    rep_s = run_paged_stream(eng, rs_shared, slots=4,
                             async_steps=async_steps)
    rs_dis = mixed()
    rep_d = run_paged_stream(eng, rs_dis, slots=4, disagg=True,
                             async_steps=async_steps)
    return dict(
        kv_dtype=dt, async_steps=async_steps,
        bitwise=([list(r.tokens) for r in rs_shared]
                 == [list(r.tokens) for r in rs_dis]),
        migrations=rep_d['migrations'],
        finished=[rep_s['finished'], rep_d['finished']],
        expected=len(rs_shared),
        compiles=[rep_s['compiles_after_warmup'],
                  rep_d['compiles_after_warmup']],
    )


out = {'matrix': []}
reset_entry_points()
eng = Engine(cfg, params, EngineConfig(**BASE))
for async_steps in (False, True):
    out['matrix'].append(matrix_arm(eng, 'fp32', async_steps))

# --- trie hit after a migrated fork: A's prompt pages are written on the
# prefill slice, migrate decode-ward at the flip, and must still reach
# the prefix trie when A finishes; B (same prompt, later arrival) adopts
# them and decodes the identical greedy tail.
prompt = tuple(int(x) for x in
               np.random.default_rng(7).integers(0, cfg.vocab_size, 24))
A = Request(rid=0, new_tokens=4, greedy=True, arrival_s=0.0, prompt=prompt)
B = Request(rid=1, new_tokens=4, greedy=True, arrival_s=5.0, prompt=prompt)
rep = run_paged_stream(eng, [A, B], slots=4, disagg=True)
out['trie'] = dict(
    migrations=rep['migrations'],
    shared_prompt_tokens=rep['shared_prompt_tokens'],
    same_tokens=list(A.tokens) == list(B.tokens),
    finished=rep['finished'],
    compiles=rep['compiles_after_warmup'],
)

# --- split -> collapse -> split mid-stream: both crossings are rebinds.
rebinds0 = int(eng.telemetry.registry.value('disagg_rebinds_total'))
cb = eng.paged_continuous(slots=4, disagg=True)
rs = mixed(seed=3)
pending = list(rs)
done = []
t, step_i = 0.0, 0
while pending or cb.has_work:
    if step_i == 4:
        cb.set_disagg(False, now=t)   # collapse: live prefills migrate back
    elif step_i == 8:
        cb.set_disagg(True, now=t)    # re-split mid-stream
    if pending and cb.free_slots:
        take = min(len(pending), cb.free_slots)
        cb.admit(pending[:take], now=t)
        del pending[:take]
    done += cb.step(now=t)
    step_i += 1
    t += 0.05
    assert step_i < 400, 'rebind arm did not drain'
cb.flush()
out['rebind'] = dict(
    finished=len(done), expected=len(rs),
    rebinds=int(
        eng.telemetry.registry.value('disagg_rebinds_total')) - rebinds0,
    compiles=eng.post_warmup_compiles,
)
eng.close()

# --- int8 pool: the dtype coordinate composes with the disagg split.
reset_entry_points()
eng = Engine(cfg, params, EngineConfig(kv_dtype='int8', **BASE))
for async_steps in (False, True):
    out['matrix'].append(matrix_arm(eng, 'int8', async_steps))
eng.close()
print('RESULT ' + json.dumps(out))
"""


@pytest.fixture(scope="module")
def disagg_runs():
    stdout = _run(_SUBPROCESS, devices=2)
    line = next(
        l for l in stdout.splitlines() if l.startswith("RESULT ")
    )
    return json.loads(line[len("RESULT "):])


def test_disagg_bitwise_matrix(disagg_runs):
    """Disagg vs shared greedy streams are bitwise identical in every
    {sync, async} x {fp32, int8} cell — the split changes where work
    runs, never what it computes — with migration exercised and zero
    post-warmup compiles."""
    cells = disagg_runs["matrix"]
    assert len(cells) == 4
    seen = {(c["kv_dtype"], c["async_steps"]) for c in cells}
    assert seen == {("fp32", False), ("fp32", True),
                    ("int8", False), ("int8", True)}
    for c in cells:
        assert c["bitwise"], c
        assert c["migrations"] > 0, c
        assert c["finished"] == [c["expected"]] * 2, c
        assert c["compiles"] == [0, 0], c


def test_prefix_trie_hit_after_migrated_fork(disagg_runs):
    """Pages that crossed the prefill->decode migration still feed the
    prefix trie: a later identical prompt adopts them instead of
    recomputing."""
    trie = disagg_runs["trie"]
    assert trie["migrations"] > 0, trie
    # B adopts 2 full pages (16 tokens) of A's migrated prompt — the last
    # prompt token seeds decode, so the third page is never trie-insertable;
    # the shared-mesh path matches the same 16 (checked equal by hand).
    assert trie["shared_prompt_tokens"] >= 16, trie
    assert trie["same_tokens"], trie
    assert trie["finished"] == 2 and trie["compiles"] == 0, trie


def test_split_collapse_split_zero_compiles(disagg_runs):
    """Mid-stream set_disagg(False) then set_disagg(True) are two
    semi-static rebinds — live prefills migrate, nothing recompiles, and
    the stream drains."""
    reb = disagg_runs["rebind"]
    assert reb["rebinds"] == 2, reb
    assert reb["compiles"] == 0, reb
    assert reb["finished"] == reb["expected"], reb
