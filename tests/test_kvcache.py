"""Paged KV-cache tests (runtime/kvcache.py, DESIGN.md §9): allocator
invariants (no double-free, accounting sums to capacity), copy-on-write
forks, prefix-trie sharing/eviction, paged-vs-dense decode equivalence on
CPU, and the paged serving stream's zero-recompile / overcommit contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import pytest as _pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # container may lack hypothesis: skip only
    # the property tests, keep the plain unit tests runnable.
    def given(*_a, **_k):
        return lambda f: _pytest.mark.skip(reason="hypothesis not installed")(f)

    def settings(*_a, **_k):
        return lambda f: f

    class _St:
        def __getattr__(self, _):
            return lambda *a, **k: None

    st = _St()

from repro import models
from repro.configs import get_config
from repro.core import reset_entry_points
from repro.runtime.kvcache import (
    NULL_PAGE,
    BlockTable,
    KVCacheError,
    PagePool,
    PrefixCache,
    sharing_report,
)
from repro.runtime.scheduler import Request, shared_prefix_arrivals


# ------------------------------------------------------------------ PagePool
def test_pool_alloc_free_accounting():
    pool = PagePool(8, 4)
    assert pool.pages_free == 8 and pool.pages_in_use == 0
    pids = [pool.alloc() for _ in range(8)]
    assert None not in pids and len(set(pids)) == 8
    assert NULL_PAGE not in pids  # null page is never handed out
    assert pool.pages_free == 0 and pool.pages_in_use == 8
    assert pool.alloc() is None  # dry, not an exception
    pool.check()
    for p in pids:
        assert pool.decref(p)  # ref 1 -> freed
    assert pool.pages_free == 8
    pool.check()


def test_pool_double_free_raises():
    pool = PagePool(2, 4)
    p = pool.alloc()
    pool.decref(p)
    with pytest.raises(KVCacheError):
        pool.decref(p)
    with pytest.raises(KVCacheError):
        pool.incref(p)  # resurrecting a freed page is also misuse
    with pytest.raises(KVCacheError):
        pool.decref(NULL_PAGE)


def test_pool_refcounts_pin_pages():
    pool = PagePool(2, 4)
    p = pool.alloc()
    pool.incref(p)
    assert not pool.decref(p)  # still referenced
    assert pool.refcount(p) == 1
    assert pool.decref(p)
    pool.check()


# ---------------------------------------------------------------- BlockTable
def test_block_table_growth_and_release():
    pool = PagePool(4, 4)
    t = BlockTable(pool=pool)
    assert t.ensure_capacity(0) and t.num_pages == 1
    assert t.ensure_capacity(11) and t.num_pages == 3  # pages for pos 0..11
    assert t.capacity == 12
    assert pool.pages_in_use == 3
    t.release()
    assert pool.pages_in_use == 0
    pool.check()


def test_block_table_oom_is_soft():
    pool = PagePool(2, 4)
    t = BlockTable(pool=pool)
    assert t.ensure_capacity(7)
    assert not t.ensure_capacity(8)  # third page: pool only has 2
    t.release()
    pool.check()


def test_fork_copies_on_write():
    copies = []
    pool = PagePool(8, 4)
    parent = BlockTable(pool=pool)
    assert parent.ensure_capacity(5)  # 2 pages
    parent.num_tokens = 6
    child = parent.fork()
    assert child.pages == parent.pages
    assert all(pool.refcount(p) == 2 for p in parent.pages)
    # child writes into the shared second page -> COW
    assert child.ensure_writable(5, copy_page=lambda s, d: copies.append((s, d)))
    assert child.pages[0] == parent.pages[0]  # untouched page still shared
    assert child.pages[1] != parent.pages[1]  # written page diverged
    assert copies == [(parent.pages[1], child.pages[1])]
    assert pool.refcount(parent.pages[1]) == 1
    assert pool.refcount(child.pages[1]) == 1
    # exclusive pages skip the copy
    assert child.ensure_writable(5, copy_page=lambda s, d: copies.append((s, d)))
    assert len(copies) == 1
    assert pool.stats.cow_copies == 1
    parent.release()
    child.release()
    pool.check()


# -------------------------------------------------------------- PrefixCache
def test_prefix_match_insert_and_refcounts():
    pool = PagePool(8, 4)
    trie = PrefixCache(pool)
    prompt = tuple(range(10))  # 2 full pages + 2 tokens
    t = BlockTable(pool=pool)
    assert t.ensure_capacity(9)  # 3 pages
    trie.insert(prompt, t.pages)
    assert len(trie) == 2  # only full pages are cached
    assert pool.refcount(t.pages[0]) == 2  # table + trie
    assert pool.refcount(t.pages[2]) == 1  # partial page not cached

    pages, matched = trie.match(prompt)
    assert matched == 8 and pages == t.pages[:2]
    assert pool.refcount(t.pages[0]) == 3  # table + trie + matcher
    for p in pages:
        pool.decref(p)

    # different prompt shares nothing
    pages2, matched2 = trie.match(tuple(range(100, 110)))
    assert pages2 == [] and matched2 == 0

    t.release()
    assert pool.pages_in_use == 2  # trie still pins its 2 full pages
    assert trie.evict(10) == 2
    assert pool.pages_in_use == 0
    pool.check()


def test_prefix_eviction_spares_live_pages():
    pool = PagePool(4, 2)
    trie = PrefixCache(pool)
    t = BlockTable(pool=pool)
    assert t.ensure_capacity(3)  # 2 pages
    trie.insert((0, 1, 2, 3), t.pages)
    # live table still references both pages: nothing is evictable
    assert trie.evict(10) == 0
    t.release()
    assert trie.evict(10) == 2
    pool.check()


def test_sharing_report_overcommit():
    pool = PagePool(4, 4)
    a = BlockTable(pool=pool)
    assert a.ensure_capacity(7)
    a.num_tokens = 8
    b = a.fork()
    rep = sharing_report([a, b], pool)
    assert rep["logical_pages"] == 4 and rep["physical_pages"] == 2
    assert rep["share_ratio"] == 2.0
    assert rep["logical_tokens"] == 16 and rep["pool_tokens"] == 16
    a.release()
    b.release()
    pool.check()


# ------------------------------------------------------- property invariants
@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=1, max_size=60))
def test_pool_invariants_random_ops(ops):
    """Random alloc/incref/decref/fork/release sequences keep accounting
    exact: in_use + free == capacity, no page both free and referenced."""
    pool = PagePool(6, 2)
    tables: list[BlockTable] = []
    for op in ops:
        if op == 0:
            t = BlockTable(pool=pool)
            if t.ensure_capacity(0):
                tables.append(t)
            else:
                t.release()
        elif op == 1 and tables:
            tables.append(tables[-1].fork())
        elif op == 2 and tables:
            tables.pop().release()
        elif op == 3 and tables:
            tables[-1].ensure_capacity(tables[-1].capacity)  # grow 1 page
        elif op == 4 and tables:
            tables[-1].ensure_writable(0)
        pool.check()
        assert pool.pages_in_use + pool.pages_free == pool.num_pages
    for t in tables:
        t.release()
    pool.check()
    assert pool.pages_free == pool.num_pages


# ------------------------------------------- paged vs dense decode (CPU bit)
@pytest.mark.parametrize(
    "arch,layers",
    [
        ("olmo-1b", None),
        # two repeats of a windowed + full period: the layer index takes
        # more than one value and the two mixers alternate
        ("gemma2-27b", 4),
    ],
)
def test_paged_decode_matches_dense_bitwise(arch, layers):
    from dataclasses import replace

    cfg = get_config(arch).smoke()
    if layers is not None:
        cfg = replace(cfg, num_layers=layers).validate()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    S, max_len, ps, steps = 3, 32, 8, 20
    PB = max_len // ps
    dense = models.init_cache(cfg, S, max_len)
    paged = models.init_paged_cache(cfg, 1 + S * PB, ps)
    rng = np.random.default_rng(0)
    # shuffled layout: each row's pages are scattered over the pool
    bt = jnp.asarray(
        1 + rng.permutation(S * PB).reshape(S, PB), jnp.int32
    )
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (S, 1)), jnp.int32)
    pos = jnp.zeros((S,), jnp.int32)
    dstep = jax.jit(lambda p, c, t, po: models.decode_step(cfg, p, c, t, po))
    pstep = jax.jit(
        lambda p, c, t, po, b: models.paged_decode_step(cfg, p, c, t, po, b)
    )
    for _ in range(steps):
        ld, dense = dstep(params, dense, tok, pos)
        lp, paged = pstep(params, paged, tok, pos, bt)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
        tok = jnp.argmax(ld, axis=-1).astype(jnp.int32)[:, None]
        pos = pos + 1
    # every layer's pages, read through the tables, hold the dense rows:
    # a write that lands in another layer (or page) fails here
    for slot in range(cfg.period):
        for leaf in ("k", "v"):
            pool = np.asarray(paged[slot][leaf])  # [m, P, ps, KH, dh]
            rows = np.asarray(dense[slot][leaf])  # [m, S, max_len, KH, dh]
            view = pool[:, np.asarray(bt)].reshape(rows.shape)
            np.testing.assert_array_equal(
                view[:, :, :steps], rows[:, :, :steps]
            )


def test_paged_kernel_matches_oracle():
    from repro.kernels import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )

    rng = np.random.default_rng(1)
    B, H, KH, dh, ps, PB = 2, 8, 4, 64, 8, 4
    P = 1 + B * PB
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, ps, KH, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, ps, KH, dh)), jnp.float32)
    # shuffled (non-contiguous) page assignment: order comes from the table
    perm = rng.permutation(np.arange(1, P))
    bt = jnp.asarray(perm.reshape(B, PB), jnp.int32)
    pos = jnp.asarray([7, 29], jnp.int32)
    for kw in ({}, {"window": 9}, {"softcap": 10.0}):
        ref = paged_decode_attention_reference(q, kp, vp, bt, pos, **kw)
        out = paged_decode_attention(q, kp, vp, bt, pos, interpret=True, **kw)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-6
        )


# -------------------------------------------------- paged serving end-to-end
def _smoke_engine(num_pages, page_size=8, max_len=32, slots=4):
    from repro.runtime.serve import Engine, EngineConfig

    reset_entry_points()
    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(
        max_len=max_len,
        batch_quantum=2,
        max_batch=slots,
        page_size=page_size,
        num_pages=num_pages,
    )
    return cfg, Engine(cfg, params, ecfg)


def test_paged_stream_shares_prefixes_and_never_recompiles():
    from repro.runtime.serve import run_paged_stream

    cfg, eng = _smoke_engine(num_pages=20)
    reqs = shared_prefix_arrivals(
        12, 400.0, seed=0, num_prefixes=2, prefix_len=8,
        suffix_len_mean=2.0, tokens_mean=4.0, total_max=32,
        vocab=cfg.vocab_size,
    )
    rep = run_paged_stream(eng, reqs, slots=4)
    eng.close()
    assert rep["finished"] == 12 and rep["unserved"] == 0
    assert rep["compiles_after_warmup"] == 0  # buckets are AOT-warmed
    assert rep["shared_prompt_tokens"] > 0  # the trie actually dedupes
    assert rep["share_ratio"] > 1.0
    assert rep["pages_in_use_peak"] <= 20


def test_paged_stream_preempts_on_oom_instead_of_rejecting():
    from repro.runtime.serve import run_paged_stream

    # 6 pages * 8 = 48 pooled tokens for 4 slots x 32 max_len: heavy pressure
    cfg, eng = _smoke_engine(num_pages=6)
    reqs = [
        Request(rid=i, new_tokens=20, greedy=True, arrival_s=0.001 * i,
                prompt=tuple(range(4)), priority=(0 if i < 3 else 1))
        for i in range(4)
    ]
    rep = run_paged_stream(eng, reqs, slots=4)
    eng.close()
    # pool pressure resolved by preemption/deferral, not rejection
    assert rep["preemptions"] + rep["starved_admissions"] > 0
    assert rep["finished"] == 4 and rep["unserved"] == 0


def test_copy_cache_pages_device_cow():
    """The device half of COW: a jitted, donated page copy moves one page's
    contents in every layer and leaves the rest untouched."""
    cfg = get_config("olmo-1b").smoke()
    cache = models.init_paged_cache(cfg, 5, 4)
    # fill page 2 with a recognisable value
    cache = jax.tree.map(lambda t: t.at[:, 2].set(7.0), cache)
    copy_jit = jax.jit(models.copy_cache_pages, donate_argnums=(0,))
    cache = copy_jit(cache, jnp.int32(2), jnp.int32(4))
    for leaf in jax.tree.leaves(cache):
        np.testing.assert_array_equal(np.asarray(leaf[:, 4]), 7.0)
        np.testing.assert_array_equal(np.asarray(leaf[:, 3]), 0.0)
        np.testing.assert_array_equal(np.asarray(leaf[:, 2]), 7.0)


def test_batcher_device_copy_threads_cache():
    """PagedContinuousBatcher._device_copy_page rebinds its cache to the
    cache_copy result (the wiring the engine's COW closure relies on)."""
    from repro.runtime.scheduler import PagedContinuousBatcher

    pool = PagePool(4, 2)
    calls = []

    def cache_copy(cache, src, dst):
        calls.append((src, dst))
        return cache + 1

    cb = PagedContinuousBatcher(
        dispatch_fn=lambda pb: None,
        pool=pool,
        prefix_cache=PrefixCache(pool),
        cache=0,
        num_slots=1,
        max_pages_per_req=2,
        cache_copy=cache_copy,
    )
    t = BlockTable(pool=pool)
    assert t.ensure_capacity(0)
    shared = t.fork()
    assert shared.ensure_writable(0, cb._device_copy_page)  # COW fires
    assert calls == [(t.pages[0], shared.pages[0])]
    assert cb._cache == 1  # the returned cache replaced the batcher's
    t.release()
    shared.release()
    pool.check()


def test_paged_stream_rejects_only_the_oversized_request():
    from repro.runtime.serve import run_paged_stream

    # cap = min(pool, ceil(max_len/page_size)) = 4 pages = 32 tokens
    cfg, eng = _smoke_engine(num_pages=12)
    reqs = [
        Request(rid=0, new_tokens=4, greedy=True, arrival_s=0.0,
                prompt=(1, 2, 3)),
        Request(rid=1, new_tokens=60, greedy=True, arrival_s=0.0),  # 8 pages
        Request(rid=2, new_tokens=4, greedy=True, arrival_s=0.0,
                prompt=(1, 2, 3)),
    ]
    rep = run_paged_stream(eng, reqs, slots=4)
    eng.close()
    # the impossible request is dropped; the stream survives and serves the rest
    assert rep["rejected_oversize"] == 1
    assert rep["finished"] == 2 and rep["unserved"] == 1


def test_paged_batcher_emits_same_tokens_as_dense():
    """Greedy shared-prefix requests through the paged stream produce the
    same token ids as teacher-forcing the same prompts through the dense
    decode oracle, page layout and preemption notwithstanding."""
    from repro.runtime.serve import run_paged_stream

    cfg, eng = _smoke_engine(num_pages=24)
    prompt = tuple(int(x) for x in np.arange(5) + 7)
    reqs = [
        Request(rid=i, new_tokens=6, greedy=True, arrival_s=0.0,
                prompt=prompt)
        for i in range(3)
    ]
    rep = run_paged_stream(eng, reqs, slots=4)
    eng.close()
    assert rep["finished"] == 3

    # dense oracle: feed the prompt token by token, then decode greedily
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    cache = models.init_cache(cfg, 1, 32)
    step = jax.jit(lambda p, c, t, po: models.decode_step(cfg, p, c, t, po))
    tok = None
    out = []
    for pos in range(5 + 6 - 1):
        feed = prompt[pos] if pos < len(prompt) else tok
        logits, cache = step(
            params, cache, jnp.asarray([[feed]], jnp.int32),
            jnp.asarray([pos], jnp.int32),
        )
        tok = int(np.argmax(np.asarray(logits), axis=-1)[0])
        if pos >= len(prompt) - 1:
            out.append(tok)
    for r in reqs:
        assert r.tokens == out
