"""Chunked-prefill tests (DESIGN.md §10): Pallas kernel vs oracle, bitwise
equivalence of chunked vs token-by-token prompt ingestion (cache contents
and first sampled token), the PREFILL -> DECODE scheduler state machine
(budget split, flip-time prefix insertion, preemption mid-prefill), the
host->device upload dedup, and the end-to-end zero-recompile contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.core import reset_entry_points
from repro.runtime.kvcache import PagePool, PrefixCache
from repro.runtime.scheduler import (
    ContinuousBatcher,
    PagedContinuousBatcher,
    Request,
)


# -------------------------------------------------------- kernel vs oracle
def test_prefill_kernel_matches_oracle():
    from repro.kernels import (
        paged_prefill_attention,
        paged_prefill_attention_reference,
    )

    rng = np.random.default_rng(1)
    for (B, H, KH, dh, ps, PB, C) in [
        (2, 8, 4, 64, 8, 4, 8),
        (1, 4, 4, 32, 16, 2, 16),
        (2, 4, 2, 32, 8, 8, 32),
    ]:
        P = 1 + B * PB
        q = jnp.asarray(rng.normal(size=(B, C, H, dh)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(P, ps, KH, dh)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(P, ps, KH, dh)), jnp.float32)
        # shuffled (non-contiguous) pages: order comes from the table
        perm = rng.permutation(np.arange(1, P))
        bt = jnp.asarray(perm.reshape(B, PB), jnp.int32)
        start = jnp.asarray(
            rng.integers(0, ps * PB - C + 1, B), jnp.int32
        )
        for kw in ({}, {"window": 9}, {"softcap": 10.0}):
            ref = paged_prefill_attention_reference(q, kp, vp, bt, start, **kw)
            out = paged_prefill_attention(
                q, kp, vp, bt, start, interpret=True, **kw
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-6
            )


# --------------------------------------------- chunked vs sequential (bits)
def test_paged_chunked_prefill_matches_sequential_bitwise():
    """Chunked ingestion == C iterations of paged_decode_step: identical
    cache bits (every allocatable page) and identical priming logits. The
    null page is excluded — bucket-padding rows scribble it by design."""
    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    ps, PB = 4, 8
    seq_cache = models.init_paged_cache(cfg, 1 + PB, ps)
    chk_cache = models.init_paged_cache(cfg, 1 + PB, ps)
    bt = jnp.asarray(1 + np.arange(PB).reshape(1, PB), jnp.int32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 20)

    dstep = jax.jit(
        lambda p, c, t, po, b: models.paged_decode_step(cfg, p, c, t, po, b)
    )
    for i, t in enumerate(prompt):
        ld, seq_cache = dstep(
            params, seq_cache, jnp.asarray([[t]], jnp.int32),
            jnp.asarray([i], jnp.int32), bt,
        )

    pf = jax.jit(
        lambda p, c, t, s, b, l: models.paged_prefill_step(
            cfg, p, c, t, s, b, l
        )
    )
    cur = 0
    for chunk in (8, 8, 4):  # last chunk padded into its bucket
        CB = 8
        tok = np.zeros((1, CB), np.int32)
        tok[0, :chunk] = prompt[cur : cur + chunk]
        lc, chk_cache = pf(
            params, chk_cache, jnp.asarray(tok),
            jnp.asarray([cur], jnp.int32), bt,
            jnp.asarray([chunk], jnp.int32),
        )
        cur += chunk

    for a, b in zip(jax.tree.leaves(seq_cache), jax.tree.leaves(chk_cache)):
        np.testing.assert_array_equal(
            np.asarray(a)[:, 1:], np.asarray(b)[:, 1:]
        )
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lc))
    assert int(np.argmax(np.asarray(ld))) == int(np.argmax(np.asarray(lc)))


def test_dense_chunked_prefill_matches_sequential_bitwise():
    """Dense chunked ingestion == C iterations of per-row decode_step."""
    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    S, max_len = 2, 32
    seq_cache = models.init_cache(cfg, S, max_len)
    chk_cache = models.init_cache(cfg, S, max_len)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 20)

    dstep = jax.jit(lambda p, c, t, po: models.decode_step(cfg, p, c, t, po))
    for i, t in enumerate(prompt):
        ld, seq_cache = dstep(
            params, seq_cache, jnp.asarray([[t]] * S, jnp.int32),
            jnp.asarray([i] * S, jnp.int32),
        )

    cstep = jax.jit(
        lambda p, c, t, s, l: models.chunked_decode_step(cfg, p, c, t, s, l)
    )
    cur = 0
    for chunk in (8, 8, 4):
        CB = 8
        tok = np.zeros((S, CB), np.int32)
        tok[:, :chunk] = prompt[cur : cur + chunk]
        lc, chk_cache = cstep(
            params, chk_cache, jnp.asarray(tok),
            jnp.asarray([cur] * S, jnp.int32),
            jnp.asarray([chunk] * S, jnp.int32),
        )
        cur += chunk

    for a, b in zip(jax.tree.leaves(seq_cache), jax.tree.leaves(chk_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lc))


# --------------------------------------- state machine (no model, no jit)
def _fake_decode_dispatch(bucket):
    def step(cache, tok, pos, bt, active, temps, greedy, keys):
        nxt = np.asarray(tok)[:, 0] + 1
        new_pos = np.asarray(pos) + np.asarray(active).astype(np.int32)
        return nxt, cache, new_pos, keys
    return step


class _FakePrefill:
    """Records every chunk row fed: (bucket, start, length, tokens).

    The paged prefill executable is batched (``("pf", slots, chunk_bucket,
    kv_dtype, mesh)``): [S]-wide per-row windows, length 0 = idle row. One
    entry is appended per *real* row, so single-prefill scenarios record
    exactly what the old B=1 lane did; ``call_rows`` records rows-per-call
    for the batching assertions. ``rows`` stands in for the row-bucketed
    ``("pfr", slots, rows, ...)`` executables; ``row_buckets`` records the
    row count of each of their calls.
    """

    def __init__(self):
        self.calls = []
        self.call_rows = []
        self.row_buckets = []

    def rows(self, n_rows):
        def step(cache, tok, start, bt, length, temps, greedy, keys, rows):
            t, ln, of = np.asarray(tok), np.asarray(length), np.asarray(rows)
            assert t.shape[0] == n_rows and of.shape == (n_rows,)
            self.row_buckets.append(n_rows)
            st = np.asarray(start)
            nxt = np.zeros(len(st), np.int64)
            for i in np.flatnonzero(ln > 0):
                s = int(of[i])
                self.calls.append(
                    (t.shape[1], int(st[s]), int(ln[i]),
                     tuple(int(x) for x in t[i, : int(ln[i])]))
                )
                nxt[s] = t[i, ln[i] - 1] + 1
            self.call_rows.append(int((ln > 0).sum()))
            return nxt, cache, keys
        return step

    def __call__(self, bucket):
        def step(cache, tok, start, bt, length, temps, greedy, keys):
            t, st, ln = np.asarray(tok), np.asarray(start), np.asarray(length)
            rows = 0
            for s in range(len(ln)):
                if ln[s] > 0:
                    rows += 1
                    self.calls.append(
                        (bucket, int(st[s]), int(ln[s]),
                         tuple(int(x) for x in t[s, : int(ln[s])]))
                    )
            self.call_rows.append(rows)
            nxt = np.array(
                [t[s, max(int(ln[s]) - 1, 0)] + 1 for s in range(len(ln))]
            )
            return nxt, cache, keys
        return step


def _paged_batcher(pool, *, slots=2, prefill_chunk=16, token_budget=0,
                   max_pages=8, row_buckets=False, telemetry=None):
    fake_pf = _FakePrefill()
    cb = PagedContinuousBatcher(
        dispatch_fn=_fake_decode_dispatch,
        pool=pool,
        prefix_cache=PrefixCache(pool),
        cache=None,
        num_slots=slots,
        max_pages_per_req=max_pages,
        prefill_dispatch=fake_pf,
        prefill_chunk=prefill_chunk,
        token_budget=token_budget,
        prefill_rows_dispatch=fake_pf.rows if row_buckets else None,
        telemetry=telemetry,
    )
    return cb, fake_pf


def test_prefill_flip_inserts_prefix_and_primes_token():
    pool = PagePool(16, 4)
    cb, pf = _paged_batcher(pool)
    prompt = tuple(range(100, 112))  # 12 tokens = 3 full pages of 4
    req = Request(rid=0, new_tokens=3, greedy=True, prompt=prompt)
    assert cb.admit([req], now=0.0) == []
    assert cb._prefilling[0]
    cb.step(now=1.0)  # one chunk of 12 (budget 2 + 16, nothing decoding)
    # flip happened: cursor at the prompt end, first token primed by the
    # chunk's last row, and the decode lane advanced the slot once more in
    # the same step (the planner budgeted for that token)
    assert not cb._prefilling[0]
    assert pf.calls == [(16, 0, 12, prompt)]
    assert req.tokens[0] == prompt[-1] + 1  # fake pf: last fed token + 1
    assert req.t_first == 1.0
    # the prompt's full pages were published at the flip
    assert len(cb.prefix) == 3
    # a second identical prompt adopts the shared pages (minus the last
    # prompt token's page, which stays private)
    req2 = Request(rid=1, new_tokens=1, greedy=True, prompt=prompt)
    assert cb.admit([req2], now=2.0) == []
    assert cb.stats.shared_tokens == 8  # 2 of 3 pages adopted
    while cb.has_work:
        cb.step(now=3.0)
    assert req.done and req2.done
    pool.check()


def test_prefill_budget_splits_with_decoding_slots():
    pool = PagePool(32, 4)
    cb, pf = _paged_batcher(pool, slots=3, prefill_chunk=32, token_budget=12,
                            max_pages=16)
    # two decoding requests occupy the decode lane
    d1 = Request(rid=1, new_tokens=50, greedy=True, first_token=5)
    d2 = Request(rid=2, new_tokens=50, greedy=True, first_token=6)
    p1 = Request(rid=3, new_tokens=2, greedy=True,
                 prompt=tuple(range(200, 240)))  # 40 tokens
    assert cb.admit([d1, d2, p1], now=0.0) == []
    cb.step(now=1.0)
    # budget 12 - 2 decoding = 10 prompt tokens, bucketed to 16
    assert pf.calls[0][0] == 16 and pf.calls[0][2] == 10
    cb.step(now=2.0)
    assert pf.calls[1] == (16, 10, 10, tuple(range(210, 220)))
    # decode lane advanced alongside each chunk
    assert len(d1.tokens) == 2 and len(d2.tokens) == 2
    for _ in range(3):
        cb.step(now=3.0)
    # final-chunk shrink: a chunk that would flip exactly at the budget
    # edge gives up one token so the flip's same-step decode sample stays
    # inside the per-step bound (10 -> 9, then a 1-token flip chunk)
    assert pf.calls[3][2] == 9
    assert pf.calls[4][2] == 1 and not cb._prefilling[2]
    pool.check()


def test_flip_refreshes_decode_block_table():
    """Regression: when the *final* chunk lands in an already-allocated page
    (no growth, no COW), the flip must still rebuild the packed decode
    table — otherwise the flipped slot decodes through its stale all-null
    row (reads garbage, writes the null page)."""
    pool = PagePool(16, 16)  # page_size 16 > prompt: one page, no growth
    seen_bt = []

    def decode_dispatch(bucket):
        def step(cache, tok, pos, bt, active, temps, greedy, keys):
            seen_bt.append(np.array(bt))
            nxt = np.asarray(tok)[:, 0] + 1
            return (nxt, cache,
                    np.asarray(pos) + np.asarray(active).astype(np.int32),
                    keys)
        return step

    fake_pf = _FakePrefill()
    cb = PagedContinuousBatcher(
        dispatch_fn=decode_dispatch,
        pool=pool,
        prefix_cache=PrefixCache(pool),
        cache=None,
        num_slots=2,
        max_pages_per_req=4,
        prefill_dispatch=fake_pf,
        prefill_chunk=8,
        token_budget=64,
    )
    d = Request(rid=0, new_tokens=20, greedy=True, first_token=5)
    p = Request(rid=1, new_tokens=4, greedy=True, prompt=tuple(range(12)))
    assert cb.admit([d, p], now=0.0) == []
    cb.step(now=1.0)  # chunk 1 (8 tokens) + decode; bt row 1 is null
    assert not seen_bt[-1][1].any()
    cb.step(now=2.0)  # chunk 2 (4 tokens, same page) -> flip; decode runs
    assert not cb._prefilling[1]
    # the flipped slot's row now carries its real page, not the null page
    assert seen_bt[-1][1, 0] == cb._tables[1].pages[0] != 0
    pool.check()


def test_preemption_mid_prefill_releases_pages():
    pool = PagePool(4, 4)  # 16 pooled tokens
    cb, pf = _paged_batcher(pool, slots=1, prefill_chunk=8, max_pages=16)
    req = Request(rid=0, new_tokens=2, greedy=True,
                  prompt=tuple(range(300, 330)))  # 30 tokens > pool
    assert cb.admit([req], now=0.0) == []
    for _ in range(8):
        if not cb.has_work:
            break
        cb.step(now=1.0)
    # the growing prefill could not be served: preempted, pages recycled
    assert req in cb.preempted
    assert req.preemptions == 1 and req.tokens == [] and req.t_first is None
    assert pool.pages_in_use == 0
    pool.check()


def test_paged_admission_matches_dense_capacity_rule():
    """Regression: the last generated token is emitted but never written,
    so a request needing exactly max_pages_per_req * page_size KV positions
    must seat — not land in rejected_oversize (the dense admit accepts the
    identical request)."""
    pool = PagePool(8, 16)
    cb, _ = _paged_batcher(pool, slots=1, prefill_chunk=16, max_pages=6)
    # 48 prompt + 49 new = 96 written positions = exactly 6 pages of 16
    req = Request(rid=0, new_tokens=49, greedy=True,
                  prompt=tuple(range(48)))
    assert cb.admit([req], now=0.0) == []
    assert cb.stats.rejected_oversize == 0 and cb.active_count == 1
    # one more token and it can never fit: rejected, not deferred
    req2 = Request(rid=1, new_tokens=50, greedy=True,
                   prompt=tuple(range(48)))
    cb2, _ = _paged_batcher(pool=PagePool(8, 16), slots=1,
                            prefill_chunk=16, max_pages=6)
    cb2.admit([req2], now=0.0)
    assert cb2.stats.rejected_oversize == 1


class _FakeDensePrefill:
    """Records every dense chunk call: (bucket, rows={slot: (start, toks)})."""

    def __init__(self):
        self.calls = []

    def __call__(self, bucket):
        def step(cache, tok, start, length, temps, greedy, keys):
            t, st, ln = np.asarray(tok), np.asarray(start), np.asarray(length)
            rows = {
                s: (int(st[s]), tuple(int(x) for x in t[s, : ln[s]]))
                for s in range(len(ln))
                if ln[s] > 0
            }
            self.calls.append((bucket, rows))
            nxt = np.array(
                [t[s, max(ln[s] - 1, 0)] + 1 for s in range(len(ln))]
            )
            return nxt, cache, keys
        return step


def test_batched_dense_prefill_fills_multiple_slots_per_step():
    """Satellite: the ("pfd", slots, chunk_bucket) executable ingests >1
    prefilling request per step — per-row chunk windows, one call."""
    fake = _FakeDensePrefill()
    cb = ContinuousBatcher(
        step=lambda cache, tok, pos, active, temps, greedy, keys: (
            np.asarray(tok)[:, 0] + 1,
            cache,
            np.asarray(pos) + np.asarray(active).astype(np.int32),
            keys,
        ),
        num_slots=3,
        max_len=64,
        cache=None,
        prefill_dispatch=fake,
        prefill_chunk=16,
        token_budget=32,
    )
    p1 = Request(rid=0, new_tokens=2, greedy=True, prompt=tuple(range(100, 120)))
    p2 = Request(rid=1, new_tokens=2, greedy=True, prompt=tuple(range(200, 212)))
    assert cb.admit([p1, p2], now=0.0) == 2
    cb.step(now=1.0)
    # one executable call carried both slots' chunks (FIFO budget split:
    # slot 0 takes its full 16-chunk, slot 1 the remaining budget)
    assert len(fake.calls) == 1
    bucket, rows = fake.calls[0]
    assert set(rows) == {0, 1}
    assert rows[0] == (0, tuple(range(100, 116)))
    assert rows[1][0] == 0 and len(rows[1][1]) > 0
    assert cb.stats.prefill_chunks == 2  # chunks counted per row
    while cb.has_work:
        cb.step(now=2.0)
    assert p1.done and p2.done


def test_batched_dense_prefill_matches_sequential_chunks(smoke_setup):
    """Satellite acceptance: a multi-request prefill step is bitwise-equal
    to sequential single-request chunks — same emitted tokens and same
    final cache bits whether prompts were ingested together or one at a
    time (rows are independent; per-row masks isolate them)."""
    cfg, params = smoke_setup
    batched = _prompt_reqs(cfg, n=3)
    sequential = _prompt_reqs(cfg, n=3)

    eng = _engine(cfg, params, prefill_chunk=16, paged=False)
    cb = eng.continuous(slots=4)
    cb.admit(batched, now=0.0)  # all three prefill concurrently
    multi_chunk_steps = 0
    while cb.has_work:
        cb.step()
        multi_chunk_steps += len(cb._chunk_slots) > 1
    assert multi_chunk_steps > 0  # some step really batched >1 chunk
    eng.close()

    eng = _engine(cfg, params, prefill_chunk=16, paged=False)
    cb2 = eng.continuous(slots=4)
    for i, r in enumerate(sequential):  # one at a time: no chunk batching
        cb2.admit([r], now=0.0)
        while cb2.has_work:
            cb2.step()
    eng.close()

    for a, b in zip(batched, sequential):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)


def test_batched_paged_prefill_fills_multiple_slots_per_step():
    """Satellite (ISSUE 5): the paged ``("pf", slots, chunk_bucket, ...)``
    executable ingests >1 prefilling request per step — per-row chunk
    windows through per-row block tables, one call — closing PR 4's
    B=1-per-step limitation (mirrors the dense ``("pfd", ...)`` test)."""
    pool = PagePool(32, 4)
    cb, pf = _paged_batcher(pool, slots=3, prefill_chunk=16,
                            token_budget=32, max_pages=16)
    p1 = Request(rid=0, new_tokens=2, greedy=True, prompt=tuple(range(100, 120)))
    p2 = Request(rid=1, new_tokens=2, greedy=True, prompt=tuple(range(200, 212)))
    assert cb.admit([p1, p2], now=0.0) == []
    cb.step(now=1.0)
    # one executable call carried both slots' chunks (FIFO budget split:
    # slot 0 takes its full 16-chunk, slot 1 the remaining budget)
    assert pf.call_rows[0] == 2
    assert pf.calls[0] == (16, 0, 16, tuple(range(100, 116)))
    assert pf.calls[1][1] == 0 and pf.calls[1][2] > 0
    assert cb.stats.prefill_chunks == 2  # chunks counted per row
    assert cb.stats.prefill_calls == 1  # ...but one executable call
    while cb.has_work:
        cb.step(now=2.0)
    assert p1.done and p2.done
    pool.check()


def test_prefill_row_counters_and_pack_span():
    """The prefill call is sized by its chunks: one prompt rides a 1-row
    call, two a 2-row call, three the full 4-slot call. ``prefill_rows``
    sums the real rows, ``prefill_rows_padded`` the rows the calls were
    sized at, and the ``serve.batcher.pack`` span of each prefill call
    carries its row count."""
    from repro.core.telemetry import Telemetry

    tel = Telemetry()
    tel.enable()
    pool = PagePool(64, 4)
    cb, pf = _paged_batcher(pool, slots=4, prefill_chunk=16,
                            token_budget=64, max_pages=16, row_buckets=True,
                            telemetry=tel)

    def prompt(i):
        return tuple(range(100 * (i + 1), 100 * (i + 1) + 16))

    reqs = [Request(rid=i, new_tokens=2, greedy=True, prompt=prompt(i))
            for i in range(6)]
    for group in (reqs[:1], reqs[1:3], reqs[3:]):
        assert cb.admit(group, now=0.0) == []
        cb.step(now=1.0)  # every prompt is one 16-token chunk
        while cb.has_work:
            cb.step(now=2.0)
    assert all(r.done for r in reqs)
    assert pf.row_buckets == [1, 2]  # packed calls, at the top chunk
    assert pf.call_rows == [1, 2, 3]  # ...then one full-width call
    assert cb.stats.prefill_calls == 3
    assert cb.stats.prefill_rows == 1 + 2 + 3
    assert cb.stats.prefill_rows_padded == 1 + 2 + 4
    packs = [e.args["rows"] for e in tel.recorder.events()
             if e.name == "serve.batcher.pack" and e.args["lane"] == "pf"]
    assert packs == [1, 2, 4]
    # every chunk reached the slot its request sat in, tokens intact
    assert sorted(c[3] for c in pf.calls) == sorted(r.prompt for r in reqs)
    assert [r.tokens[0] for r in reqs] == [r.prompt[-1] + 1 for r in reqs]
    pool.check()


def test_row_bucketed_prefill_matches_full_width_bitwise():
    """The ``("pfr", slots, rows, ...)`` executable at two rows against
    the full-width ``("pf", slots, ...)`` one, fed the same real row at
    slot 2 over two chunks: the same priming token, every allocatable
    cache page, and ``new_keys`` for every slot equal; the slots no row
    names keep their keys bitwise."""
    from repro.runtime import steps

    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    S, C, ps, PB, slot = 4, 8, 4, 8, 2
    full = jax.jit(steps.make_paged_prefill_fn(cfg))
    by_rows = jax.jit(steps.make_paged_prefill_fn(cfg, by_rows=True))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 14).astype(np.int32)
    table = np.arange(1 + PB, 1 + 2 * PB, dtype=np.int32)  # private pages
    keys = jnp.asarray(rng.integers(0, 2**32, (S, 2), dtype=np.uint32))
    temps = np.ones(S, np.float32)
    greedy = np.array([True, True, False, True])  # slot 2 samples
    c_full = models.init_paged_cache(cfg, 1 + S * PB, ps)
    c_rows = models.init_paged_cache(cfg, 1 + S * PB, ps)
    k_full = k_rows = keys
    for cursor, n in ((0, 8), (8, 6)):  # the second chunk is padded
        pos = np.array([5, 3, cursor, 7], np.int32)  # other slots: data
        tok = np.zeros((S, C), np.int32)
        tok[slot, :n] = prompt[cursor : cursor + n]
        bt = np.zeros((S, PB), np.int32)
        bt[slot] = table
        length = np.zeros(S, np.int32)
        length[slot] = n
        nxt_f, c_full, k_full = full(
            params, c_full, tok, pos, bt, length, temps, greedy, k_full
        )
        rows = np.array([slot, S], np.int32)  # one real row, one pad row
        nxt_r, c_rows, k_new = by_rows(
            params, c_rows, tok[[slot, 0]], pos, bt[[slot, 0]],
            length[[slot, 0]], temps, greedy, k_rows, rows,
        )
        assert int(nxt_r[slot]) == int(nxt_f[slot])
        np.testing.assert_array_equal(np.asarray(k_new), np.asarray(k_full))
        others = [0, 1, 3]
        np.testing.assert_array_equal(
            np.asarray(k_new)[others], np.asarray(k_rows)[others]
        )
        assert not np.array_equal(
            np.asarray(k_new)[slot], np.asarray(k_rows)[slot]
        )
        k_rows = k_new
    for a, b in zip(jax.tree.leaves(c_full), jax.tree.leaves(c_rows)):
        np.testing.assert_array_equal(
            np.asarray(a)[:, 1:], np.asarray(b)[:, 1:]
        )


def test_batched_paged_prefill_matches_sequential_chunks(smoke_setup):
    """Satellite acceptance (ISSUE 5): a multi-request paged prefill step
    is bitwise-equal to sequential single-request chunks — same emitted
    tokens whether prompts were ingested together or one at a time (rows
    write disjoint private pages; per-row masks isolate the reads)."""
    from repro.runtime.serve import Engine, EngineConfig

    cfg, params = smoke_setup
    batched = _prompt_reqs(cfg, n=3)
    sequential = _prompt_reqs(cfg, n=3)

    eng = _engine(cfg, params, prefill_chunk=16)
    cb = eng.paged_continuous(slots=4)
    cb.admit(batched, now=0.0)  # all three prefill concurrently
    multi_chunk_steps = 0
    while cb.has_work:
        cb.step()
        multi_chunk_steps += len(cb._chunk_slots) > 1
    assert multi_chunk_steps > 0  # some step really batched >1 chunk
    eng.close()

    eng = _engine(cfg, params, prefill_chunk=16)
    cb2 = eng.paged_continuous(slots=4)
    for r in sequential:  # one at a time: no chunk batching, no sharing
        cb2.admit([r], now=0.0)
        while cb2.has_work:
            cb2.step()
        cb2.prefix.clear()  # distinct prompts anyway; keep runs isolated
    eng.close()

    for a, b in zip(batched, sequential):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)


def test_upload_dedup_steady_state():
    """Satellite: steady-state decode re-uploads nothing — only admits,
    flips, finishes, and table growth touch the host->device path."""
    cb = ContinuousBatcher(
        step=lambda cache, tok, pos, active, temps, greedy, keys: (
            np.asarray(tok)[:, 0] + 1,
            cache,
            np.asarray(pos) + np.asarray(active).astype(np.int32),
            keys,
        ),
        num_slots=2,
        max_len=64,
        cache=None,
    )
    cb.admit([
        Request(rid=0, new_tokens=40, greedy=True, first_token=1),
        Request(rid=1, new_tokens=40, greedy=True, first_token=2),
    ])
    cb.step()
    after_first = cb.stats.h2d_uploads
    for _ in range(10):
        cb.step()
    assert cb.stats.h2d_uploads == after_first  # zero per-step churn


def test_admit_double_buffers_uploads_off_issue_path():
    """Satellite: admission stages the edited coordinate arrays into the
    device mirror immediately (overlapping host planning / the in-flight
    step), so the next issue's ``get``s are hits — and the staged copies
    are pure prefetch: later host edits still ``touch`` them away, so the
    emitted stream is unchanged."""
    mk = lambda: ContinuousBatcher(
        step=lambda cache, tok, pos, active, temps, greedy, keys: (
            np.asarray(tok)[:, 0] + 1,
            cache,
            np.asarray(pos) + np.asarray(active).astype(np.int32),
            keys,
        ),
        num_slots=2,
        max_len=64,
        cache=None,
    )
    cb = mk()
    reqs = lambda: [
        Request(rid=0, new_tokens=3, greedy=True, first_token=1),
        Request(rid=1, new_tokens=3, greedy=True, first_token=2),
    ]
    cb.admit(reqs())
    staged = cb.stats.h2d_overlapped
    assert staged > 0  # tok/pos/active/... staged at admission
    assert cb.stats.h2d_uploads == staged  # no issue yet: all overlapped
    u0 = cb.stats.h2d_uploads
    cb.step()
    # first issue rode the staged copies: no re-upload of a staged name
    assert cb.stats.h2d_uploads == u0
    done = []
    for _ in range(5):
        done += cb.step()
    # the prefetch changed data movement only, never tokens
    ref_reqs = reqs()
    ref = mk()
    ref._mirror.preload = lambda name, host: None  # disable the prefetch
    ref.admit(ref_reqs)
    ref_done = []
    for _ in range(6):
        ref_done += ref.step()
    assert ref.stats.h2d_overlapped == 0
    got = {r.rid: r.tokens for r in done}
    want = {r.rid: r.tokens for r in ref_done}
    assert got == want and len(got) == 2


# ----------------------------------------------------- end-to-end (smoke)
@pytest.fixture(scope="module")
def smoke_setup():
    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt_reqs(cfg, n=3, prompt_len=24, new_tokens=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i, new_tokens=new_tokens, greedy=True, arrival_s=0.0,
            prompt=tuple(
                int(x) for x in rng.integers(0, cfg.vocab_size, prompt_len)
            ),
        )
        for i in range(n)
    ]


def _engine(cfg, params, *, prefill_chunk, paged=True):
    from repro.runtime.serve import Engine, EngineConfig

    reset_entry_points()
    return Engine(
        cfg,
        params,
        EngineConfig(
            max_len=64,
            batch_quantum=2,
            max_batch=4,
            page_size=8,
            num_pages=40,
            prefill_chunk=prefill_chunk,
        ),
    )


def test_chunked_stream_matches_sequential_stream(smoke_setup):
    """The acceptance contract: chunked prefill emits exactly the tokens
    token-by-token forcing emits, with zero compiles after warmup."""
    from repro.runtime.serve import run_paged_stream

    cfg, params = smoke_setup
    chunked_reqs = _prompt_reqs(cfg)
    legacy_reqs = _prompt_reqs(cfg)

    eng = _engine(cfg, params, prefill_chunk=16)
    rep_c = run_paged_stream(eng, chunked_reqs, slots=4)
    eng.close()
    eng = _engine(cfg, params, prefill_chunk=0)
    rep_s = run_paged_stream(eng, legacy_reqs, slots=4)
    eng.close()

    assert rep_c["finished"] == len(chunked_reqs)
    assert rep_c["compiles_after_warmup"] == 0
    assert rep_c["prefill_chunks"] > 0
    assert rep_c["steps"] < rep_s["steps"]  # chunks collapse the ingest loop
    for a, b in zip(chunked_reqs, legacy_reqs):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
    # TTFT is tracked for both engines
    assert "ttft_p95_ms" in rep_c and "ttft_p95_ms" in rep_s


def test_dense_chunked_stream_aligns_with_paged(smoke_setup):
    """Satellite: the dense engine's prompt path goes through the same
    chunked prefill and emits the same tokens as the paged engine."""
    from repro.runtime.serve import run_continuous_stream, run_paged_stream

    cfg, params = smoke_setup
    dense_reqs = _prompt_reqs(cfg)
    paged_reqs = _prompt_reqs(cfg)

    eng = _engine(cfg, params, prefill_chunk=16)
    run_paged_stream(eng, paged_reqs, slots=4)
    eng.close()
    eng = _engine(cfg, params, prefill_chunk=16)
    rep_d = run_continuous_stream(eng, dense_reqs, slots=4)
    eng.close()

    assert rep_d["compiles_after_warmup"] == 0
    assert rep_d["prefill_chunks"] > 0
    for a, b in zip(dense_reqs, paged_reqs):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
