"""GQA attention: full-sequence (train/prefill) and single-token decode paths.

Two implementations selectable as a *semi-static* choice (DESIGN.md §2):
  * ``naive``   — materialise [B,KH,G,S,S] scores (paper-faithful baseline; what
                  a straight port compiles to)
  * ``chunked`` — lax.scan over KV blocks with online softmax (flash-style data
                  movement in pure JAX; the beyond-paper memory-term optimisation)

These pure-JAX paths are what runs everywhere, the TPU included: nothing
here calls the Pallas kernels in ``repro.kernels``, which are tested in
interpret mode and compiled for a described chip by
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import perf
from repro.configs import ArchConfig
from repro.distributed.sharding import hint, hint_attn_q

from .layers import apply_rope, dense_init, dtype_of, rms_norm, softcap

NEG_INF = -2.0e38


def attn_init(cfg: ArchConfig, key: jax.Array) -> dict:
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.num_heads, cfg.head_dim), dt),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.num_kv_heads, cfg.head_dim), dt),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.num_kv_heads, cfg.head_dim), dt),
        "wo": dense_init(ks[3], (cfg.num_heads, cfg.head_dim, cfg.d_model), dt),
    }
    if cfg.qk_norm:
        p["q_scale"] = jnp.zeros((cfg.head_dim,), dt)
        p["k_scale"] = jnp.zeros((cfg.head_dim,), dt)
    return p


def _qkv(cfg: ArchConfig, p: dict, x: jax.Array, positions: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_scale"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group(cfg: ArchConfig, q: jax.Array) -> jax.Array:
    """[B,S,H,dh] -> [B,S,KH,G,dh]."""
    b, s, h, dh = q.shape
    g = h // cfg.num_kv_heads
    return q.reshape(b, s, cfg.num_kv_heads, g, dh)


def _mask(
    s_q: int,
    s_k: int,
    *,
    causal: bool,
    window: int | None,
    q_offset: int = 0,
    dtype=jnp.float32,
) -> jax.Array:
    """[S_q, S_k] additive mask (0 / -inf-ish in the scores dtype)."""
    qi = jnp.arange(s_q)[:, None] + q_offset
    ki = jnp.arange(s_k)[None, :]
    ok = jnp.ones((s_q, s_k), jnp.bool_)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    neg = jnp.asarray(jnp.finfo(dtype).min / 2, dtype)
    return jnp.where(ok, jnp.zeros((), dtype), neg)


def _sdpa_naive(
    cfg: ArchConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int | None,
) -> jax.Array:
    """q: [B,Sq,KH,G,dh]; k,v: [B,Sk,KH,dh] -> [B,Sq,KH,G,dh]."""
    scale = 1.0 / np.sqrt(cfg.head_dim)
    po = perf.current()
    sd = jnp.dtype(po.score_dtype) if po.score_dtype else jnp.float32
    # preferred_element_type at the dot itself: otherwise the QK^T dot
    # materialises an f32 accumulator tensor and converts afterwards
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=sd
    ) * jnp.asarray(scale, sd)
    scores = softcap(scores, cfg.attn_logit_softcap)
    scores = scores + _mask(
        q.shape[1], k.shape[1], causal=True, window=window, dtype=sd
    )
    probs = jax.nn.softmax(scores, axis=-1).astype(po.probs_dtype or v.dtype)
    return jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs, v.astype(probs.dtype)
    ).astype(v.dtype)


def _sdpa_chunked(
    cfg: ArchConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int | None,
    block: int = 1024,
) -> jax.Array:
    """Online-softmax over KV blocks: O(S·block) score memory instead of O(S²)."""
    b, sq, kh, g, dh = q.shape
    sk = k.shape[1]
    block = min(block, sk)
    assert sk % block == 0, (sk, block)
    nblk = sk // block
    scale = 1.0 / np.sqrt(cfg.head_dim)
    kb = k.reshape(b, nblk, block, kh, dh)
    vb = v.reshape(b, nblk, block, kh, dh)
    qf = q.astype(jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kv_i, (k_i, v_i) = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_i.astype(jnp.float32)) * scale
        s = softcap(s, cfg.attn_logit_softcap)
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(block)[None, :] + kv_i * block
        ok = ki <= qi
        if window is not None:
            ok &= ki > qi - window
        s = s + jnp.where(ok, 0.0, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pd = perf.current().probs_dtype
        if pd is not None:  # cheaper PV matmul traffic (perf opt)
            pv = jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(pd), v_i.astype(pd)
            ).astype(jnp.float32)
        else:
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_i.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kh, g, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (jnp.arange(nblk), (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0))),
    )
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return jnp.moveaxis(out, -2, 1).astype(v.dtype)  # [B,Sq,KH,G,dh]


def attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    local: bool,
    impl: str = "naive",
) -> jax.Array:
    """Full-sequence causal attention. x: [B,S,D] -> [B,S,D]."""
    window = cfg.sliding_window if local else None
    po = perf.current()
    if impl == "auto":
        impl = po.impl
    q, k, v = _qkv(cfg, p, x, positions)
    q = hint_attn_q(q)
    k = hint(k, "batch", None, "model", None)
    v = hint(v, "batch", None, "model", None)
    qg = _group(cfg, q)
    if impl == "chunked":
        og = _sdpa_chunked(cfg, qg, k, v, window=window, block=po.attn_block)
    else:
        og = _sdpa_naive(cfg, qg, k, v, window=window)
    b, s = x.shape[:2]
    o = og.reshape(b, s, cfg.num_heads, cfg.head_dim)
    o = hint_attn_q(o)
    return hint(jnp.einsum("bshk,hkd->bsd", o, p["wo"]), "batch", None, None)


# -------------------------------------------------------------------- decode
def _decode_sdpa_rows(
    cfg: ArchConfig,
    p: dict,
    q: jax.Array,
    keys: jax.Array,
    vals: jax.Array,
    pos: jax.Array,
    *,
    local: bool,
) -> jax.Array:
    """Per-row masked SDPA tail shared by dense per-row decode, paged
    decode, and the chunked prefill paths: q [B,Sq,H,dh]; keys/vals
    [B,L,KH,dh] (each row's *logical* cache view — dense rows or gathered
    pages); pos is i32[B] (one query per row, Sq == 1) or i32[B,Sq]
    (per-query causal frontiers — chunked prefill, DESIGN.md §10). One
    implementation so the paged path's bit-for-bit-equals-dense guarantee
    (DESIGN.md §9) can't drift. Returns the projected output [B,Sq,D]."""
    b, sq = q.shape[:2]
    qg = _group(cfg, q)  # [B,Sq,KH,G,dh]
    scale = 1.0 / np.sqrt(cfg.head_dim)
    scores = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, keys).astype(jnp.float32) * scale
    )
    scores = softcap(scores, cfg.attn_logit_softcap)
    ki = jnp.arange(keys.shape[1])
    if pos.ndim == 2:  # [B,Sq]: each chunk row has its own causal frontier
        ok = ki[None, None, :] <= pos[:, :, None]  # [B,Sq,L]
        if local and cfg.sliding_window is not None:
            ok &= ki[None, None, :] > pos[:, :, None] - cfg.sliding_window
        scores = scores + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :, :]
    else:
        ok = ki[None, :] <= pos[:, None]  # [B,L]
        if local and cfg.sliding_window is not None:
            ok &= ki[None, :] > pos[:, None] - cfg.sliding_window
        scores = scores + jnp.where(ok, 0.0, NEG_INF)[:, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(vals.dtype)
    og = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vals)
    o = og.reshape(b, sq, cfg.num_heads, cfg.head_dim)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def init_kv_cache(
    cfg: ArchConfig, batch: int, max_len: int, kv_dtype: str = "fp32"
) -> dict:
    """Dense per-slot KV cache. ``kv_dtype="int8"`` stores quantised rows
    plus per-(row, position) scales — the draft lanes' storage coordinate
    (DESIGN.md §16); decode paths detect the dtype from the cache leaves,
    so one semi-static executable exists per storage format."""
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    if kv_dtype == "int8":
        sc = (batch, max_len)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "ks": jnp.zeros(sc, jnp.float32),
            "vs": jnp.zeros(sc, jnp.float32),
        }
    if kv_dtype != "fp32":
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
    dt = dtype_of(cfg)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def prefill_attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    local: bool,
    impl: str = "naive",
) -> tuple[jax.Array, dict]:
    """Full-sequence attention that also returns the populated KV cache."""
    window = cfg.sliding_window if local else None
    po = perf.current()
    if impl == "auto":
        impl = po.impl
    q, k, v = _qkv(cfg, p, x, positions)
    q = hint_attn_q(q)
    k = hint(k, "batch", None, "model", None)
    v = hint(v, "batch", None, "model", None)
    qg = _group(cfg, q)
    if impl == "chunked":
        og = _sdpa_chunked(cfg, qg, k, v, window=window, block=po.attn_block)
    else:
        og = _sdpa_naive(cfg, qg, k, v, window=window)
    b, s = x.shape[:2]
    o = og.reshape(b, s, cfg.num_heads, cfg.head_dim)
    o = hint_attn_q(o)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), {"k": k, "v": v}


def decode_attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    cache: dict,
    pos: jax.Array,
    *,
    local: bool,
) -> tuple[jax.Array, dict]:
    """One-token decode. x: [B,1,D]; cache k/v: [B,Smax,KH,dh].

    ``pos`` is either a scalar (the whole batch sits at one position — the
    classic bucketed-burst engine) or a vector ``[B]`` of per-row positions
    (continuous batching, DESIGN.md §4: slots join and leave mid-loop, each
    at its own depth). The per-row form writes the new K/V with a one-hot
    scatter and masks attention per row, so a slot that just joined at
    position 0 never sees the previous occupant's stale cache rows.

    No head hints here: the cache's seq dim owns the model axis (flash-decode
    style distributed softmax via partial-reduce + all-reduce).
    """
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _qkv(cfg, p, x, positions)
    q = hint(q, "batch", None, None, None)
    ki = jnp.arange(cache["k"].shape[1])
    if cache["k"].dtype == jnp.int8:
        # Quantised dense rows (draft lanes, DESIGN.md §16): scatter the new
        # row as int8 + its scale, dequantise the whole view for the shared
        # SDPA tail. Per-row form only — the scalar-pos burst engine has no
        # int8 coordinate.
        if not per_row:
            raise ValueError("int8 dense KV caches require per-row pos [B]")
        qk, ksc = quantise_kv_rows(k[:, 0])  # [B,KH,dh] -> int8 + [B]
        qv, vsc = quantise_kv_rows(v[:, 0])
        sel = ki[None, :] == pos[:, None]  # [B,S]
        sel4 = sel[:, :, None, None]
        ckq = jnp.where(sel4, qk[:, None], cache["k"])
        cvq = jnp.where(sel4, qv[:, None], cache["v"])
        cks = jnp.where(sel, ksc[:, None], cache["ks"])
        cvs = jnp.where(sel, vsc[:, None], cache["vs"])
        ck = dequantise_kv_rows(ckq, cks)
        cv = dequantise_kv_rows(cvq, cvs)
        return (
            _decode_sdpa_rows(cfg, p, q, ck, cv, pos, local=local),
            {"k": ckq, "v": cvq, "ks": cks, "vs": cvs},
        )
    if per_row:
        sel = (ki[None, :] == pos[:, None])[:, :, None, None]  # [B,S,1,1]
        ck = jnp.where(sel, k, cache["k"])
        cv = jnp.where(sel, v, cache["v"])
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
    if per_row:
        return (
            _decode_sdpa_rows(cfg, p, q, ck, cv, pos, local=local),
            {"k": ck, "v": cv},
        )
    qg = _group(cfg, q)  # [B,1,KH,G,dh]
    scale = 1.0 / np.sqrt(cfg.head_dim)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck).astype(jnp.float32) * scale
    scores = softcap(scores, cfg.attn_logit_softcap)
    ok = ki <= pos
    if local and cfg.sliding_window is not None:
        ok &= ki > pos - cfg.sliding_window
    scores = scores + jnp.where(ok, 0.0, NEG_INF)[None, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    og = jnp.einsum("bhgqk,bkhd->bqhgd", probs, cv)
    o = og.reshape(b, 1, cfg.num_heads, cfg.head_dim)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), {"k": ck, "v": cv}


# ------------------------------------------------------------- paged decode
# int8 KV quantisation range (DESIGN.md §12): symmetric, full int8 span.
KV_QUANT_MAX = 127.0
KV_SCALE_EPS = 1e-8  # all-zero rows quantise with a tiny non-zero scale

# One domain for the kv_dtype dispatch coordinate: runtime/kvcache.py (the
# host-side page accounting, stdlib-only) is canonical; validating against
# a second copy here would let the two sites drift.
from repro.runtime.kvcache import KV_DTYPES  # noqa: E402


def quantise_kv_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-token-row symmetric int8 quantisation (DESIGN.md §12).

    ``x``: ``[..., KH, dh]`` K or V rows in the model dtype. Each *row*
    (one token's heads×dims) gets its own absmax scale, so a page of
    ``page_size`` tokens carries ``page_size`` scales — the per-page scale
    array that rides the pooled cache. Returns ``(q int8[...], scale
    f32[...])`` with the trailing two axes reduced out of ``scale``.

    One shared implementation for the decode scatter, the chunked-prefill
    scatter, and the kernels' oracles: the written bits are identical
    whichever lane wrote them, which is what keeps int8 chunked ingestion
    bit-for-bit equal to int8 token-by-token decode.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.maximum(amax / KV_QUANT_MAX, KV_SCALE_EPS)
    q = jnp.clip(
        jnp.round(xf / scale[..., None, None]), -KV_QUANT_MAX, KV_QUANT_MAX
    ).astype(jnp.int8)
    return q, scale


def dequantise_kv_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of ``quantise_kv_rows``: ``q [..., KH, dh]`` int8 rows times
    their per-row scales ``[...]`` -> f32 rows."""
    return q.astype(jnp.float32) * scale[..., None, None]


def init_paged_kv_cache(
    cfg: ArchConfig, num_pages: int, page_size: int, kv_dtype: str = "fp32"
) -> dict:
    """Pooled KV pages shared by every request (DESIGN.md §9).

    ``num_pages`` counts *total* physical pages including the reserved null
    page 0 (``kvcache.PagePool(n, ps)`` needs ``n + 1`` here). Unlike the
    dense cache there is no batch axis: concurrency is bounded by pages, not
    by ``B × max_len``.

    ``kv_dtype`` is the page storage dtype — a *dispatch coordinate*
    (DESIGN.md §12), not a hot-loop branch: ``"fp32"`` stores pages in the
    model dtype; ``"int8"`` stores int8 pages plus per-page scale arrays
    (``k_scale``/``v_scale``, f32 ``[P, page_size]`` — one scale per token
    row) that are scattered on write and gathered on read alongside the
    pages themselves. The executables specialise on the cache's abstract
    dtype at trace time.
    """
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}"
        )
    shape = (num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    if kv_dtype == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:2], jnp.float32),
            "v_scale": jnp.zeros(shape[:2], jnp.float32),
        }
    dt = dtype_of(cfg)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_decode_attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    cache: dict,
    pos: jax.Array,
    block_tables: jax.Array,
    *,
    local: bool,
) -> tuple[jax.Array, dict]:
    """One-token decode through a paged KV cache.

    x: [B,1,D]; cache k/v: [P, page_size, KH, dh] (pooled pages);
    ``block_tables``: i32[B, pages_bucket] page ids mapping each row's
    logical positions onto physical pages (0 = the null page); ``pos``:
    i32[B] per-row positions.

    The write is a scatter into ``pages[bt[b, pos//ps], pos%ps]``; the hot
    loop never checks capacity — the table's width (``pages_bucket``) is a
    compile-time constant, and growing past it is a cold-path rebind to the
    next bucket's executable (DESIGN.md §9). Inactive slots carry all-null
    tables so their writes land in the null page, which no live table
    references. The read is a page gather; positions past ``pos`` (incl.
    whatever garbage the null page holds) are masked exactly like the dense
    per-row path, so paged and dense decode agree bit-for-bit.

    With an int8 cache (DESIGN.md §12) the write quantises each new K/V row
    (per-row absmax scale, ``quantise_kv_rows``) and scatters row + scale;
    the read gathers pages *and* scales and dequantises before the shared
    SDPA tail. The branch is on the cache's abstract dtype — trace-time,
    one executable per ``kv_dtype`` coordinate, never a hot-loop check.

    This pure-JAX gather + SDPA is what runs on every backend, the TPU
    included. ``kernels.paged_decode_attention`` computes the same thing
    in place but is not called from here: the TPU compiler refuses its
    page block (``tests/test_tpu_compile.py``).
    """
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)
    num_pages, ps = cache["k"].shape[:2]
    pages_bucket = bt.shape[1]
    positions = pos[:, None]
    q, k, v = _qkv(cfg, p, x, positions)
    q = hint(q, "batch", None, None, None)
    # ---- write: scatter the new K/V row into each request's current page
    page_idx = jnp.clip(pos // ps, 0, pages_bucket - 1)
    wpage = jnp.take_along_axis(bt, page_idx[:, None], axis=1)[:, 0]
    woff = pos % ps
    seq = pages_bucket * ps
    if cache["k"].dtype == jnp.int8:  # trace-time: dtype is a dispatch key
        qk, ksc = quantise_kv_rows(k[:, 0])
        qv, vsc = quantise_kv_rows(v[:, 0])
        ck = cache["k"].at[wpage, woff].set(qk)
        cv = cache["v"].at[wpage, woff].set(qv)
        cks = cache["k_scale"].at[wpage, woff].set(ksc)
        cvs = cache["v_scale"].at[wpage, woff].set(vsc)
        gk = dequantise_kv_rows(ck[bt], cks[bt]).reshape(
            b, seq, cfg.num_kv_heads, cfg.head_dim
        )
        gv = dequantise_kv_rows(cv[bt], cvs[bt]).reshape(
            b, seq, cfg.num_kv_heads, cfg.head_dim
        )
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        ck = cache["k"].at[wpage, woff].set(k[:, 0])
        cv = cache["v"].at[wpage, woff].set(v[:, 0])
        # ---- read: gather each request's pages into its logical view
        gk = ck[bt].reshape(b, seq, cfg.num_kv_heads, cfg.head_dim)
        gv = cv[bt].reshape(b, seq, cfg.num_kv_heads, cfg.head_dim)
        new_cache = {"k": ck, "v": cv}
    return _decode_sdpa_rows(cfg, p, q, gk, gv, pos, local=local), new_cache


# ----------------------------------------------------------- chunked prefill
def paged_prefill_attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    cache: dict,
    start: jax.Array,
    block_tables: jax.Array,
    length: jax.Array,
    *,
    local: bool,
) -> tuple[jax.Array, dict]:
    """Chunk-of-C-tokens prompt ingestion through the paged KV cache.

    x: [B,C,D] chunk embeddings; cache k/v: [P, page_size, KH, dh];
    ``start``: i32[B] logical position of each row's first chunk token;
    ``length``: i32[B] real tokens in the chunk (columns >= length are
    bucket padding); ``block_tables``: i32[B, pages_bucket].

    Scatter-writes all C new K/V positions through the block table in one
    step — padded columns are redirected to the null page 0, so bucket
    padding never corrupts live pages — then attends causally over the
    gathered pages: query row i sees logical positions <= start+i, which
    covers both the pre-existing cache and the in-flight chunk (the chunk's
    own K/V is read back from the pages it just wrote). Bit-for-bit equal
    on CPU to C iterations of ``paged_decode_attention``: future chunk rows
    are masked to exactly-zero probability, so their (different) garbage
    contributes exactly 0.0 to every softmax sum (DESIGN.md §10).

    C (the chunk bucket) is a compile-time constant — the semi-static chunk
    key ``("pf", slots, chunk_bucket, kv_dtype)`` — so chunk-size variation
    dispatches on the cold path and never branches per step.
    """
    b, c = x.shape[:2]
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    bt = jnp.asarray(block_tables, jnp.int32)
    _, ps = cache["k"].shape[:2]
    pages_bucket = bt.shape[1]
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = start[:, None] + offs[None, :]  # [B,C]
    q, k, v = _qkv(cfg, p, x, positions)
    q = hint(q, "batch", None, None, None)
    # ---- write: scatter every real chunk row through the block table;
    # padded rows land in the reserved null page (id 0).
    page_idx = jnp.clip(positions // ps, 0, pages_bucket - 1)
    wpage = jnp.take_along_axis(bt, page_idx, axis=1)  # [B,C]
    wpage = jnp.where(offs[None, :] < length[:, None], wpage, 0)
    woff = positions % ps
    seq = pages_bucket * ps
    if cache["k"].dtype == jnp.int8:  # trace-time: dtype is a dispatch key
        # per-row scales, identical math to the decode scatter — int8
        # chunked ingestion writes the same bits as int8 token-by-token
        qk, ksc = quantise_kv_rows(k)
        qv, vsc = quantise_kv_rows(v)
        ck = cache["k"].at[wpage, woff].set(qk)
        cv = cache["v"].at[wpage, woff].set(qv)
        cks = cache["k_scale"].at[wpage, woff].set(ksc)
        cvs = cache["v_scale"].at[wpage, woff].set(vsc)
        gk = dequantise_kv_rows(ck[bt], cks[bt]).reshape(
            b, seq, cfg.num_kv_heads, cfg.head_dim
        )
        gv = dequantise_kv_rows(cv[bt], cvs[bt]).reshape(
            b, seq, cfg.num_kv_heads, cfg.head_dim
        )
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        ck = cache["k"].at[wpage, woff].set(k)
        cv = cache["v"].at[wpage, woff].set(v)
        # ---- read: gather pages, mask per query row (causal in the chunk)
        gk = ck[bt].reshape(b, seq, cfg.num_kv_heads, cfg.head_dim)
        gv = cv[bt].reshape(b, seq, cfg.num_kv_heads, cfg.head_dim)
        new_cache = {"k": ck, "v": cv}
    return (
        _decode_sdpa_rows(cfg, p, q, gk, gv, positions, local=local),
        new_cache,
    )


def chunked_decode_attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    cache: dict,
    start: jax.Array,
    length: jax.Array,
    *,
    local: bool,
) -> tuple[jax.Array, dict]:
    """Chunk-of-C-tokens prompt ingestion into the dense per-slot cache.

    x: [B,C,D]; cache k/v: [B,Smax,KH,dh]; ``start``: i32[B] per-row first
    chunk position; ``length``: i32[B] real tokens (rows with length 0 are
    idle and write nothing). The dense-cache counterpart of
    ``paged_prefill_attention`` — a slot's private cache rows are just a
    trivial identity block table (DESIGN.md §10) — generalising
    ``decode_attention``'s per-row one-token path to C tokens: the chunk is
    inserted with a per-row masked select and each query row is causally
    masked at its own position, so join/leave isolation holds exactly as in
    the single-token path. Bit-for-bit equal on CPU to C iterations of the
    per-row ``decode_attention``.
    """
    b, c = x.shape[:2]
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = start[:, None] + offs[None, :]  # [B,C]
    q, k, v = _qkv(cfg, p, x, positions)
    q = hint(q, "batch", None, None, None)
    ki = jnp.arange(cache["k"].shape[1])
    # masked insert: cache row j takes chunk row j-start when it is inside
    # this row's [start, start+length) write window
    sel = (ki[None, :] >= start[:, None]) & (
        ki[None, :] < start[:, None] + length[:, None]
    )  # [B,Smax]
    idx = jnp.clip(ki[None, :] - start[:, None], 0, c - 1)  # [B,Smax]
    sel4 = sel[:, :, None, None]
    idx4 = idx[:, :, None, None]
    if cache["k"].dtype == jnp.int8:
        # int8 chunk ingestion (draft prompt mirror, DESIGN.md §16): the
        # chunk's rows quantise once, then insert exactly like the fp32
        # path — bitwise equal to C iterations of the int8 per-row decode
        # because the per-row scales are position-local.
        qk, ksc = quantise_kv_rows(k)  # [B,C,KH,dh] -> int8 + [B,C]
        qv, vsc = quantise_kv_rows(v)
        ckq = jnp.where(sel4, jnp.take_along_axis(qk, idx4, axis=1), cache["k"])
        cvq = jnp.where(sel4, jnp.take_along_axis(qv, idx4, axis=1), cache["v"])
        cks = jnp.where(sel, jnp.take_along_axis(ksc, idx, axis=1), cache["ks"])
        cvs = jnp.where(sel, jnp.take_along_axis(vsc, idx, axis=1), cache["vs"])
        ck = dequantise_kv_rows(ckq, cks)
        cv = dequantise_kv_rows(cvq, cvs)
        return (
            _decode_sdpa_rows(cfg, p, q, ck, cv, positions, local=local),
            {"k": ckq, "v": cvq, "ks": cks, "vs": cvs},
        )
    ck = jnp.where(sel4, jnp.take_along_axis(k, idx4, axis=1), cache["k"])
    cv = jnp.where(sel4, jnp.take_along_axis(v, idx4, axis=1), cache["v"])
    return (
        _decode_sdpa_rows(cfg, p, q, ck, cv, positions, local=local),
        {"k": ck, "v": cv},
    )
