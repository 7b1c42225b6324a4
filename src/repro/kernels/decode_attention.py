"""Pallas TPU single-token GQA decode attention, blocked over the KV cache.

One query token per sequence attends over a [B, KH, S, dh] cache. The grid is
(B, KH, S/bk); each step loads the q-head *group* for its kv head ([G, dh]) and
one KV block, carrying the online-softmax state in VMEM scratch. The cache
length (current position) arrives as a prefetched scalar so fully-out-of-range
blocks are skipped structurally.

Mode (window / softcap) is semi-statically specialised exactly as in
flash_attention.py — a gemma2 local layer and a global layer are two different
compiled kernels, not one kernel with a flag.

``paged_decode_attention`` is the paged-KV variant (DESIGN.md §9): K/V live in
a page pool ``[P, page_size, KH, dh]`` and each sequence's logical cache is an
ordered *block table* of page ids. The block table rides in as a prefetched
scalar array, so the page gather is an **index-map indirection** — the kernel
body is identical online-softmax work; only the BlockSpec's index map chases
``block_table[b, j]`` instead of a dense offset. The number of table columns
(``pages_bucket``) is a compile-time constant per kernel: capacity is a
semi-static dispatch key, never a hot-loop branch.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _make_kernel(
    *,
    window: Optional[int],
    softcap: Optional[float],
    block_k: int,
    group: int,
    sm_scale: float,
    num_k_blocks: int,
):
    def kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        kb = pl.program_id(2)
        pos = pos_ref[0]

        @pl.when(kb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # structural skips: blocks past the cache position, or (window mode)
        # blocks entirely before the window.
        run = kb * block_k <= pos
        if window is not None:
            run = jnp.logical_and(run, kb * block_k + block_k - 1 > pos - window)

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32)  # [G, dh]
            k = k_ref[0, 0].astype(jnp.float32)  # [bk, dh]
            v = v_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ()))
            ) * sm_scale  # [G, bk]
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            ki = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (group, block_k), 1
            )
            s = jnp.where(ki <= pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(ki > pos - window, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
            m_scr[...] = m_new
            acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ()))
            )

        @pl.when(kb == num_k_blocks - 1)
        def _finalize():
            l = jnp.maximum(l_scr[...], 1e-37)
            o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)

    return kernel


def decode_attention(
    q: jax.Array,  # [B, H, dh] one token per sequence
    k: jax.Array,  # [B, KH, S, dh]
    v: jax.Array,
    pos: jax.Array,  # i32[] current cache position (inclusive)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, dh = q.shape
    _, kh, s, _ = k.shape
    assert h % kh == 0
    group = h // kh
    block_k = min(block_k, s)
    assert s % block_k == 0
    nk = s // block_k
    sm_scale = 1.0 / np.sqrt(dh)
    qg = q.reshape(b, kh, group, dh)

    kernel = _make_kernel(
        window=window,
        softcap=softcap,
        block_k=block_k,
        group=group,
        sm_scale=sm_scale,
        num_k_blocks=nk,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, group, dh), lambda b_, h_, kb, pos: (b_, h_, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, dh), lambda b_, h_, kb, pos: (b_, h_, kb, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, dh), lambda b_, h_, kb, pos: (b_, h_, kb, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, dh), lambda b_, h_, kb, pos: (b_, h_, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), qg, k, v)
    return out.reshape(b, h, dh)


# ----------------------------------------------------------------- paged path
def _make_paged_kernel(
    *,
    window: Optional[int],
    softcap: Optional[float],
    page_size: int,
    group: int,
    sm_scale: float,
    num_pages_per_req: int,
    quantised: bool = False,
):
    """One online-softmax body for both page dtypes (DESIGN.md §12).

    ``quantised`` is a *trace-time* flag: True adds two per-token-row scale
    operands (gathered through the same block-table index maps) and one
    in-register dequant multiply after each K/V load. fp32 and int8 are
    still two separately compiled branch targets — the flag specialises the
    kernel, it never branches at runtime — but the masking/softmax body is
    written exactly once.
    """

    def kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest):
        if quantised:
            ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
        else:
            o_ref, m_scr, l_scr, acc_scr = rest
        b = pl.program_id(0)
        pb = pl.program_id(2)
        pos = pos_ref[b]

        @pl.when(pb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # structural skips: logical pages past this row's position, or
        # (window mode) pages entirely before the window.
        run = pb * page_size <= pos
        if window is not None:
            run = jnp.logical_and(
                run, pb * page_size + page_size - 1 > pos - window
            )

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32)  # [G, dh]
            k = k_ref[0, :, 0].astype(jnp.float32)  # [ps, dh]
            v = v_ref[0, :, 0].astype(jnp.float32)
            if quantised:  # dequant: int8 rows x their per-row scales
                k = k * ks_ref[0][:, None]
                v = v * vs_ref[0][:, None]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ()))
            ) * sm_scale  # [G, ps]
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            ki = pb * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (group, page_size), 1
            )
            s = jnp.where(ki <= pos, s, NEG_INF)
            if window is not None:
                s = jnp.where(ki > pos - window, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
            m_scr[...] = m_new
            acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ()))
            )

        @pl.when(pb == num_pages_per_req - 1)
        def _finalize():
            l = jnp.maximum(l_scr[...], 1e-37)
            o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)

    return kernel


def _paged_decode_call(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    scales: tuple[jax.Array, jax.Array] | None,
    *,
    window: Optional[int],
    softcap: Optional[float],
    interpret: bool,
) -> jax.Array:
    """Shared grid/spec plumbing for the fp32 and int8 public entry points;
    ``scales`` (k_scale, v_scale) present selects the quantised kernel."""
    b, h, dh = q.shape
    _, page_size, kh, _ = k_pages.shape
    assert h % kh == 0
    _, npages = block_tables.shape
    group = h // kh
    sm_scale = 1.0 / np.sqrt(dh)
    qg = q.reshape(b, kh, group, dh)

    kernel = _make_paged_kernel(
        window=window,
        softcap=softcap,
        page_size=page_size,
        group=group,
        sm_scale=sm_scale,
        num_pages_per_req=npages,
        quantised=scales is not None,
    )
    # page indirection: every per-page operand's index map chases the
    # prefetched block table (scale pages included)
    page_spec = pl.BlockSpec(
        (1, page_size, 1, dh),
        lambda b_, h_, pb, bt, pos_: (bt[b_, pb], 0, h_, 0),
    )
    scale_spec = pl.BlockSpec(
        (1, page_size), lambda b_, h_, pb, bt, pos_: (bt[b_, pb], 0)
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, group, dh), lambda b_, h_, pb, bt, pos_: (b_, h_, 0, 0)
        ),
        page_spec,
        page_spec,
    ]
    operands = [qg, k_pages, v_pages]
    if scales is not None:
        in_specs += [scale_spec, scale_spec]
        operands += [jnp.asarray(s, jnp.float32) for s in scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (block_tables, pos)
        grid=(b, kh, npages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, group, dh), lambda b_, h_, pb, bt, pos_: (b_, h_, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(pos, jnp.int32),
        *operands,
    )
    return out.reshape(b, h, dh)


def paged_decode_attention(
    q: jax.Array,  # [B, H, dh] one token per sequence
    k_pages: jax.Array,  # [P, page_size, KH, dh] pooled pages
    v_pages: jax.Array,
    block_tables: jax.Array,  # i32[B, pages_bucket] page ids (0 = null page)
    pos: jax.Array,  # i32[B] per-row positions (inclusive)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Block-table-gather decode attention over a page pool.

    The logical cache row ``j`` of sequence ``b`` lives at
    ``k_pages[block_tables[b, j // ps], j % ps]``. The gather happens in the
    BlockSpec index map via the prefetched table; page count per request is a
    compile-time constant (the semi-static ``pages_bucket``).
    """
    return _paged_decode_call(
        q, k_pages, v_pages, block_tables, pos, None,
        window=window, softcap=softcap, interpret=interpret,
    )


def paged_decode_attention_int8(
    q: jax.Array,  # [B, H, dh] one token per sequence
    k_pages: jax.Array,  # int8 [P, page_size, KH, dh] quantised pages
    v_pages: jax.Array,
    k_scale: jax.Array,  # f32 [P, page_size] per-token-row scales
    v_scale: jax.Array,
    block_tables: jax.Array,  # i32[B, pages_bucket] page ids (0 = null page)
    pos: jax.Array,  # i32[B] per-row positions (inclusive)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Block-table gather + dequant decode attention over int8 pages.

    The quantised twin of ``paged_decode_attention`` (DESIGN.md §12): the
    scale pages ride the same index-map indirection as the K/V pages, so
    the gather stays an index-map trick and the kernel body only adds one
    multiply per load. ``kv_dtype`` is a semi-static dispatch coordinate:
    this specialisation and the fp32 one are two AOT branch targets.
    """
    return _paged_decode_call(
        q, k_pages, v_pages, block_tables, pos, (k_scale, v_scale),
        window=window, softcap=softcap, interpret=interpret,
    )


def paged_decode_attention_int8_reference(
    q: jax.Array,  # [B, H, dh]
    k_pages: jax.Array,  # int8 [P, page_size, KH, dh]
    v_pages: jax.Array,
    k_scale: jax.Array,  # f32 [P, page_size]
    v_scale: jax.Array,
    block_tables: jax.Array,  # i32[B, pages_bucket]
    pos: jax.Array,  # i32[B]
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Pure-jax oracle for ``paged_decode_attention_int8``: dequantise the
    pools, then reuse the fp32 oracle."""
    dk = k_pages.astype(jnp.float32) * k_scale[..., None, None]
    dv = v_pages.astype(jnp.float32) * v_scale[..., None, None]
    return paged_decode_attention_reference(
        q, dk.astype(q.dtype), dv.astype(q.dtype), block_tables, pos,
        window=window, softcap=softcap,
    )


def paged_decode_attention_reference(
    q: jax.Array,  # [B, H, dh]
    k_pages: jax.Array,  # [P, page_size, KH, dh]
    v_pages: jax.Array,
    block_tables: jax.Array,  # i32[B, pages_bucket]
    pos: jax.Array,  # i32[B]
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Pure-jax oracle for ``paged_decode_attention`` (gather + masked SDPA)."""
    b, h, dh = q.shape
    _, page_size, kh, _ = k_pages.shape
    npages = block_tables.shape[1]
    group = h // kh
    seq = npages * page_size
    bt = jnp.asarray(block_tables, jnp.int32)
    gk = k_pages[bt].reshape(b, seq, kh, dh)  # [B, PB, ps, KH, dh] flattened
    gv = v_pages[bt].reshape(b, seq, kh, dh)
    qg = q.reshape(b, kh, group, dh).astype(jnp.float32)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qg, gk.astype(jnp.float32)
    ) * (1.0 / np.sqrt(dh))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    ki = jnp.arange(seq)[None, :]
    ok = ki <= jnp.asarray(pos, jnp.int32)[:, None]
    if window is not None:
        ok &= ki > jnp.asarray(pos, jnp.int32)[:, None] - window
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, gv.astype(jnp.float32))
    return o.reshape(b, h, dh).astype(q.dtype)
