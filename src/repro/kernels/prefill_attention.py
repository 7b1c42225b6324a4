"""Pallas TPU paged *prefill* attention: a causal query chunk over pages.

The chunked-prefill counterpart of ``decode_attention.paged_decode_attention``
(DESIGN.md §10): a ``[C, dh]`` query chunk per sequence attends over its
block-table-gathered pages — which, by the time the kernel runs, already hold
the in-flight chunk's K/V (the jax-level caller scatter-writes the chunk
through the block table first, exactly as the decode path writes before
reading). The grid is (B, KH, PB); each step loads the chunk's q rows for one
kv head (``[C·G, dh]``) and one page, carrying online-softmax state in VMEM
scratch. Causality is per query row: chunk row i masks logical positions
``> start + i``, so rows attend to earlier chunk rows but never to later ones.

Semi-static structure, twice over:

* ``C`` (the chunk bucket, from the log-sized set {8, 16, 32, ...}) is a
  compile-time constant — one kernel per ``("pf", ..., chunk_bucket, ...)``
  dispatch key, never a per-step size branch;
* the page gather is the same **index-map indirection** as paged decode: the
  prefetched block table drives the BlockSpec, the kernel body never sees a
  page id.

Blocks whose pages lie entirely beyond the chunk's last position (or, in
window mode, entirely before its window) are skipped structurally via the
prefetched ``start`` scalar.

The **verify lane** of speculative decoding (DESIGN.md §11) reuses this
kernel verbatim: a verify window of K+1 tokens (the committed token plus K
draft candidates) is exactly a C = K+1 chunk whose per-row causal frontiers
score every candidate in one target pass — the ``("vf", slots, k_bucket)``
executables lower onto the same kernel with the k-bucket as the chunk axis.
``paged_verify_attention`` is the exported alias that documents (and pins,
via tests) this reuse.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _make_prefill_kernel(
    *,
    window: Optional[int],
    softcap: Optional[float],
    page_size: int,
    chunk: int,
    group: int,
    sm_scale: float,
    num_pages_per_req: int,
    quantised: bool = False,
):
    """One causal-chunk online-softmax body for both page dtypes
    (DESIGN.md §12). ``quantised`` is a *trace-time* flag: True adds two
    per-token-row scale operands (gathered through the same block-table
    index maps) and one in-register dequant multiply after each K/V load —
    fp32 and int8 stay two separately compiled branch targets, but the
    masking/softmax body is written exactly once."""
    rows = chunk * group  # q rows per (batch, kv-head) block: [C, G] packed

    def kernel(bt_ref, start_ref, q_ref, k_ref, v_ref, *rest):
        if quantised:
            ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
        else:
            o_ref, m_scr, l_scr, acc_scr = rest
        b = pl.program_id(0)
        pb = pl.program_id(2)
        start = start_ref[b]

        @pl.when(pb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # structural skips: pages past the chunk's last position, or (window
        # mode) pages entirely before the earliest query row's window.
        run = pb * page_size <= start + chunk - 1
        if window is not None:
            run = jnp.logical_and(
                run, pb * page_size + page_size - 1 > start - window
            )

        @pl.when(run)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32)  # [rows, dh]
            k = k_ref[0, :, 0].astype(jnp.float32)  # [ps, dh]
            v = v_ref[0, :, 0].astype(jnp.float32)
            if quantised:  # dequant: int8 rows x their per-row scales
                k = k * ks_ref[0][:, None]
                v = v * vs_ref[0][:, None]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ()))
            ) * sm_scale  # [rows, ps]
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            ki = pb * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1
            )
            # per-query-row causal frontier: row r is chunk token r // G
            qi = start + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 0
            ) // group
            s = jnp.where(ki <= qi, s, NEG_INF)
            if window is not None:
                s = jnp.where(ki > qi - window, s, NEG_INF)
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


def _paged_prefill_call(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    start: jax.Array,
    scales: tuple[jax.Array, jax.Array] | None,
    *,
    window: Optional[int],
    softcap: Optional[float],
    interpret: bool,
) -> jax.Array:
    """Shared grid/spec plumbing for the fp32 and int8 public entry points;
    ``scales`` (k_scale, v_scale) present selects the quantised kernel."""
    b, c, h, dh = q.shape
    _, page_size, kh, _ = k_pages.shape
    assert h % kh == 0
    _, npages = block_tables.shape
    group = h // kh
    rows = c * group
    sm_scale = 1.0 / np.sqrt(dh)
    # [B, C, KH, G, dh] -> [B, KH, C*G, dh]: rows of one kv head contiguous
    qg = q.reshape(b, c, kh, group, dh).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, kh, rows, dh)

    kernel = _make_prefill_kernel(
        window=window,
        softcap=softcap,
        page_size=page_size,
        chunk=c,
        group=group,
        sm_scale=sm_scale,
        num_pages_per_req=npages,
        quantised=scales is not None,
    )
    # page indirection: every per-page operand's index map chases the
    # prefetched block table (scale pages included)
    page_spec = pl.BlockSpec(
        (1, page_size, 1, dh),
        lambda b_, h_, pb, bt, start_: (bt[b_, pb], 0, h_, 0),
    )
    scale_spec = pl.BlockSpec(
        (1, page_size), lambda b_, h_, pb, bt, start_: (bt[b_, pb], 0)
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, rows, dh),
            lambda b_, h_, pb, bt, start_: (b_, h_, 0, 0),
        ),
        page_spec,
        page_spec,
    ]
    operands = [qg, k_pages, v_pages]
    if scales is not None:
        in_specs += [scale_spec, scale_spec]
        operands += [jnp.asarray(s, jnp.float32) for s in scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (block_tables, start)
        grid=(b, kh, npages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, rows, dh), lambda b_, h_, pb, bt, start_: (b_, h_, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rows, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(start, jnp.int32),
        *operands,
    )
    # [B, KH, C*G, dh] -> [B, C, H, dh]
    out = out.reshape(b, kh, c, group, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, c, h, dh)


def paged_prefill_attention(
    q: jax.Array,  # [B, C, H, dh] one chunk of C query tokens per sequence
    k_pages: jax.Array,  # [P, page_size, KH, dh] pooled pages (chunk written)
    v_pages: jax.Array,
    block_tables: jax.Array,  # i32[B, pages_bucket] page ids (0 = null page)
    start: jax.Array,  # i32[B] logical position of each row's first chunk token
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal flash over a query chunk, gathered through block tables.

    The chunk's own K/V must already live in the pages (the caller scatters
    before calling — see ``models.attention.paged_prefill_attention``); row i
    of the chunk attends to logical positions ``<= start + i``. Chunk length
    C and table width are compile-time constants (the semi-static chunk and
    capacity buckets). Returns [B, C, H, dh].
    """
    return _paged_prefill_call(
        q, k_pages, v_pages, block_tables, start, None,
        window=window, softcap=softcap, interpret=interpret,
    )


# Speculative decoding's verify pass is the same computation with C = K+1:
# per-row causal frontiers score the committed token + K draft candidates in
# one pass (DESIGN.md §11). Alias it so the lane's kernel dependency is an
# explicit, importable contract rather than an implementation coincidence.
paged_verify_attention = paged_prefill_attention


# --------------------------------------------------------------- int8 pages
def paged_prefill_attention_int8(
    q: jax.Array,  # [B, C, H, dh] one chunk of C query tokens per sequence
    k_pages: jax.Array,  # int8 [P, page_size, KH, dh] (chunk written)
    v_pages: jax.Array,
    k_scale: jax.Array,  # f32 [P, page_size] per-token-row scales
    v_scale: jax.Array,
    block_tables: jax.Array,  # i32[B, pages_bucket] page ids (0 = null page)
    start: jax.Array,  # i32[B] logical position of the first chunk token
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal chunk flash over quantised pages (DESIGN.md §12): the int8
    twin of ``paged_prefill_attention``, scale pages gathered through the
    same block-table index maps. The chunk's quantised K/V (and scales)
    must already live in the pages — the jax-level caller scatters via
    ``models.attention.quantise_kv_rows`` before calling."""
    return _paged_prefill_call(
        q, k_pages, v_pages, block_tables, start, (k_scale, v_scale),
        window=window, softcap=softcap, interpret=interpret,
    )


# The verify lane's int8 twin (DESIGN.md §11/§12): same kernel, C = K+1.
paged_verify_attention_int8 = paged_prefill_attention_int8


def paged_prefill_attention_int8_reference(
    q: jax.Array,  # [B, C, H, dh]
    k_pages: jax.Array,  # int8 [P, page_size, KH, dh]
    v_pages: jax.Array,
    k_scale: jax.Array,  # f32 [P, page_size]
    v_scale: jax.Array,
    block_tables: jax.Array,  # i32[B, pages_bucket]
    start: jax.Array,  # i32[B]
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Pure-jax oracle for ``paged_prefill_attention_int8``: dequantise the
    pools, then reuse the fp32 oracle."""
    dk = k_pages.astype(jnp.float32) * k_scale[..., None, None]
    dv = v_pages.astype(jnp.float32) * v_scale[..., None, None]
    return paged_prefill_attention_reference(
        q, dk.astype(q.dtype), dv.astype(q.dtype), block_tables, start,
        window=window, softcap=softcap,
    )


def paged_prefill_attention_reference(
    q: jax.Array,  # [B, C, H, dh]
    k_pages: jax.Array,  # [P, page_size, KH, dh]
    v_pages: jax.Array,
    block_tables: jax.Array,  # i32[B, pages_bucket]
    start: jax.Array,  # i32[B]
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Pure-jax oracle for ``paged_prefill_attention`` (gather + per-row
    causal masked SDPA)."""
    b, c, h, dh = q.shape
    _, page_size, kh, _ = k_pages.shape
    npages = block_tables.shape[1]
    group = h // kh
    seq = npages * page_size
    bt = jnp.asarray(block_tables, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    gk = k_pages[bt].reshape(b, seq, kh, dh)
    gv = v_pages[bt].reshape(b, seq, kh, dh)
    qg = q.reshape(b, c, kh, group, dh).astype(jnp.float32)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, gk.astype(jnp.float32)
    ) * (1.0 / np.sqrt(dh))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    ki = jnp.arange(seq)[None, None, :]  # [1,1,L]
    qi = start[:, None, None] + jnp.arange(c)[None, :, None]  # [B,C,1]
    ok = ki <= qi
    if window is not None:
        ok &= ki > qi - window
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, gv.astype(jnp.float32))
    return o.reshape(b, c, h, dh).astype(q.dtype)
