"""Pallas TPU flash attention with *semi-static mode specialisation*.

The paper's construct transplanted to the kernel level (DESIGN.md §2): the
attention mode — causal masking, sliding window, logit softcap, GQA group — is
baked into the kernel as Python constants, so each mode compiles to a distinct
specialised kernel with *no runtime mode branches per tile*:

  * causal       -> whole k-blocks above the diagonal are skipped structurally
                    (a `pl.when` whose predicate is grid-index arithmetic)
  * window       -> k-blocks outside the sliding window are skipped the same way
  * softcap=None -> the tanh never appears in the compiled kernel

The conditional baseline (`ops.flash_attention_branchy`) is the same algorithm
taking runtime mode flags: every tile computes the mask and the softcap and
`select`s — the kernel-level analogue of `lax.cond`-style branching the paper
benchmarks against.

Layouts: q [B, H, Sq, dh]; k,v [B, KH, Sk, dh]; out [B, H, Sq, dh].
Grid: (B, H, Sq/bq, Sk/bk), innermost dim "arbitrary" (sequential) with VMEM
scratch carrying the online-softmax state (m, l, acc).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _make_kernel(
    *,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    block_q: int,
    block_k: int,
    num_q_heads: int,
    num_kv_heads: int,
    sm_scale: float,
    num_k_blocks: int,
):
    def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        qb = pl.program_id(2)
        kb = pl.program_id(3)

        @pl.when(kb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # ---- semi-static structural block skip (compile-time specialised) --
        run = None
        if causal:
            # lowest q row of this block vs lowest k col: skip fully-masked
            run = kb * block_k <= qb * block_q + block_q - 1
        if window is not None:
            in_win = kb * block_k + block_k - 1 > qb * block_q - window
            run = in_win if run is None else jnp.logical_and(run, in_win)

        def compute():
            q = q_ref[0, 0].astype(jnp.float32)  # [bq, dh]
            k = k_ref[0, 0].astype(jnp.float32)  # [bk, dh]
            v = v_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ()))
            ) * sm_scale  # [bq, bk]
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            ki = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            if causal:
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

        if run is None:
            compute()
        else:
            pl.when(run)(compute)

        @pl.when(kb == num_k_blocks - 1)
        def _finalize():
            l = jnp.maximum(l_scr[...], 1e-37)
            o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)

    return kernel


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Specialised flash attention. q: [B,H,Sq,dh]; k,v: [B,KH,Sk,dh]."""
    b, h, sq, dh = q.shape
    _, kh, sk, _ = k.shape
    assert h % kh == 0, (h, kh)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k
    group = h // kh
    sm_scale = 1.0 / np.sqrt(dh)

    kernel = _make_kernel(
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        num_q_heads=h,
        num_kv_heads=kh,
        sm_scale=sm_scale,
        num_k_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, dh), lambda b_, h_, qb, kb: (b_, h_, qb, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, dh),
                lambda b_, h_, qb, kb, g=group: (b_, h_ // g, kb, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, dh),
                lambda b_, h_, qb, kb, g=group: (b_, h_ // g, kb, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, dh), lambda b_, h_, qb, kb: (b_, h_, qb, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


def _make_branchy_kernel(
    *,
    block_q: int,
    block_k: int,
    sm_scale: float,
    num_k_blocks: int,
):
    """Runtime-flag kernel: the conditional baseline. Every tile evaluates
    every mode's work and selects — no structural skips possible because the
    mode is data, not code."""

    def kernel(
        flags_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr
    ):
        qb = pl.program_id(2)
        kb = pl.program_id(3)
        causal_f = flags_ref[0]  # 0/1
        window_f = flags_ref[1]  # 0 => off, else window size
        softcap_f = flags_ref[2]  # 0 => off, else cap (as int, scaled by 1)

        @pl.when(kb == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale
        cap = jnp.maximum(softcap_f.astype(jnp.float32), 1.0)
        s_capped = jnp.tanh(s / cap) * cap
        s = jnp.where(softcap_f > 0, s_capped, s)  # both sides computed
        qi = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        ki = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(jnp.logical_or(causal_f == 0, ki <= qi), s, NEG_INF)
        s = jnp.where(
            jnp.logical_or(window_f == 0, ki > qi - window_f), s, NEG_INF
        )
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


def flash_attention_branchy(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    flags: jax.Array,  # i32[3]: (causal, window|0, softcap|0)
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, dh = q.shape
    _, kh, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq, nk = sq // block_q, sk // block_k
    group = h // kh
    sm_scale = 1.0 / np.sqrt(dh)
    kernel = _make_branchy_kernel(
        block_q=block_q, block_k=block_k, sm_scale=sm_scale, num_k_blocks=nk
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, dh),
                lambda b_, h_, qb, kb, flags: (b_, h_, qb, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, dh),
                lambda b_, h_, qb, kb, flags, g=group: (b_, h_ // g, kb, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, dh),
                lambda b_, h_, qb, kb, flags, g=group: (b_, h_ // g, kb, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, dh),
            lambda b_, h_, qb, kb, flags: (b_, h_, qb, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(flags, q, k, v)
