"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (arXiv:2405.21060 §6).

Grid (B, H, num_chunks) with the chunk dim sequential; the recurrent state
[P, N] lives in VMEM scratch across chunk steps — the HLO formulation's
scan-carried state (which §Perf showed is traffic-bound in pure JAX) never
touches HBM here.

Semi-static specialisation: the chunk length L is baked per kernel (the
mamba2 arch-applicability note in DESIGN.md — chunk-size specialisation is
this family's analogue of attention-mode specialisation).

Layouts match repro.models.ssm: x [B,S,H,P], b/c [B,S,H,N], dt [B,S,H]
(post-softplus), A [H] (negative). Outputs: y [B,S,H,P], state [B,H,P,N].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_kernel(*, chunk: int, num_chunks: int):
    L = chunk

    def kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, s_ref, state_scr):
        ci = pl.program_id(2)

        @pl.when(ci == 0)
        def _init():
            state_scr[...] = jnp.zeros_like(state_scr)

        x = x_ref[0, :, 0, :].astype(jnp.float32)  # [L, P]
        bm = b_ref[0, :, 0, :].astype(jnp.float32)  # [L, N]
        cm = c_ref[0, :, 0, :].astype(jnp.float32)  # [L, N]
        dt = dt_ref[0, :, 0].astype(jnp.float32)  # [L]
        a = a_ref[0].astype(jnp.float32)  # scalar (this head's A)

        da = dt * a
        cum = jnp.cumsum(da)  # [L]
        total = cum[-1]
        seg = cum[:, None] - cum[None, :]  # [L, L']
        li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        lj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        decay = jnp.where(lj <= li, jnp.exp(seg), 0.0)
        cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # [L, L']
        att = cb * decay * dt[None, :]
        y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())))  # [L, P]

        state = state_scr[...]  # [P, N]
        # inter-chunk contribution: exp(cum[l]) * C[l] @ state^T
        y_in = jax.lax.dot_general(
            cm, state, (((1,), (1,)), ((), ()))
        ) * jnp.exp(cum)[:, None]
        y_ref[0, :, 0, :] = (y + y_in).astype(y_ref.dtype)

        # state update: exp(total)*state + x^T @ (B * exp(total-cum) * dt)
        w_in = (jnp.exp(total - cum) * dt)[:, None]  # [L, 1]
        state_scr[...] = state * jnp.exp(total) + jax.lax.dot_general(
            x, bm * w_in, (((0,), (0,)), ((), ()))
        )

        @pl.when(ci == num_chunks - 1)
        def _emit_state():
            s_ref[0, 0] = state_scr[...].astype(s_ref.dtype)

    return kernel


def ssd_chunk(
    x: jax.Array,  # [B, S, H, P]
    b: jax.Array,  # [B, S, H, N] (group-expanded)
    c: jax.Array,  # [B, S, H, N]
    dt: jax.Array,  # [B, S, H] post-softplus
    a: jax.Array,  # [H] negative decay rates
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = _make_kernel(chunk=chunk, num_chunks=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b_, h_, ci: (b_, ci, h_, 0)),
            pl.BlockSpec((1, chunk, 1, n), lambda b_, h_, ci: (b_, ci, h_, 0)),
            pl.BlockSpec((1, chunk, 1, n), lambda b_, h_, ci: (b_, ci, h_, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b_, h_, ci: (b_, ci, h_)),
            pl.BlockSpec((1,), lambda b_, h_, ci: (h_,)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b_, h_, ci: (b_, ci, h_, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, ci: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, b, c, dt, a)
    return y, state
