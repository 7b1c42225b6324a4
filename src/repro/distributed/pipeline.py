"""GPipe-style pipeline parallelism over a dedicated "stage" mesh axis.

Beyond-paper scaling feature (DESIGN.md §5): the layer stack is split into
`num_stages` contiguous groups; microbatches stream through stages with
`shard_map` + `collective_permute` boundary transfers. The schedule is the
classic GPipe fill/steady/drain: T = M + S - 1 ticks for M microbatches over
S stages, bubble fraction (S-1)/(M+S-1).

Semi-static tie-in: a pipeline-parallel step and a pure-FSDP step for the same
model are two branch targets behind one BranchChanger — switching execution
strategy is a cold-path direction change, exactly like the failover plan.

Scope: forward pipelining (inference / activation streaming). It reuses the
same per-stage block apply as the rest of the framework, so every arch config
works; training through the pipeline composes with jax.grad per stage in the
usual GPipe fashion but is not wired into the default trainer.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ArchConfig

def split_stages(cfg: ArchConfig, num_stages: int) -> int:
    """Layers per stage; requires an even split of period-groups."""
    m = cfg.num_layers // cfg.period
    assert m % num_stages == 0, (
        f"{cfg.name}: {m} period-groups not divisible by {num_stages} stages"
    )
    return m // num_stages


def pipeline_forward(
    stage_fn: Callable,  # (stage_params, x) -> x, applied on every stage
    params_stacked: Any,  # leaves [S, ...] — stage-major stacked params
    x_microbatches: jax.Array,  # [M, mb, ...]
    *,
    mesh: Mesh,
    stage_axis: str = "stage",
) -> jax.Array:
    """Run M microbatches through S pipeline stages (GPipe schedule).

    Implemented as shard_map over the stage axis: each device(-group) holds
    one stage's params; activations hop stage->stage+1 with ppermute.
    """
    num_stages = mesh.shape[stage_axis]
    m_total = x_microbatches.shape[0]

    def per_stage(stage_params, xs):
        # stage_params: this stage's slice [1, ...]; xs: all microbatches
        sp = jax.tree.map(lambda t: t[0], stage_params)
        stage_id = jax.lax.axis_index(stage_axis)
        ticks = m_total + num_stages - 1
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf = carry  # the activation currently entering this stage
            # stage 0 injects microbatch t (if in range), others use buf
            inject = jnp.where(
                t < m_total,
                xs[jnp.minimum(t, m_total - 1)],
                jnp.zeros(mb_shape, xs.dtype),
            )
            x_in = jnp.where(stage_id == 0, inject, buf)
            y = stage_fn(sp, x_in)
            # pass to the next stage (last stage's output wraps to 0, unused
            # there except as the final result collection below)
            y_next = jax.lax.ppermute(
                y,
                stage_axis,
                [(i, (i + 1) % num_stages) for i in range(num_stages)],
            )
            # collect: the LAST stage's output at tick t corresponds to
            # microbatch t - (num_stages - 1)
            out_idx = t - (num_stages - 1)
            emit = jnp.where(stage_id == num_stages - 1, y, jnp.zeros_like(y))
            return y_next, (out_idx, emit)

        buf0 = jax.lax.pcast(
            jnp.zeros(mb_shape, xs.dtype), (stage_axis,), to="varying"
        )
        _, (idxs, emits) = jax.lax.scan(
            tick, buf0, jnp.arange(ticks)
        )
        # scatter emitted outputs into [M, ...] (invalid ticks write to 0
        # then get overwritten by valid ones because idx increases)
        out = jnp.zeros_like(xs)
        valid = (idxs >= 0) & (idxs < m_total)
        safe = jnp.clip(idxs, 0, m_total - 1)
        out = out.at[safe].add(
            emits * valid.reshape((-1,) + (1,) * (emits.ndim - 1))
        )
        # only the last stage holds real outputs; broadcast them to all
        return jax.lax.psum(
            jnp.where(stage_id == num_stages - 1, out, jnp.zeros_like(out)),
            stage_axis,
        )

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
    )
    return fn(params_stacked, x_microbatches)


def reference_forward(
    stage_fn: Callable, params_stacked: Any, x_microbatches: jax.Array
) -> jax.Array:
    """Sequential oracle: every stage applied in order, no pipelining."""
    s = jax.tree.leaves(params_stacked)[0].shape[0]

    def run_one(x):
        for i in range(s):
            sp = jax.tree.map(lambda t: t[i], params_stacked)
            x = stage_fn(sp, x)
        return x

    return jax.vmap(run_one)(x_microbatches)


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
