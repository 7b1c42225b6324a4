"""Step builders: train / prefill / decode, with sharding + donation plumbing.

These produce (fn, in_shardings, out_shardings, donate) bundles ready for
``jax.jit(...).lower(...).compile()`` — the AOT path every semi-static branch
target goes through (DESIGN.md §2).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import models, perf
from repro.configs import ArchConfig, ShapeSpec
from repro.core.telemetry import span
from repro.distributed import sharding as shd
from repro.models.model import input_specs
from repro.optim import adamw


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState


def state_shapes(cfg: ArchConfig, key=None) -> TrainState:
    """Abstract TrainState via eval_shape (no allocation)."""
    k = jax.random.PRNGKey(0) if key is None else key
    p_shape = jax.eval_shape(lambda: models.init_params(cfg, k))
    o_shape = jax.eval_shape(lambda: adamw.init(p_shape))
    return TrainState(params=p_shape, opt=o_shape)


def make_train_fn(
    cfg: ArchConfig,
    opt_cfg: adamw.AdamWConfig | None = None,
    *,
    impl: str = "naive",
    moe_policy: str = "drop",
    remat: bool | None = None,
    grad_compress: Callable | None = None,
) -> Callable:
    opt_cfg = opt_cfg or adamw.AdamWConfig()

    def train_step(state: TrainState, batch: dict):
        def lf(p):
            return models.loss_fn(
                cfg, p, batch, impl=impl, moe_policy=moe_policy
            )

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(
            state.params
        )
        if grad_compress is not None:
            grads = grad_compress(grads)
        new_p, new_opt, opt_metrics = adamw.update(
            opt_cfg, grads, state.opt, state.params
        )
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return TrainState(params=new_p, opt=new_opt), metrics

    return train_step


def train_shardings(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec):
    """(state_in, batch_in, state_out, metrics_out) NamedSharding trees."""
    st = state_shapes(cfg)
    p_spec = shd.param_pspec_tree(st.params, mesh)
    mu_spec = shd.opt_pspec_tree(cfg, p_spec, st.params, mesh)
    opt_spec = adamw.AdamWState(step=P(), mu=mu_spec, nu=mu_spec)
    state_spec = TrainState(params=p_spec, opt=opt_spec)
    batch = input_specs(cfg, "train", shape.global_batch, shape.seq_len)
    batch_spec = {k: shd.data_pspec(v.shape, mesh) for k, v in batch.items()}
    named = lambda t: shd.to_named(t, mesh)
    return st, batch, named(state_spec), named(batch_spec)


def lower_train(
    cfg: ArchConfig,
    mesh: Mesh,
    shape: ShapeSpec,
    *,
    impl: str = "naive",
    moe_policy: str = "drop",
    donate: bool = True,
    opts: perf.PerfOpts | None = None,
):
    fn = make_train_fn(cfg, impl=impl, moe_policy=moe_policy)
    st, batch, state_shard, batch_shard = train_shardings(cfg, mesh, shape)
    metrics_shard = None  # inferred (replicated scalars)
    jitted = jax.jit(
        fn,
        in_shardings=(state_shard, batch_shard),
        out_shardings=(state_shard, metrics_shard),
        donate_argnums=(0,) if donate else (),
    )
    with mesh, shd.use_shard_hints(mesh), perf.use_perf_opts(opts or perf.current()):
        return jitted.lower(st, batch)


def cache_shapes(cfg: ArchConfig, batch: int, max_len: int) -> list:
    return jax.eval_shape(lambda: models.init_cache(cfg, batch, max_len))


def make_decode_fn(cfg: ArchConfig, *, moe_policy: str = "drop") -> Callable:
    def serve_step(params, cache, inputs, pos):
        logits, new_cache = models.decode_step(
            cfg, params, cache, inputs, pos, moe_policy=moe_policy
        )
        return logits, new_cache

    return serve_step


def make_sampling_decode_fn(
    cfg: ArchConfig,
    *,
    mode: int,
    temperature: float = 1.0,
    moe_policy: str = "drop",
) -> Callable:
    """Decode step with the sampling mode *baked into the executable*.

    One compiled branch target per (bucket, mode) — the per-burst engine's
    branch targets (DESIGN.md §2). ``mode`` 0 = greedy, 1 = sample. Flipping
    mode means dispatching a different executable: cheap once compiled, but a
    cold compile on first sight and a slot rebind per flip.
    """

    def step(params, cache, inputs, pos, key):
        logits, cache = models.decode_step(
            cfg, params, cache, inputs, pos, moe_policy=moe_policy
        )
        if mode == 0:  # greedy
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            tok = jax.random.categorical(
                key, logits / temperature, axis=-1
            ).astype(jnp.int32)
        return tok, cache

    return step


def make_slot_decode_fn(cfg: ArchConfig, *, moe_policy: str = "drop") -> Callable:
    """Continuous-batching decode step: sampling params are *data*, not code.

    The unified hot-loop signature (DESIGN.md §4) — one executable per bucket
    size, shared by every request mix:

        step(params, cache, tok[S,1], pos[S], active[S], temps[S],
             greedy[S], keys[S,2])
          -> (next_tok[S], cache, new_pos[S], new_keys[S,2],
              tok_col[S,1], packed[S,4])

    The trailing pair is the step's *bundle* (DESIGN.md §13): ``tok_col``
    is the next step's chained input and ``packed`` the single host-bound
    d2h array ``[next_tok | new_pos | new_keys-as-int32]``, both staged
    inside the executable so the async pipeline pays no per-step host
    re-staging or packing dispatch. The synchronous loop ignores them and
    keeps its legacy pulls.

    Per-slot fields:
      * ``pos``    — each slot's own cache depth; frozen while inactive.
      * ``active`` — slots currently owned by a request; inactive slots
                     still compute (fixed shapes = no recompile) but their
                     outputs are ignored on the host and their positions
                     don't advance.
      * ``temps``/``greedy`` — packed sampling params. GREEDY vs SAMPLE is a
        ``jnp.where`` on data, so a mode flip never recompiles or rebinds.
      * ``keys``   — per-slot PRNG keys, split in-step so sampling streams
        are independent per request.
    """

    def slot_step(params, cache, tok, pos, active, temps, greedy, keys):
        logits, cache = models.decode_step(
            cfg, params, cache, tok, pos, moe_policy=moe_policy
        )
        nxt, new_keys = _sample_rows(logits, temps, greedy, keys)
        new_pos = pos + active.astype(jnp.int32)
        return (nxt, cache, new_pos, new_keys,
                *_step_bundle(nxt, new_pos, new_keys))

    return slot_step


def make_paged_slot_decode_fn(
    cfg: ArchConfig, *, moe_policy: str = "drop"
) -> Callable:
    """Paged continuous-batching decode step (DESIGN.md §9).

    Same contract as ``make_slot_decode_fn`` with one extra input — the
    packed block tables:

        step(params, cache, tok[S,1], pos[S], block_tables[S,PB], active[S],
             temps[S], greedy[S], keys[S,2])
          -> (next_tok[S], cache, new_pos[S], new_keys[S,2],
              tok_col[S,1], packed[S,4])

    ``cache`` is the pooled page cache (``models.init_paged_cache``), shared
    by every slot. ``PB`` (``pages_bucket``) is baked into the executable's
    shapes: it is the semi-static capacity key — one executable per
    ``("cbp", slots, pages_bucket, kv_dtype)``, and a request growing past the bucket
    is a cold-path rebind, never a hot-loop capacity check. Inactive slots
    carry all-null block tables, so their (structurally unavoidable) writes
    land in the reserved null page.
    """

    def paged_slot_step(
        params, cache, tok, pos, block_tables, active, temps, greedy, keys
    ):
        logits, cache = models.paged_decode_step(
            cfg, params, cache, tok, pos, block_tables, moe_policy=moe_policy
        )
        nxt, new_keys = _sample_rows(logits, temps, greedy, keys)
        new_pos = pos + active.astype(jnp.int32)
        return (nxt, cache, new_pos, new_keys,
                *_step_bundle(nxt, new_pos, new_keys))

    return paged_slot_step


# ----------------------------------------------------- packed d2h transfers
# The serving loop used to pull each step's outputs as separate
# ``np.asarray`` transfers (next tokens, split keys, verify rows). Every
# pull is a blocking device sync with its own fixed cost, so the step
# pipeline (DESIGN.md §13) packs all host-bound outputs of a step into one
# int32 device array and fetches it in a single transfer — on the async
# path that one transfer is also the *only* sync point, deferred to the
# token-emit boundary. uint32 key halves ride along bit-cast to int32
# (``np.ndarray.astype(np.uint32)`` on the host restores the exact bits).
#
# The decode lanes go one step further: ``_step_bundle`` runs *inside* the
# compiled step executable, so the packed array and the chained next-step
# input are part of the step's own outputs — a "future-returning step
# bundle" the host merely holds on to. ``pack_step_d2h``/``pack_verify_d2h``
# remain as host-dispatched packers for the lanes whose executables predate
# the bundle contract (prefill, verify).
#
# Donation audit: every step executable donates only its cache argument
# (``donate_argnums=(1,)``), so the nxt/pos/keys outputs packed here are
# fresh buffers — packing reads them *after* the mirror adopted them as
# next-step inputs, and jax.jit without donation never aliases them away.
def _step_bundle(nxt, new_pos, new_keys):
    """Bundle tail of a decode step, traced into the step executable:
    ``tok_col [S,1]`` (the chained next-step input) and ``packed [S,4]``
    (``[next_tok | new_pos | new_keys-as-int32]``, one d2h transfer)."""
    tok_col = nxt[:, None]
    k32 = jax.lax.bitcast_convert_type(new_keys, jnp.int32)
    packed = jnp.concatenate([tok_col, new_pos[:, None], k32], axis=1)
    return tok_col, packed


@jax.jit
def pack_step_d2h(nxt, keys):
    """[S] int32 next tokens + [S,2] uint32 keys -> [S,3] int32."""
    k32 = jax.lax.bitcast_convert_type(keys, jnp.int32)
    return jnp.concatenate([nxt[:, None], k32], axis=1)


@jax.jit
def pack_verify_d2h(rows, nxt0, keys):
    """[S,K+1] rows + [S] next0 + [S,2] uint32 keys -> [S,K+4] int32."""
    k32 = jax.lax.bitcast_convert_type(keys, jnp.int32)
    return jnp.concatenate([rows, nxt0[:, None], k32], axis=1)


def pull_host(dev, recorder=None) -> tuple[np.ndarray, int]:
    """The d2h pull boundary: materialise a packed device array on the host.

    This is *the* sync point of the step pipeline (DESIGN.md §13) — on the
    async path the only place the host ever blocks on the device — so it is
    also where device wait is measured. Returns ``(host_array,
    elapsed_ns)``. The pull is the ``serve.dispatch.pull`` phase span
    (``core.telemetry.span``, with the transfer's bytes): on the profiler's
    trace while it collects, on ``recorder`` while that is enabled."""
    with span("serve.dispatch.pull", recorder) as sp:
        t0 = time.perf_counter_ns()
        out = np.asarray(dev)
        dt = time.perf_counter_ns() - t0
        sp.note(bytes=int(out.nbytes))
    return out, dt


def _sample_rows(logits, temps, greedy, keys):
    """Shared sampling-as-data tail: greedy/temperature are per-row *data*
    (DESIGN.md §4), so mode flips never touch the cold path. Returns
    (next_tok [B], new_keys [B,2])."""
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temps, 1e-4)[:, None].astype(logits.dtype)
    sample_keys, new_keys = jnp.split(
        jax.vmap(lambda k: jax.random.split(k, 2))(keys), 2, axis=1
    )
    s = jax.vmap(jax.random.categorical)(
        sample_keys[:, 0], logits / t
    ).astype(jnp.int32)
    return jnp.where(greedy, g, s), new_keys[:, 0]


def make_draft_fn(
    draft_cfg: ArchConfig, *, k: int, moe_policy: str = "drop"
) -> Callable:
    """Draft lane (DESIGN.md §11): K candidate tokens per slot in *one*
    executable — the ``("dr", slots, k_bucket)`` semi-static dispatch key.

        step(draft_params, draft_cache, tok[S,1], pos[S], active[S],
             temps[S], greedy[S], keys[S,2])
          -> (drafts[S,K], draft_cache, new_pos[S], new_keys[S,2])

    ``draft_cfg``/``draft_params`` are the truncated-layer view of the
    target (``models.draft_view``); ``draft_cache`` is the draft's own
    dense per-slot KV. The K decode steps run as a ``lax.scan`` *inside*
    the executable, so draft depth is a compile-time constant — varying K
    picks a different k-bucket executable on the cold path and never
    branches per step. Each scan step feeds the previous candidate back,
    writing the draft's KV at the advancing position; the scheduler later
    rewinds ``pos`` to the verified frontier as pure data, and the next
    round's writes overwrite whatever the rejected tail left behind.

    Sampling params ride through ``_sample_rows`` exactly like every other
    lane (the scheduler forces ``greedy`` on so candidate streams are
    deterministic; the shared tail keeps the contract uniform and leaves
    sampled drafts open for rejection-sampling later).
    """

    def draft_step(params, cache, tok, pos, active, temps, greedy, keys):
        def body(carry, _):
            tok, cache, pos, keys = carry
            logits, cache = models.decode_step(
                draft_cfg, params, cache, tok, pos, moe_policy=moe_policy
            )
            nxt, keys = _sample_rows(logits, temps, greedy, keys)
            new_pos = pos + active.astype(jnp.int32)
            return (nxt[:, None], cache, new_pos, keys), nxt

        (_, cache, pos, new_keys), drafts = jax.lax.scan(
            body, (tok, cache, pos, keys), None, length=k
        )
        return jnp.moveaxis(drafts, 0, 1), cache, pos, new_keys

    return draft_step


def make_paged_verify_fn(
    cfg: ArchConfig, *, moe_policy: str = "drop"
) -> Callable:
    """Verify lane through the paged KV cache (DESIGN.md §11) — the
    ``("vf", slots, k_bucket)`` semi-static dispatch key.

        step(params, cache, tok[S,K+1], start[S], block_tables[S,PB],
             length[S], temps[S], greedy[S], keys[S,2])
          -> (rows[S,K+1], next0[S], cache, new_keys[S,2])

    ``tok`` packs each slot's current token followed by its K draft
    candidates; the chunked-prefill scatter path scores all K+1 positions
    in one target pass (columns >= ``length`` are bucket padding into the
    null page). ``rows[s, i]`` is the greedy continuation after feeding
    rows 0..i — the acceptance test and the correction token are host-side
    comparisons over this array (accept/rollback is *data*, never a code
    branch). ``next0`` is the mode-respecting sample from row 0 via the
    shared ``_sample_rows`` tail, so a verify with length 1 *is* a decode
    step — sampling slots and draft-ineligible slots ride the same
    executable with k as padding.
    """

    def verify_step(
        params, cache, tok, start, block_tables, length, temps, greedy, keys
    ):
        logits, cache = models.paged_verify_step(
            cfg, params, cache, tok, start, block_tables, length,
            moe_policy=moe_policy,
        )
        rows = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt0, new_keys = _sample_rows(logits[:, 0], temps, greedy, keys)
        return rows, nxt0, cache, new_keys

    return verify_step


def make_slot_verify_fn(
    cfg: ArchConfig, *, moe_policy: str = "drop"
) -> Callable:
    """Verify lane over the dense per-slot cache (DESIGN.md §11) — the
    ``("vfd", slots, k_bucket)`` dispatch key.

        step(params, cache, tok[S,K+1], start[S], length[S], temps[S],
             greedy[S], keys[S,2])
          -> (rows[S,K+1], next0[S], cache, new_keys[S,2])

    Behaviourally aligned with ``make_paged_verify_fn`` — a dense slot's
    cache rows are a trivial identity block table, so both engines share
    the accept/rollback contract (and ``_sample_rows``).
    """

    def verify_step(params, cache, tok, start, length, temps, greedy, keys):
        logits, cache = models.chunked_verify_step(
            cfg, params, cache, tok, start, length, moe_policy=moe_policy
        )
        rows = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt0, new_keys = _sample_rows(logits[:, 0], temps, greedy, keys)
        return rows, nxt0, cache, new_keys

    return verify_step


def make_paged_prefill_fn(
    cfg: ArchConfig, *, moe_policy: str = "drop", by_rows: bool = False
) -> Callable:
    """Chunked-prefill step through the paged KV cache (DESIGN.md §10).

        step(params, cache, tok[B,CB], start[B], block_tables[B,PB],
             length[B], temps[B], greedy[B], keys[B,2])
          -> (next_tok[B], cache, new_keys[B,2])

    ``CB`` (the chunk bucket, from the log-sized set {8, 16, 32, ...}) is
    baked into the executable's shapes — the semi-static chunk key
    ``("pf", slots, chunk_bucket, kv_dtype, mesh)``. Ingesting prompts is
    then a handful of direct executable calls instead of one decode step
    per token; the returned
    ``next_tok`` (sampled from the last real chunk row) primes generation
    when the chunk reaches the prompt end. Cache contents and priming
    *logits* are bit-for-bit what token-by-token forcing through
    ``make_paged_slot_decode_fn`` would have produced — so greedy streams
    are identical across ingestion modes; sampling streams draw from the
    same distribution but a different PRNG path (keys split once per chunk,
    not once per prompt token). Columns >= ``length`` are bucket padding:
    their K/V writes land in the reserved null page and their logits are
    never read.

    ``by_rows`` makes the row count a semi-static coordinate too — the
    ``("pfr", slots, rows, kv_dtype, mesh)`` key. The chunks ride the first
    of R rows and ``rows[R]`` names each row's slot; everything per slot
    stays [S]:

        step(params, cache, tok[R,CB], start[S], block_tables[R,PB],
             length[R], temps[S], greedy[S], keys[S,2], rows[R])
          -> (next_tok[S], cache, new_keys[S,2])

    The per-slot inputs are gathered by ``rows`` and the outputs scattered
    back inside this one executable, so the contract after the call is the
    ``[S]`` one above. Pad rows carry the out-of-range index S: they read
    fill values, their length is 0, and the scatter drops them. Slots no
    row names keep their keys bitwise and read 0 as their next token.
    """

    def chunk_step(
        params, cache, tok, start, block_tables, length, temps, greedy, keys
    ):
        logits, cache = models.paged_prefill_step(
            cfg, params, cache, tok, start, block_tables, length,
            moe_policy=moe_policy,
        )
        nxt, new_keys = _sample_rows(logits, temps, greedy, keys)
        # Idle rows (length 0, bucket padding) keep their keys unsplit:
        # the async pipeline adopts the whole returned key array when a
        # prefill chunk chains (DESIGN.md §17), and the sync loop only
        # copies planned rows — masking here makes both reads identical.
        new_keys = jnp.where(length[:, None] > 0, new_keys, keys)
        return nxt, cache, new_keys

    if not by_rows:

        def paged_prefill_step(
            params, cache, tok, start, block_tables, length, temps, greedy,
            keys,
        ):
            return chunk_step(
                params, cache, tok, start, block_tables, length, temps,
                greedy, keys,
            )

        return paged_prefill_step

    # The jitted function keeps the name ``paged_prefill_step`` in both
    # forms: device traces find the prefill calls by it.
    def paged_prefill_step(
        params, cache, tok, start, block_tables, length, temps, greedy, keys,
        rows,
    ):
        def gather(x, fill):
            return x.at[rows].get(mode="fill", fill_value=fill)

        nxt, cache, row_keys = chunk_step(
            params, cache, tok, gather(start, 0), block_tables, length,
            gather(temps, 1.0), gather(greedy, True), gather(keys, 0),
        )
        out = jnp.zeros(start.shape, nxt.dtype).at[rows].set(nxt, mode="drop")
        return out, cache, keys.at[rows].set(row_keys, mode="drop")

    return paged_prefill_step


def make_slot_prefill_fn(
    cfg: ArchConfig, *, moe_policy: str = "drop"
) -> Callable:
    """Chunked-prefill step into the dense per-slot cache (DESIGN.md §10).

        step(params, cache, tok[S,CB], start[S], length[S], temps[S],
             greedy[S], keys[S,2])
          -> (next_tok[S], cache, new_keys[S,2])

    The dense engine's prompt path: every slot carries its own chunk window
    (``length`` 0 = idle row, writes nothing), so the one executable per
    ``("pfd", slots, chunk_bucket)`` serves any mix of prefilling and idle
    slots. Behaviourally aligned with ``make_paged_prefill_fn`` — a dense
    slot's cache rows are a trivial identity block table.
    """

    def slot_prefill_step(params, cache, tok, start, length, temps, greedy, keys):
        logits, cache = models.chunked_decode_step(
            cfg, params, cache, tok, start, length, moe_policy=moe_policy
        )
        nxt, new_keys = _sample_rows(logits, temps, greedy, keys)
        # Same idle-row key mask as the paged prefill (DESIGN.md §17).
        new_keys = jnp.where(length[:, None] > 0, new_keys, keys)
        return nxt, cache, new_keys

    return slot_prefill_step


# -------------------------------------------- KV-page migration transport
# Disaggregated prefill/decode (DESIGN.md §17) moves a request's KV pages
# between two pooled caches when its slot flips PREFILL -> DECODE. The
# transport unit is a *page-index bucket*: gather up to B pages out of the
# source cache tree ([m, P, page_size, ...] leaves, page axis 1), ship the
# block tree across with one batched ``device_put``, scatter it into the
# destination cache under donation. ``idx`` is padded with the pools'
# *null* page ids, so a short migration gathers (and overwrites) only
# garbage rows — shapes stay fixed and the pair compiles once per
# (kv_dtype, mesh) cell. ``jax.tree.map`` covers the int8 ``k_scale``/
# ``v_scale`` leaves automatically because they share the page axis.
def make_page_gather_fn() -> Callable:
    """``gather(cache, idx[B]) -> block`` — slice B pages out of every
    leaf of the paged cache tree (the migration export half)."""

    def gather(cache, idx):
        return jax.tree.map(lambda x: x[:, idx], cache)

    return gather


def make_page_scatter_fn() -> Callable:
    """``scatter(cache, block, idx[B]) -> cache`` — write a migrated block
    tree into the destination cache at ``idx`` (the import half; the cache
    argument is donated by the AOT wrapper)."""

    def scatter(cache, block, idx):
        return jax.tree.map(
            lambda x, b: x.at[:, idx].set(b), cache, block
        )

    return scatter


def lower_decode(
    cfg: ArchConfig,
    mesh: Mesh,
    shape: ShapeSpec,
    *,
    moe_policy: str = "drop",
    donate: bool = True,
    impl: str = "naive",  # accepted for API uniformity; decode has one impl
    opts: perf.PerfOpts | None = None,
):
    """decode shapes: one new token against a KV cache of seq_len."""
    fn = make_decode_fn(cfg, moe_policy=moe_policy)
    p_shape = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))
    )
    c_shape = cache_shapes(cfg, shape.global_batch, shape.seq_len)
    p_spec = shd.param_pspec_tree(p_shape, mesh)
    c_spec = shd.cache_pspec_tree(cfg, c_shape, mesh)
    ins = input_specs(cfg, "decode", shape.global_batch, shape.seq_len)
    in_spec = shd.data_pspec(ins["inputs"].shape, mesh)
    named = lambda t: shd.to_named(t, mesh)
    jitted = jax.jit(
        fn,
        in_shardings=(
            named(p_spec),
            named(c_spec),
            named(in_spec),
            shd.replicated(mesh),
        ),
        out_shardings=(None, named(c_spec)),
        donate_argnums=(1,) if donate else (),
    )
    with mesh, shd.use_shard_hints(mesh), perf.use_perf_opts(opts or perf.current()):
        return jitted.lower(p_shape, c_shape, ins["inputs"], ins["pos"])


def make_prefill_fn(
    cfg: ArchConfig, *, impl: str = "naive", moe_policy: str = "drop"
) -> Callable:
    def prefill_step(params, inputs):
        return models.prefill(cfg, params, inputs, impl=impl, moe_policy=moe_policy)

    return prefill_step


def lower_prefill(
    cfg: ArchConfig,
    mesh: Mesh,
    shape: ShapeSpec,
    *,
    impl: str = "naive",
    moe_policy: str = "drop",
    opts: perf.PerfOpts | None = None,
):
    fn = make_prefill_fn(cfg, impl=impl, moe_policy=moe_policy)
    p_shape = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0))
    )
    p_spec = shd.param_pspec_tree(p_shape, mesh)
    ins = input_specs(cfg, "prefill", shape.global_batch, shape.seq_len)
    in_spec = shd.data_pspec(ins["inputs"].shape, mesh)
    # the emitted cache shards like a decode cache
    c_shape = jax.eval_shape(
        lambda p, i: fn(p, i)[1], p_shape, ins["inputs"]
    )
    c_spec = shd.cache_pspec_tree(cfg, c_shape, mesh)
    named = lambda t: shd.to_named(t, mesh)
    jitted = jax.jit(
        fn,
        in_shardings=(named(p_spec), named(in_spec)),
        out_shardings=(None, named(c_spec)),
    )
    with mesh, shd.use_shard_hints(mesh), perf.use_perf_opts(opts or perf.current()):
        return jitted.lower(p_shape, ins["inputs"])


def lower_for(cfg: ArchConfig, mesh: Mesh, shape: ShapeSpec, **kw):
    if shape.kind == "train":
        return lower_train(cfg, mesh, shape, **kw)
    if shape.kind == "prefill":
        return lower_prefill(cfg, mesh, shape, **kw)
    if shape.kind == "decode":
        return lower_decode(cfg, mesh, shape, **kw)
    raise ValueError(shape.kind)


def place_train_state(cfg: ArchConfig, state: TrainState, mesh: Mesh) -> TrainState:
    """Elastic re-mesh: place a (host or otherwise-sharded) TrainState onto a
    target mesh using the rule-derived shardings — the reshard step of
    checkpoint-based elastic scaling (DESIGN.md §6). Works across mesh shapes
    because checkpoints are stored unsharded per host."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )
    p_spec = shd.param_pspec_tree(shapes.params, mesh)
    mu_spec = shd.opt_pspec_tree(cfg, p_spec, shapes.params, mesh)
    opt_spec = adamw.AdamWState(step=P(), mu=mu_spec, nu=mu_spec)
    shardings = shd.to_named(
        TrainState(params=p_spec, opt=opt_spec), mesh
    )
    return jax.device_put(state, shardings)
