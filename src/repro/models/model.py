"""Top-level LM: init / train forward / prefill / decode, scanned over depth.

Params layout::

    {"embed": {...}, "head": {...}, "final_norm": {...},
     "blocks": [slot_0_params, ..., slot_{p-1}_params]}   # each stacked [m, ...]

Caches mirror "blocks" (stacked per slot). All functions are pure; the runtime
layer (repro.runtime) wraps them in jit/pjit with shardings.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro import perf
from repro.configs import ArchConfig
from repro.distributed.sharding import hint

from .blocks import (
    block_apply,
    block_cache_init,
    block_chunk_decode,
    block_decode,
    block_init,
    block_paged_cache_init,
    block_paged_decode,
    block_paged_prefill,
    block_prefill,
)
from .layers import dtype_of, embed_apply, embed_init, head_apply, head_init, norm_init

MOE_AUX_WEIGHT = 0.01


def init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    p = cfg.period
    m = cfg.num_layers // p
    keys = jax.random.split(key, 3 + p)
    params: dict[str, Any] = {
        "embed": embed_init(cfg, keys[0]),
        "head": head_init(cfg, keys[1]),
        "final_norm": norm_init(cfg),
    }
    blocks = []
    for slot in range(p):
        slot_keys = jax.random.split(keys[3 + slot], m)
        blocks.append(jax.vmap(lambda k, s=slot: block_init(cfg, k, s))(slot_keys))
    params["blocks"] = blocks
    return params


def _positions(batch: int, seq: int) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))


def _stack_body(cfg: ArchConfig, *, impl: str, moe_policy: str, remat: bool):
    """Scan body applying one period of blocks."""

    def body(carry, slot_params):
        x, aux, positions = carry
        for slot in range(cfg.period):
            x, a = block_apply(
                cfg, slot, slot_params[slot], x, positions,
                impl=impl, moe_policy=moe_policy,
            )
            aux = aux + a
        return (x, aux, positions), None

    if remat:
        if perf.current().remat_policy == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots
            )
        else:
            body = jax.checkpoint(body)
    return body


def forward(
    cfg: ArchConfig,
    params: dict,
    inputs: jax.Array,
    *,
    impl: str = "naive",
    moe_policy: str = "drop",
    remat: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """inputs: tokens [B,S] or stub-frontend embeddings [B,S,D].

    Returns (logits [B,S,V] float32, moe_aux scalar)."""
    if remat is None:
        remat = cfg.remat == "block"
    x = hint(embed_apply(cfg, params["embed"], inputs), "batch", None, None)
    b, s = x.shape[:2]
    positions = _positions(b, s)
    body = _stack_body(cfg, impl=impl, moe_policy=moe_policy, remat=remat)
    aux0 = jnp.zeros((), jnp.float32)
    (x, aux, _), _ = jax.lax.scan(
        body, (x, aux0, positions), tuple(params["blocks"])
    )
    from .layers import norm_apply

    x = norm_apply(cfg, params["final_norm"], x)
    logits = hint(
        head_apply(cfg, params["head"], params["embed"], x),
        "batch", None, "model",
    )
    return logits, aux


def loss_fn(
    cfg: ArchConfig,
    params: dict,
    batch: dict,
    *,
    impl: str = "naive",
    moe_policy: str = "drop",
) -> tuple[jax.Array, dict]:
    """batch: {"inputs": tokens|embeds, "labels": [B,S] int32 (-1 = pad)}."""
    logits, aux = forward(cfg, params, batch["inputs"],
                          impl=impl, moe_policy=moe_policy)
    labels = batch["labels"]
    valid = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = (logz - ll) * valid
    ntok = jnp.maximum(jnp.sum(valid), 1.0)
    loss = jnp.sum(ce) / ntok
    total = loss + MOE_AUX_WEIGHT * aux
    return total, {"ce": loss, "moe_aux": aux, "ntok": ntok}


# ------------------------------------------------------------------- serving
def init_cache(
    cfg: ArchConfig, batch: int, max_len: int, kv_dtype: str = "fp32"
) -> list:
    p = cfg.period
    m = cfg.num_layers // p
    caches = []
    for slot in range(p):
        one = block_cache_init(cfg, slot, batch, max_len, kv_dtype)
        caches.append(jax.tree.map(lambda t: jnp.stack([t] * m), one))
    return caches


def prefill(
    cfg: ArchConfig,
    params: dict,
    inputs: jax.Array,
    *,
    impl: str = "naive",
    moe_policy: str = "drop",
) -> tuple[jax.Array, list]:
    """Run the full prompt; returns (last-token logits [B,V], cache)."""
    x = embed_apply(cfg, params["embed"], inputs)
    b, s = x.shape[:2]
    positions = _positions(b, s)

    def body(carry, slot_params):
        x = carry
        caches = []
        for slot in range(cfg.period):
            x, c = block_prefill(
                cfg, slot, slot_params[slot], x, positions,
                impl=impl, moe_policy=moe_policy,
            )
            caches.append(c)
        return x, tuple(caches)

    x, caches = jax.lax.scan(body, x, tuple(params["blocks"]))
    from .layers import norm_apply

    x = norm_apply(cfg, params["final_norm"], x)
    logits = head_apply(cfg, params["head"], params["embed"], x[:, -1])
    return logits, list(caches)


def decode_step(
    cfg: ArchConfig,
    params: dict,
    cache: list,
    inputs: jax.Array,
    pos: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, list]:
    """One token for the whole stack.

    inputs: [B,1] tokens or [B,1,D] embeddings; pos: scalar int32 (shared
    write index into the KV cache) or [B] int32 (per-row positions for
    continuous batching — see ``attention.decode_attention`` and DESIGN.md
    §4). Returns (logits [B,V], new cache).
    """
    x = embed_apply(cfg, params["embed"], inputs)

    def body(x, slots):
        slot_params, slot_caches = slots
        new_caches = []
        for slot in range(cfg.period):
            x, c = block_decode(
                cfg, slot, slot_params[slot], x, slot_caches[slot], pos,
                moe_policy=moe_policy,
            )
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_cache = jax.lax.scan(body, x, (tuple(params["blocks"]), tuple(cache)))
    from .layers import norm_apply

    x = norm_apply(cfg, params["final_norm"], x)
    logits = head_apply(cfg, params["head"], params["embed"], x[:, -1])
    return logits, list(new_cache)


def init_paged_cache(
    cfg: ArchConfig, num_pages: int, page_size: int, kv_dtype: str = "fp32"
) -> list:
    """Pooled paged KV cache, stacked per period slot (DESIGN.md §9).

    ``num_pages`` includes the reserved null page 0. No batch axis: the same
    physical pages back every request via block tables, which is what lets
    shared prefixes dedupe and concurrency overcommit the dense ``B×max_len``
    bound. Attention-only stacks (SSM state is per-slot, not pageable).

    ``kv_dtype`` (DESIGN.md §12): ``"fp32"`` model-dtype pages, ``"int8"``
    quantised pages plus per-page scale leaves (``[m, P, page_size]``) that
    ride the same pytree — ``copy_cache_pages`` COWs them with the pages
    automatically because the page axis is shared.
    """
    p = cfg.period
    m = cfg.num_layers // p
    caches = []
    for slot in range(p):
        one = block_paged_cache_init(
            cfg, slot, num_pages, page_size, kv_dtype=kv_dtype
        )
        caches.append(jax.tree.map(lambda t: jnp.stack([t] * m), one))
    return caches


def _paged_scan(
    cfg: ArchConfig, params: dict, cache: list, x: jax.Array, block
) -> tuple[jax.Array, list]:
    """Run the stack over the paged pool with the pool in the scan's carry.

    The stacked per-slot pools ride the carry beside ``x`` and each layer
    writes its rows in place at ``(layer, page, offset)``; nothing comes out
    as ``ys``. A pool scanned as ``xs`` with the new pool as ``ys`` would
    slice every layer's whole pool out, update it and write it back, then
    copy the fresh stack into the donated buffer: whole-pool traffic on
    every call (DESIGN.md §9). ``block(slot, p, x, cache, layer)`` is one
    block step returning ``(x, cache)``.
    """
    m = cfg.num_layers // cfg.period

    def body(carry, slots):
        x, caches = carry
        slot_params, layer = slots
        caches = list(caches)
        for slot in range(cfg.period):
            x, caches[slot] = block(
                slot, slot_params[slot], x, caches[slot], layer
            )
        return (x, tuple(caches)), None

    (x, new_cache), _ = jax.lax.scan(
        body, (x, tuple(cache)),
        (tuple(params["blocks"]), jnp.arange(m, dtype=jnp.int32)),
    )
    return x, list(new_cache)


def paged_decode_step(
    cfg: ArchConfig,
    params: dict,
    cache: list,
    inputs: jax.Array,
    pos: jax.Array,
    block_tables: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, list]:
    """One token for the whole stack through the paged KV cache.

    inputs: [B,1] tokens or [B,1,D] embeddings; pos: [B] int32 per-row
    positions; block_tables: i32[B, pages_bucket] page ids (DESIGN.md §9).
    Returns (logits [B,V], new cache).
    """
    x = embed_apply(cfg, params["embed"], inputs)

    def block(slot, p, x, c, layer):
        return block_paged_decode(
            cfg, slot, p, x, c, layer, pos, block_tables,
            moe_policy=moe_policy,
        )

    x, new_cache = _paged_scan(cfg, params, cache, x, block)
    from .layers import norm_apply

    x = norm_apply(cfg, params["final_norm"], x)
    logits = head_apply(cfg, params["head"], params["embed"], x[:, -1])
    return logits, new_cache


def _last_real_row(x: jax.Array, length: jax.Array) -> jax.Array:
    """x: [B,C,D]; pick row ``length - 1`` per batch element -> [B,D].

    The chunked-prefill head input: only the last *real* chunk token's
    hidden state primes generation (bucket-padding rows carry garbage)."""
    last = jnp.clip(jnp.asarray(length, jnp.int32) - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]


def _paged_chunk_hidden(
    cfg: ArchConfig,
    params: dict,
    cache: list,
    inputs: jax.Array,
    start: jax.Array,
    block_tables: jax.Array,
    length: jax.Array,
    *,
    moe_policy: str,
) -> tuple[jax.Array, list]:
    """Shared chunk tower for the paged prompt/verify paths: embed, run the
    stack through ``block_paged_prefill``, final-norm. Returns the normed
    hidden states of every chunk row ([B,C,D]) plus the new cache; the
    callers differ only in which rows they project to logits."""
    x = embed_apply(cfg, params["embed"], inputs)

    def block(slot, p, x, c, layer):
        return block_paged_prefill(
            cfg, slot, p, x, c, layer, start, block_tables, length,
            moe_policy=moe_policy,
        )

    x, new_cache = _paged_scan(cfg, params, cache, x, block)
    from .layers import norm_apply

    return norm_apply(cfg, params["final_norm"], x), new_cache


def paged_prefill_step(
    cfg: ArchConfig,
    params: dict,
    cache: list,
    inputs: jax.Array,
    start: jax.Array,
    block_tables: jax.Array,
    length: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, list]:
    """Chunk-of-C prompt tokens for the whole stack through the paged KV
    cache (DESIGN.md §10).

    inputs: [B,C] tokens (columns >= ``length`` are bucket padding); start:
    i32[B] first chunk position; block_tables: i32[B, PB]; length: i32[B].
    Returns (logits of the last real chunk row [B,V], new cache) — the
    logits that prime generation when the chunk reaches the prompt end.
    Bit-for-bit equal on CPU to feeding the same C tokens through C
    iterations of ``paged_decode_step``.
    """
    x, new_cache = _paged_chunk_hidden(
        cfg, params, cache, inputs, start, block_tables, length,
        moe_policy=moe_policy,
    )
    logits = head_apply(
        cfg, params["head"], params["embed"], _last_real_row(x, length)
    )
    return logits, new_cache


def paged_verify_step(
    cfg: ArchConfig,
    params: dict,
    cache: list,
    inputs: jax.Array,
    start: jax.Array,
    block_tables: jax.Array,
    length: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, list]:
    """Verify lane (DESIGN.md §11): score all K+1 positions of a draft
    window in one pass through the paged chunk tower.

    Same contract as ``paged_prefill_step`` — inputs are the current token
    followed by K draft candidates, columns >= ``length`` are bucket
    padding writing only the null page — but the head projects *every*
    chunk row: returns (logits [B,C,V], new cache). Row i's logits are
    bit-for-bit what ``paged_decode_step`` would produce after feeding
    rows 0..i sequentially, which is what makes greedy speculative decode
    exactly equal to plain greedy decode.
    """
    x, new_cache = _paged_chunk_hidden(
        cfg, params, cache, inputs, start, block_tables, length,
        moe_policy=moe_policy,
    )
    logits = head_apply(cfg, params["head"], params["embed"], x)
    return logits, new_cache


def _dense_chunk_hidden(
    cfg: ArchConfig,
    params: dict,
    cache: list,
    inputs: jax.Array,
    start: jax.Array,
    length: jax.Array,
    *,
    moe_policy: str,
) -> tuple[jax.Array, list]:
    """Shared chunk tower for the dense prompt/verify paths (the dense
    counterpart of ``_paged_chunk_hidden``)."""
    x = embed_apply(cfg, params["embed"], inputs)

    def body(x, slots):
        slot_params, slot_caches = slots
        new_caches = []
        for slot in range(cfg.period):
            x, c = block_chunk_decode(
                cfg, slot, slot_params[slot], x, slot_caches[slot], start,
                length, moe_policy=moe_policy,
            )
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_cache = jax.lax.scan(body, x, (tuple(params["blocks"]), tuple(cache)))
    from .layers import norm_apply

    return norm_apply(cfg, params["final_norm"], x), list(new_cache)


def chunked_decode_step(
    cfg: ArchConfig,
    params: dict,
    cache: list,
    inputs: jax.Array,
    start: jax.Array,
    length: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, list]:
    """Chunk-of-C prompt tokens for the whole stack into the dense per-slot
    cache (DESIGN.md §10) — the dense engine's prompt path.

    inputs: [B,C] tokens; start: i32[B] per-row first position; length:
    i32[B] real tokens (0 = idle row). Returns (logits of the last real
    chunk row [B,V], new cache). Bit-for-bit equal on CPU to C iterations
    of ``decode_step`` with per-row positions.
    """
    x, new_cache = _dense_chunk_hidden(
        cfg, params, cache, inputs, start, length, moe_policy=moe_policy
    )
    logits = head_apply(
        cfg, params["head"], params["embed"], _last_real_row(x, length)
    )
    return logits, new_cache


def chunked_verify_step(
    cfg: ArchConfig,
    params: dict,
    cache: list,
    inputs: jax.Array,
    start: jax.Array,
    length: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, list]:
    """Verify lane over the dense per-slot cache (DESIGN.md §11): score all
    K+1 positions of a draft window in one pass — ``chunked_decode_step``
    with the head applied to every chunk row. Returns (logits [B,C,V], new
    cache); rows with length 0 are idle and write nothing."""
    x, new_cache = _dense_chunk_hidden(
        cfg, params, cache, inputs, start, length, moe_policy=moe_policy
    )
    logits = head_apply(cfg, params["head"], params["embed"], x)
    return logits, new_cache


def draft_view(
    cfg: ArchConfig, params: dict, draft_layers: int = 1
) -> tuple[ArchConfig, dict]:
    """Truncated-layer draft model: the speculative-decode predictor as a
    *view* of the target (DESIGN.md §11) — no extra weights to train, load,
    or checkpoint.

    Keeps the first ``draft_layers`` repetitions of each period slot's
    stacked block params (leaves are stacked ``[m, ...]``; the view slices
    the leading axis) and shares embed/head/final_norm with the target, so
    a draft forward is exactly a shallower run of the same network. Returns
    ``(draft_cfg, draft_params)`` ready for ``decode_step``/``init_cache``.
    """
    from dataclasses import replace

    m = cfg.num_layers // cfg.period
    d = max(1, min(int(draft_layers), m))
    dcfg = replace(
        cfg, name=f"{cfg.name}-draft{d}", num_layers=d * cfg.period
    ).validate()
    dparams = dict(params)
    dparams["blocks"] = [
        jax.tree.map(lambda t: t[:d], b) for b in params["blocks"]
    ]
    return dcfg, dparams


def copy_cache_pages(cache: list, src: jax.Array, dst: jax.Array) -> list:
    """Copy one physical page's contents (every layer) — the device half of
    copy-on-write (``kvcache.BlockTable.ensure_writable``). Cold path only;
    jit once per engine with donation so it is a cheap in-place scatter."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    # leaves are [m, P, page_size, KH, dh] pages — and, for int8 pools,
    # [m, P, page_size] per-page scale arrays: page axis is 1 in both, so
    # one tree map COWs quantised bits and scales together (DESIGN.md §12)
    return jax.tree.map(lambda t: t.at[:, dst].set(t[:, src]), cache)


def pad_cache(cfg: ArchConfig, cache: list, max_len: int) -> list:
    """Grow prefill KV caches (length = prompt) to max_len for decoding."""

    def pad(slot: int, tree: dict) -> dict:
        if not cfg.mixer_at(slot).startswith("attn"):
            return tree  # SSM caches are O(1); nothing to grow
        def grow(t):
            # [m, B, S, KH, dh] -> [m, B, max_len, KH, dh]
            padw = [(0, 0)] * t.ndim
            padw[2] = (0, max_len - t.shape[2])
            return jnp.pad(t, padw)
        return jax.tree.map(grow, tree)

    return [pad(slot, c) for slot, c in enumerate(cache)]


# ------------------------------------------------------------- input specs
def input_specs(cfg: ArchConfig, kind: str, batch: int, seq: int) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation).

    For [audio]/[vlm] archs the stub modality frontend supplies precomputed
    frame/patch embeddings (DESIGN.md §4).
    """
    dt = dtype_of(cfg)
    if cfg.input_kind == "tokens":
        train_in = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        dec_in = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    else:
        train_in = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), dt)
        dec_in = jax.ShapeDtypeStruct((batch, 1, cfg.d_model), dt)
    if kind == "train":
        return {
            "inputs": train_in,
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        }
    if kind == "prefill":
        return {"inputs": train_in}
    if kind == "decode":
        return {"inputs": dec_in, "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    raise ValueError(kind)
