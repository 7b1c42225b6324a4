"""Block assembly: (mixer, mlp) pairs per period slot, scanned over repeats.

A config's ``layer_pattern``/``mlp_pattern`` define a period-p cycle; the L
layers are p "slots" repeated m = L/p times. Params (and caches) are stacked
[m, ...] per slot and the stack is driven by ``lax.scan`` — one traced block
body per slot regardless of depth, which keeps 95-layer × 512-device compiles
tractable (DESIGN.md §8).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.distributed.sharding import hint

from . import attention as attn
from . import mlp as mlp_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import norm_apply, norm_init


def block_init(cfg: ArchConfig, key: jax.Array, slot: int) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    mixer = cfg.mixer_at(slot)
    mlp = cfg.mlp_at(slot)
    p: dict[str, Any] = {"norm1": norm_init(cfg)}
    if mixer.startswith("attn"):
        p["attn"] = attn.attn_init(cfg, k1)
    else:
        p["ssm"] = ssm_mod.ssm_init(cfg, k1)
    if mlp == "mlp":
        p["norm2"] = norm_init(cfg)
        p["mlp"] = mlp_mod.mlp_init(cfg, k2)
    elif mlp == "moe":
        p["norm2"] = norm_init(cfg)
        p["moe"] = moe_mod.moe_init(cfg, k2)
    return p


def _block_tail(
    cfg: ArchConfig, slot: int, p: dict, x: jax.Array, *, moe_policy: str
) -> tuple[jax.Array, jax.Array]:
    """Residual MLP/MoE tail shared by every block variant. Returns
    (x, moe_aux); aux is zero unless the slot routes through a MoE."""
    aux = jnp.zeros((), jnp.float32)
    mlp = cfg.mlp_at(slot)
    if mlp != "none":
        h = norm_apply(cfg, p["norm2"], x)
        if mlp == "mlp":
            h = mlp_mod.mlp_apply(cfg, p["mlp"], h)
        else:
            h, aux = moe_mod.moe_apply(cfg, p["moe"], h, policy=moe_policy)
        x = x + h
    return x, aux


def block_apply(
    cfg: ArchConfig,
    slot: int,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    impl: str = "naive",
    moe_policy: str = "drop",
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence block. Returns (x, moe_aux)."""
    x = hint(x, "batch", None, None)
    mixer = cfg.mixer_at(slot)
    h = norm_apply(cfg, p["norm1"], x)
    if mixer.startswith("attn"):
        h = attn.attention(
            cfg, p["attn"], h, positions, local=(mixer == "attn_local"), impl=impl
        )
    else:
        h, _ = ssm_mod.ssm_apply(cfg, p["ssm"], h)
    x = x + h
    return _block_tail(cfg, slot, p, x, moe_policy=moe_policy)


def block_cache_init(
    cfg: ArchConfig, slot: int, batch: int, max_len: int,
    kv_dtype: str = "fp32",
) -> dict:
    mixer = cfg.mixer_at(slot)
    if mixer.startswith("attn"):
        return attn.init_kv_cache(cfg, batch, max_len, kv_dtype)
    if kv_dtype != "fp32":
        raise ValueError(
            f"{cfg.name}: slot {slot} mixer {mixer!r} has recurrent state; "
            f"quantised dense KV is attention-only."
        )
    return ssm_mod.init_ssm_cache(cfg, batch)


def block_prefill(
    cfg: ArchConfig,
    slot: int,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    impl: str = "naive",
    moe_policy: str = "drop",
) -> tuple[jax.Array, dict]:
    """Full-sequence block that also emits this slot's cache entry."""
    mixer = cfg.mixer_at(slot)
    h = norm_apply(cfg, p["norm1"], x)
    if mixer.startswith("attn"):
        h, cache = attn.prefill_attention(
            cfg, p["attn"], h, positions, local=(mixer == "attn_local"), impl=impl
        )
    else:
        h, cache = ssm_mod.ssm_apply(cfg, p["ssm"], h, return_cache=True)
    x = x + h
    x, _ = _block_tail(cfg, slot, p, x, moe_policy=moe_policy)
    return x, cache


def block_paged_cache_init(
    cfg: ArchConfig,
    slot: int,
    num_pages: int,
    page_size: int,
    kv_dtype: str = "fp32",
) -> dict:
    """Per-slot paged cache entry (attention mixers only, DESIGN.md §9).
    ``kv_dtype`` selects fp32 or int8+scales page storage (DESIGN.md §12)."""
    mixer = cfg.mixer_at(slot)
    if not mixer.startswith("attn"):
        raise ValueError(
            f"{cfg.name}: slot {slot} mixer {mixer!r} has recurrent state; "
            f"the paged KV path supports attention-only stacks."
        )
    return attn.init_paged_kv_cache(cfg, num_pages, page_size, kv_dtype)


def block_paged_decode(
    cfg: ArchConfig,
    slot: int,
    p: dict,
    x: jax.Array,
    cache: dict,
    layer: jax.Array,
    pos: jax.Array,
    block_tables: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, dict]:
    """Single-token block step through the paged KV cache (DESIGN.md §9).
    ``cache`` is the slot's stacked ``[m, ...]`` pool; ``layer`` the repeat
    this step reads and writes in place. Returns the updated stack."""
    mixer = cfg.mixer_at(slot)
    h = norm_apply(cfg, p["norm1"], x)
    if not mixer.startswith("attn"):
        raise ValueError(
            f"{cfg.name}: slot {slot} mixer {mixer!r}: paged decode is "
            f"attention-only (see block_paged_cache_init)."
        )
    h, cache = attn.paged_decode_attention(
        cfg, p["attn"], h, cache, layer, pos, block_tables,
        local=(mixer == "attn_local"),
    )
    x = x + h
    x, _ = _block_tail(cfg, slot, p, x, moe_policy=moe_policy)
    return x, cache


def block_paged_prefill(
    cfg: ArchConfig,
    slot: int,
    p: dict,
    x: jax.Array,
    cache: dict,
    layer: jax.Array,
    start: jax.Array,
    block_tables: jax.Array,
    length: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, dict]:
    """Chunked-prefill block step through the paged KV cache (DESIGN.md §10).
    ``cache`` and ``layer`` as in ``block_paged_decode``."""
    mixer = cfg.mixer_at(slot)
    h = norm_apply(cfg, p["norm1"], x)
    if not mixer.startswith("attn"):
        raise ValueError(
            f"{cfg.name}: slot {slot} mixer {mixer!r}: paged prefill is "
            f"attention-only (see block_paged_cache_init)."
        )
    h, cache = attn.paged_prefill_attention(
        cfg, p["attn"], h, cache, layer, start, block_tables, length,
        local=(mixer == "attn_local"),
    )
    x = x + h
    x, _ = _block_tail(cfg, slot, p, x, moe_policy=moe_policy)
    return x, cache


def block_chunk_decode(
    cfg: ArchConfig,
    slot: int,
    p: dict,
    x: jax.Array,
    cache: dict,
    start: jax.Array,
    length: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, dict]:
    """Chunked-prefill block step into the dense per-slot cache
    (DESIGN.md §10). Attention-only: SSM state is recurrent and would need
    a per-chunk scan — those stacks fall back to token-by-token forcing."""
    mixer = cfg.mixer_at(slot)
    h = norm_apply(cfg, p["norm1"], x)
    if not mixer.startswith("attn"):
        raise ValueError(
            f"{cfg.name}: slot {slot} mixer {mixer!r}: chunked prefill is "
            f"attention-only; teacher-force SSM stacks token by token."
        )
    h, cache = attn.chunked_decode_attention(
        cfg, p["attn"], h, cache, start, length,
        local=(mixer == "attn_local"),
    )
    x = x + h
    x, _ = _block_tail(cfg, slot, p, x, moe_policy=moe_policy)
    return x, cache


def block_decode(
    cfg: ArchConfig,
    slot: int,
    p: dict,
    x: jax.Array,
    cache: dict,
    pos: jax.Array,
    *,
    moe_policy: str = "drop",
) -> tuple[jax.Array, dict]:
    """Single-token block step."""
    mixer = cfg.mixer_at(slot)
    h = norm_apply(cfg, p["norm1"], x)
    if mixer.startswith("attn"):
        h, cache = attn.decode_attention(
            cfg, p["attn"], h, cache, pos, local=(mixer == "attn_local")
        )
    else:
        h, cache = ssm_mod.ssm_decode_step(cfg, p["ssm"], h, cache)
    x = x + h
    x, _ = _block_tail(cfg, slot, p, x, moe_policy=moe_policy)
    return x, cache
