"""Serving engine: bucketed AOT dispatch built on the unified dispatch core.

The HFT analogy made literal (DESIGN.md §2/§4): the *hot path* is the token
loop — it must never trace, compile, hash a jit cache key, or branch on mode.
The *cold path* is the scheduler: it admits requests, picks the executable in
the ``Dispatcher``'s compile cache, warms it, and only then lets the hot loop
run.

Two serving modes share one ``core.dispatch.Dispatcher``:

* **Per-burst** (the paper's construct, one burst at a time):
  ``Engine.set_mode(...)`` is ``set_direction`` (with dummy-order warming);
  ``Engine.decode_loop`` is the patched-jmp hot path. The sampling mode is
  baked into the executable, so every mode flip is a dispatch (and a cold
  compile on first sight of a ``("burst", bucket, mode)`` key).
* **Continuous batching** (``Engine.continuous()`` →
  ``runtime.scheduler.ContinuousBatcher``): one executable per bucket size,
  sampling params packed per-slot *as data*. Requests join and leave
  mid-loop; after warmup the dispatcher's compile counter never moves.

Plus the paged variant (``Engine.paged_continuous()`` →
``PagedContinuousBatcher``, DESIGN.md §9): KV lives in a shared page pool,
requests map positions through block tables, and the dispatch key grows
more coordinates — ``("cbp", slots, pages_bucket, kv_dtype)`` — the
semi-static capacity bucket and page dtype (DESIGN.md §12). All buckets
are AOT-warmed (log-sized fan-out), so bucket crossings rebind but never
compile.

Both continuous engines run a **multi-lane step pipeline** (DESIGN.md
§10/§11): prefill chunks through ``("pf"/"pfd", ..., chunk_bucket)`` (or,
when few paged slots prefill, a row bucket: ``("pfr", slots, rows, ...)``), and —
with ``spec_k > 0`` — speculative decoding through the draft/verify lanes:
``("dr", slots, k_bucket)`` runs a truncated-layer *view* of the target
(``models.draft_view``, no extra weights) K steps in one executable, and
``("vf"/"vfd", slots, k_bucket)`` scores all K+1 positions in one target
pass over the chunked scatter path.

The key space itself is declarative (DESIGN.md §12): every lane is a
``core.lanes.LaneSpec`` — name, coordinate axes with their bucket ladders,
builder/warmer hooks — and warmup is one registry iteration: every key in
every enabled lane's ``fanout`` is AOT-compiled *and* dummy-run, so the
whole fan-out — decode × capacity, prefill × chunk, draft/verify × k,
paged lanes × ``kv_dtype`` — compiles exactly once per engine and never
again. ``kv_dtype ∈ {fp32, int8}`` is the first registry-added coordinate:
quantised int8 KV pages (per-page scales, ~4× the pages per byte) are just
another semi-static axis — flipping a pool's dtype is a rebind over warmed
executables, never a compile and never a per-step branch. Unregistered
lanes raise ``UnknownLaneError`` at build/warmup time instead of falling
through silently.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs import ArchConfig
from repro.core import DispatchPolicy, Dispatcher, bucket_multiple
from repro.core import lanes as lanes_mod
from repro.core.lanes import LANES
from repro.core.telemetry import Telemetry
from repro.distributed import sharding as shd
from repro.runtime import steps as steps_mod
from repro.runtime.scheduler import (
    CHUNK_BUCKET_MIN,
    Clock,
    ContinuousBatcher,
    PagedContinuousBatcher,
    Request,
    RequestQueue,
    form_bursts,
    latency_report,
)

GREEDY, SAMPLE = 0, 1


@dataclass
class EngineConfig:
    max_len: int = 512
    batch_quantum: int = 4
    max_batch: int = 64
    temperature: float = 1.0
    moe_policy: str = "drop"
    # Dispatch policy (DESIGN.md §3): how sticky is the hot slot, and how
    # many executables may the compile cache keep?
    hysteresis: int = 1
    cache_capacity: int | None = None
    # Paged KV cache (DESIGN.md §9): page granularity and pool size
    # (allocatable pages, excluding the reserved null page). 0 pages means
    # "dense-equivalent": slots × max_len tokens worth of pages.
    page_size: int = 16
    num_pages: int = 0
    # Chunked prefill (DESIGN.md §10): the largest prompt chunk ingested per
    # step. 0 disables the chunked lane (prompts teacher-force token by
    # token at decode speed — the baseline). Chunk sizes are drawn from the
    # log-sized bucket set {8, 16, ..., prefill_chunk}, each an AOT-warmed
    # ("pf", slots, chunk_bucket, kv_dtype, mesh) dispatch key. A paged
    # call whose prefilling slots fit a smaller power-of-two row bucket
    # runs at prefill_chunk columns as ("pfr", slots, rows, kv_dtype, mesh)
    # instead, unless spec lanes are on or the page pool is sharded.
    prefill_chunk: int = 0
    # Per-step token budget split across the lanes by the LanePolicy;
    # 0 = slots + prefill_chunk.
    token_budget: int = 0
    # Speculative decoding (DESIGN.md §11): max draft depth per target step
    # (0 disables the draft/verify lanes; per-step k is drawn from the
    # log-sized k-bucket set {1, 2, ..., spec_k}, each an AOT-warmed
    # dispatch key) and the truncated-layer draft view's depth in
    # layer-periods (models.draft_view).
    spec_k: int = 0
    draft_layers: int = 1
    # Quantised KV pages (DESIGN.md §12): the paged pool's storage dtype
    # ("fp32" or "int8" — int8 pages carry per-page scales and cost ~1/4
    # the bytes), plus any *extra* dtypes to AOT-warm so a pool flip is a
    # rebind over warmed executables, never a compile. kv_dtype is a
    # registry axis on every paged lane key.
    kv_dtype: str = "fp32"
    kv_dtypes: tuple = ()
    # Quantised draft KV (DESIGN.md §16): the draft lanes' dense-cache
    # storage dtype plus extras to keep warm — an int8 drafter pairs with a
    # full-precision verify lane, and a precision flip across the warmed
    # set is a rebind, never a compile.
    draft_kv_dtype: str = "fp32"
    draft_kv_dtypes: tuple = ()
    # Sharded serving (DESIGN.md §16): the active device mesh "DPxMP"
    # (data x model) plus every standby topology to AOT-warm. The mesh is
    # a trailing coordinate on every pool-touching lane key, so a topology
    # change at run time — scale-out 1x1->2x2 or a failover shrink — is a
    # hot-slot flip plus a device_put of the live cache, never a compile.
    mesh: str = "1x1"
    meshes: tuple = ()


@dataclass
class _WarmCtx:
    """Mutable state threaded through one registry warmup pass.

    The warm methods dummy-run each executable through the exact runtime
    path (paper §4.3) and thread the donated caches forward; ``spec`` is
    the per-batcher speculation opt-in the ``_spec_lanes_enabled`` gate
    reads. Caches are keyed per mesh coordinate (and pool dtype): a warm
    run through a sharded executable hands back a cache *committed* to
    that mesh's NamedSharding, which a different mesh's executable would
    reject — so every (dtype, mesh) cell warms against its own cache and
    the batcher adopts the active cell's (DESIGN.md §12/§16).
    """

    spec: bool = False
    disagg: bool = False  # per-batcher disaggregation opt-in (§17)
    dense_caches: dict = None  # mesh -> dense cache
    paged_caches: dict = None  # (kv_dtype, mesh) -> pooled cache
    draft_caches: dict = None  # (draft_kv_dtype, mesh) -> draft cache


class Engine:
    """Single-host reference engine (the multi-pod path reuses steps.py)."""

    def __init__(
        self,
        cfg: ArchConfig,
        params: Any,
        ecfg: EngineConfig,
        telemetry: Telemetry | None = None,
    ):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        # Flight recorder + metrics registry (DESIGN.md §14). The default
        # is disabled recording: the registry still accumulates (it backs
        # latency_report), but the event ring costs one None-check per
        # call site until ``telemetry.enable()``.
        self.telemetry = telemetry or Telemetry()
        self._warm_marks: dict | None = None
        self._burst_calls = None  # lazy: lane_calls_total{lane="burst"}
        self._burst_hist = None  # lazy: lane_step_ms{lane="burst"}
        self._decode = Dispatcher(
            self._build,
            name=f"decode@{id(self):x}",
            policy=DispatchPolicy(
                hysteresis=ecfg.hysteresis, capacity=ecfg.cache_capacity
            ),
            recorder=self.telemetry.recorder,
        )
        self._current: Callable | None = None  # mirror of the hot slot
        self._current_key: tuple | None = None
        # Mesh plans (DESIGN.md §16): one MeshPlan per warmed topology
        # name; plans own the lazy jax Mesh and the NamedSharding trees.
        self._mesh_plans: dict[str, shd.MeshPlan] = {}
        self._solo_params: dict[tuple[str, str], Any] = {}
        # Speculative decoding (DESIGN.md §11): the draft model is a
        # truncated-layer *view* of the target — shared embed/head, the
        # first draft_layers periods of blocks — so it costs no extra
        # weights and its abstract shapes derive from the same params.
        self.draft_cfg = None
        self.draft_params = None
        if ecfg.spec_k > 0:
            self.draft_cfg, self.draft_params = models.draft_view(
                cfg, params, ecfg.draft_layers
            )
        self.stats = {"tokens": 0, "hot_calls": 0, "mode_switches": 0}

    def close(self) -> None:
        """Release the dispatcher's entry-point name (and with it the
        registry reference that keeps this Engine and its params alive)."""
        self._decode.close()

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # ------------------------------------------------------------ cold path
    def _abstract_params(self):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params
        )

    def _tok_aval(self, batch: int) -> jax.ShapeDtypeStruct:
        if self.cfg.input_kind == "tokens":
            return jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        return jax.ShapeDtypeStruct(
            (batch, 1, self.cfg.d_model), jnp.dtype(self.cfg.dtype)
        )

    def _build(self, key: tuple) -> Callable:
        """Dispatcher builder: compile the executable for a dispatch key.

        The key space is the ``core.lanes`` registry (DESIGN.md §12): the
        key's lane name resolves to its ``LaneSpec``, whose ``builder``
        hook receives the arity-checked coordinates. An unregistered lane
        (or a malformed key) raises ``UnknownLaneError`` here — at
        build/warmup time — instead of falling through a sniffing chain.
        """
        spec = LANES.spec_for(key)
        if not self.telemetry.compile_analysis:
            return getattr(self, spec.builder)(*spec.coords(key))
        # Per-key compile report (DESIGN.md §14): build time plus the HLO
        # cost-model estimate, collected into telemetry.compile_reports
        # (launch/serve.py --compile-report writes them as one artifact).
        t0 = time.perf_counter()
        exe = getattr(self, spec.builder)(*spec.coords(key))
        build_ms = (time.perf_counter() - t0) * 1e3
        from repro.hlo_analysis import analyze_compiled

        rep = analyze_compiled(exe)
        rep["key"] = str(key)
        rep["lane"] = spec.name
        rep["build_ms"] = round(build_ms, 3)
        self.telemetry.compile_reports.append(rep)
        return exe

    # ------------------------------------------------------- mesh lowering
    def _mesh_plan(self, name: str) -> shd.MeshPlan:
        plan = self._mesh_plans.get(name)
        if plan is None:
            plan = self._mesh_plans[name] = shd.MeshPlan(name)
        return plan

    def _params_for_mesh(self, mesh: str, *, draft: bool = False) -> Any:
        """Params as the lane executables for ``mesh`` expect them.

        On the default device (``single`` plans) this is ``self.params``
        untouched. On a one-device offset slice (§17) the weights are
        committed to the slice's device once and cached — otherwise every
        call of a prefill-slice executable re-transfers the whole
        parameter tree through the default device. Non-solo plans keep
        the uncommitted tree: GSPMD executables shard it themselves.
        """
        base = self.draft_params if draft else self.params
        plan = self._mesh_plan(mesh)
        if plan.single or not plan.solo:
            return base
        key = (plan.name, "draft" if draft else "target")
        hit = self._solo_params.get(key)
        if hit is None:
            hit = self._solo_params[key] = jax.device_put(base, plan.device)
        return hit

    def _compile_step(
        self,
        step: Callable,
        mesh: str,
        params_aval: Any,
        c_shape: Any,
        row_avals: tuple,
        cache_kind: str,
    ) -> Callable:
        """Lower + AOT-compile one lane executable under a mesh plan.

        ``"1x1"`` takes the exact pre-mesh path — no Mesh, no shardings —
        which is what keeps the 1x1 lane bitwise identical to the
        unsharded engine. One-device *offset* slices ("1x1@1", §17) take
        the same plain-jit path pinned to their device with
        ``SingleDeviceSharding`` — a one-device GSPMD mesh pays real
        per-call overhead (sharded output wrappers, slow D2H) for nothing.
        Non-solo plans lower under the plan's Mesh with GSPMD
        ``in_shardings``: TP params over 'model', per-slot rows and cache
        slots/pages over 'data' (DESIGN.md §16); the compiler propagates
        output shardings, so the donated cache round-trips committed to
        the same plan.
        """
        plan = self._mesh_plan(mesh)
        if plan.single:
            return jax.jit(step, donate_argnums=(1,)).lower(
                params_aval, c_shape, *row_avals
            ).compile()
        if plan.solo:
            pin = jax.sharding.SingleDeviceSharding(plan.device)
            return jax.jit(
                step, donate_argnums=(1,), in_shardings=pin,
                out_shardings=pin,
            ).lower(params_aval, c_shape, *row_avals).compile()
        cache_sh = (
            plan.paged_cache_shardings(c_shape)
            if cache_kind == "paged"
            else plan.dense_cache_shardings(c_shape)
        )
        in_sh = (
            plan.param_shardings(params_aval),
            cache_sh,
            *plan.row_shardings(row_avals),
        )
        with plan.mesh, shd.use_shard_hints(plan.mesh):
            lowered = jax.jit(
                step, donate_argnums=(1,), in_shardings=in_sh
            ).lower(params_aval, c_shape, *row_avals)
        return lowered.compile()

    def _reshard_cache(self, cache: Any, mesh: str, cache_kind: str) -> Any:
        """Move a live cache to the target topology: pure data movement
        (``jax.device_put``), no compile, no host round-trip. Shrinking to
        "1x1" gathers onto the default device so the unsharded executables
        accept it unchanged."""
        plan = self._mesh_plan(mesh)
        if plan.solo:
            return jax.device_put(cache, plan.device)
        shape = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache
        )
        sh = (
            plan.paged_cache_shardings(shape)
            if cache_kind == "paged"
            else plan.dense_cache_shardings(shape)
        )
        return jax.device_put(cache, sh)

    def _build_burst_decode(self, batch: int, mode: int) -> Callable:
        cfg, ecfg = self.cfg, self.ecfg
        step = steps_mod.make_sampling_decode_fn(
            cfg,
            mode=mode,
            temperature=ecfg.temperature,
            moe_policy=ecfg.moe_policy,
        )
        c_shape = jax.eval_shape(
            lambda: models.init_cache(cfg, batch, ecfg.max_len)
        )
        lowered = jax.jit(step, donate_argnums=(1,)).lower(
            self._abstract_params(),
            c_shape,
            self._tok_aval(batch),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        return lowered.compile()

    def _build_slot_decode(self, slots: int, mesh: str = "1x1") -> Callable:
        cfg, ecfg = self.cfg, self.ecfg
        step = steps_mod.make_slot_decode_fn(cfg, moe_policy=ecfg.moe_policy)
        c_shape = jax.eval_shape(
            lambda: models.init_cache(cfg, slots, ecfg.max_len)
        )
        rows = (
            jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
        )
        return self._compile_step(
            step, mesh, self._abstract_params(), c_shape, rows, "dense"
        )

    def _build_paged_slot_decode(
        self,
        slots: int,
        pages_bucket: int,
        kv_dtype: str = "fp32",
        mesh: str = "1x1",
    ) -> Callable:
        """Executable for the ``("cbp", slots, pages_bucket, kv_dtype,
        mesh)`` dispatch key.

        Capacity is one semi-static condition here (DESIGN.md §9): the block
        table's width is baked into the shapes, so the hot loop never checks
        whether a request fits — outgrowing the bucket re-dispatches on the
        cold path exactly like a paper branch-direction change. The page
        dtype is another (DESIGN.md §12): the cache's abstract dtype bakes
        the quant/dequant into the executable, so fp32 and int8 pools are
        two AOT branch targets, never a per-step check. The mesh is a third
        (DESIGN.md §16): the sharding plan is baked at lower time, so each
        topology is its own AOT branch target.
        """
        cfg, ecfg = self.cfg, self.ecfg
        step = steps_mod.make_paged_slot_decode_fn(
            cfg, moe_policy=ecfg.moe_policy
        )
        c_shape = jax.eval_shape(
            lambda: models.init_paged_cache(
                cfg, self.pool_physical_pages, ecfg.page_size, kv_dtype
            )
        )
        rows = (
            jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, pages_bucket), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
        )
        return self._compile_step(
            step, mesh, self._abstract_params(), c_shape, rows, "paged"
        )

    def _build_paged_prefill(
        self,
        slots: int,
        chunk_bucket: int,
        kv_dtype: str = "fp32",
        mesh: str = "1x1",
    ) -> Callable:
        """Executable for the ``("pf", slots, chunk_bucket, kv_dtype,
        mesh)`` dispatch key: *batched* paged chunked prefill.

        Chunk size is the headline semi-static condition (DESIGN.md §10):
        the chunk width is baked into the shapes, one executable per bucket
        in the log-sized set, all AOT-warmed — prompt-length variation
        picks an executable on the cold path and never branches in the hot
        loop. Every prefilling slot the budget covers rides the same call
        (length 0 = idle row), mirroring the dense ``("pfd", ...)`` lane —
        the old B=1-per-step limitation is gone. The block-table width is
        pinned at the per-request page cap (masked positions contribute
        exactly nothing); the page dtype is the registry's ``kv_dtype``
        axis (DESIGN.md §12). A call carrying fewer chunks than a smaller
        row bucket holds goes to ``_build_paged_prefill_rows`` instead.
        """
        cfg, ecfg = self.cfg, self.ecfg
        step = steps_mod.make_paged_prefill_fn(cfg, moe_policy=ecfg.moe_policy)
        c_shape = jax.eval_shape(
            lambda: models.init_paged_cache(
                cfg, self.pool_physical_pages, ecfg.page_size, kv_dtype
            )
        )
        pb = self.max_pages_per_req
        rows = (
            jax.ShapeDtypeStruct((slots, chunk_bucket), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, pb), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
        )
        return self._compile_step(
            step, mesh, self._abstract_params(), c_shape, rows, "paged"
        )

    def _build_paged_prefill_rows(
        self,
        slots: int,
        rows: int,
        kv_dtype: str = "fp32",
        mesh: str = "1x1",
    ) -> Callable:
        """Executable for the ``("pfr", slots, rows, kv_dtype, mesh)``
        dispatch key: the paged prefill sized by the slots that prefill.

        The row count is a semi-static condition beside the chunk width
        (DESIGN.md §10): the batcher packs a call's chunks into the first
        of ``rows`` rows, a power-of-two bucket below ``slots``, and the
        executable gathers the per-slot inputs by each row's slot and
        scatters the outputs back, so one new prompt pays for one row, not
        for every slot. Only the top chunk bucket is built at these row
        counts: with the ``("pf", slots, ...)`` keys at every chunk bucket
        the warm set is L-shaped, not a product. The per-slot arrays keep
        the ``slots`` width, which is why ``slots`` stays in the key.
        """
        cfg, ecfg = self.cfg, self.ecfg
        step = steps_mod.make_paged_prefill_fn(
            cfg, moe_policy=ecfg.moe_policy, by_rows=True
        )
        c_shape = jax.eval_shape(
            lambda: models.init_paged_cache(
                cfg, self.pool_physical_pages, ecfg.page_size, kv_dtype
            )
        )
        avals = (
            jax.ShapeDtypeStruct((rows, ecfg.prefill_chunk), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((rows, self.max_pages_per_req), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
        )
        return self._compile_step(
            step, mesh, self._abstract_params(), c_shape, avals, "paged"
        )

    def _build_slot_prefill(
        self, slots: int, chunk_bucket: int, mesh: str = "1x1"
    ) -> Callable:
        """Executable for the ``("pfd", slots, chunk_bucket, mesh)``
        dispatch key: the dense engine's chunked prompt path (DESIGN.md
        §10) — a slot's private cache rows are a trivial identity block
        table, so the same chunk-bucket machinery serves both engines."""
        cfg, ecfg = self.cfg, self.ecfg
        step = steps_mod.make_slot_prefill_fn(cfg, moe_policy=ecfg.moe_policy)
        c_shape = jax.eval_shape(
            lambda: models.init_cache(cfg, slots, ecfg.max_len)
        )
        rows = (
            jax.ShapeDtypeStruct((slots, chunk_bucket), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
        )
        return self._compile_step(
            step, mesh, self._abstract_params(), c_shape, rows, "dense"
        )

    def _abstract_draft_params(self):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self.draft_params,
        )

    def _build_draft(
        self,
        slots: int,
        k: int,
        kv_dtype: str = "fp32",
        mesh: str = "1x1",
    ) -> Callable:
        """Executable for the ``("dr", slots, k, draft_kv_dtype, mesh)``
        dispatch key: K draft decode steps scanned inside one executable
        (DESIGN.md §11). Draft depth is the semi-static condition — k is
        baked into the scan length, so depth variation re-dispatches on
        the cold path and the hot loop never counts iterations. The draft
        cache's storage dtype is its own coordinate (DESIGN.md §16): an
        int8 drafter pairs with a full-precision verify lane, the
        quant/dequant baked in at trace time."""
        ecfg = self.ecfg
        step = steps_mod.make_draft_fn(
            self.draft_cfg, k=k, moe_policy=ecfg.moe_policy
        )
        c_shape = jax.eval_shape(
            lambda: models.init_cache(
                self.draft_cfg, slots, ecfg.max_len, kv_dtype
            )
        )
        rows = (
            jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
        )
        return self._compile_step(
            step, mesh, self._abstract_draft_params(), c_shape, rows,
            "dense",
        )

    def _build_paged_verify(
        self,
        slots: int,
        k: int,
        kv_dtype: str = "fp32",
        mesh: str = "1x1",
    ) -> Callable:
        """Executable for the ``("vf", slots, k, kv_dtype, mesh)`` dispatch
        key: the target scores all K+1 window positions in one pass through
        the paged chunk path (DESIGN.md §11). The window width k+1 is baked
        into the shapes; the block-table width is pinned at the per-request
        page cap (masked positions contribute exactly nothing); the page
        dtype rides as the registry's ``kv_dtype`` axis (DESIGN.md §12)."""
        cfg, ecfg = self.cfg, self.ecfg
        step = steps_mod.make_paged_verify_fn(cfg, moe_policy=ecfg.moe_policy)
        c_shape = jax.eval_shape(
            lambda: models.init_paged_cache(
                cfg, self.pool_physical_pages, ecfg.page_size, kv_dtype
            )
        )
        pb = self.max_pages_per_req
        rows = (
            jax.ShapeDtypeStruct((slots, k + 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, pb), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
        )
        return self._compile_step(
            step, mesh, self._abstract_params(), c_shape, rows, "paged"
        )

    def _build_slot_verify(
        self, slots: int, k: int, mesh: str = "1x1"
    ) -> Callable:
        """Executable for the ``("vfd", slots, k, mesh)`` dispatch key: the
        dense engine's verify pass (DESIGN.md §11) — a slot's private cache
        rows are a trivial identity block table, so the same k-bucket
        machinery serves both engines."""
        cfg, ecfg = self.cfg, self.ecfg
        step = steps_mod.make_slot_verify_fn(cfg, moe_policy=ecfg.moe_policy)
        c_shape = jax.eval_shape(
            lambda: models.init_cache(cfg, slots, ecfg.max_len)
        )
        rows = (
            jax.ShapeDtypeStruct((slots, k + 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
        )
        return self._compile_step(
            step, mesh, self._abstract_params(), c_shape, rows, "dense"
        )

    def _build_draft_prefill(
        self,
        slots: int,
        chunk_bucket: int,
        kv_dtype: str = "fp32",
        mesh: str = "1x1",
    ) -> Callable:
        """Executable for the ``("drp", slots, chunk_bucket,
        draft_kv_dtype, mesh)`` dispatch key: the draft stack's prompt
        mirror (DESIGN.md §11) — the same chunked dense ingestion as
        ``("pfd", ...)`` but over the truncated-layer draft view, so the
        draft's KV tracks the committed stream in the draft's own storage
        dtype."""
        ecfg = self.ecfg
        step = steps_mod.make_slot_prefill_fn(
            self.draft_cfg, moe_policy=ecfg.moe_policy
        )
        c_shape = jax.eval_shape(
            lambda: models.init_cache(
                self.draft_cfg, slots, ecfg.max_len, kv_dtype
            )
        )
        rows = (
            jax.ShapeDtypeStruct((slots, chunk_bucket), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
        )
        return self._compile_step(
            step, mesh, self._abstract_draft_params(), c_shape, rows,
            "dense",
        )

    def _build_migrate(
        self, op: str, kv_dtype: str = "fp32", mesh: str = "1x1"
    ) -> Callable:
        """Executable for the ``("mg", op, kv_dtype, mesh)`` dispatch key:
        one half of the KV-page migration transport (DESIGN.md §17).

        ``gather(cache, idx[B]) -> block`` slices B pages out of every
        cache leaf (int8 scales ride along) on the source slice;
        ``scatter(cache, block, idx[B]) -> cache`` writes a transported
        block into the destination slice's cache (donated). B is pinned at
        the per-request page cap and short migrations pad ``idx`` with
        null-page ids, so the page *count* never becomes a dispatch
        coordinate — the same two executables move one page or a whole
        request. On sharded slices the block lowers replicated: it is the
        unit that ``device_put``s across slices, so neither end may assume
        the other's layout.
        """
        cfg, ecfg = self.cfg, self.ecfg
        c_shape = jax.eval_shape(
            lambda: models.init_paged_cache(
                cfg, self.pool_physical_pages, ecfg.page_size, kv_dtype
            )
        )
        pb = self.max_pages_per_req
        idx_aval = jax.ShapeDtypeStruct((pb,), jnp.int32)
        gather = steps_mod.make_page_gather_fn()
        plan = self._mesh_plan(mesh)
        if op == "gather":
            if plan.single:
                return jax.jit(gather).lower(c_shape, idx_aval).compile()
            if plan.solo:
                pin = jax.sharding.SingleDeviceSharding(plan.device)
                return jax.jit(
                    gather, in_shardings=pin, out_shardings=pin
                ).lower(c_shape, idx_aval).compile()
            cache_sh = plan.paged_cache_shardings(c_shape)
            rep = shd.replicated(plan.mesh)
            blk_shape = jax.eval_shape(gather, c_shape, idx_aval)
            with plan.mesh, shd.use_shard_hints(plan.mesh):
                lowered = jax.jit(
                    gather,
                    in_shardings=(cache_sh, rep),
                    out_shardings=jax.tree.map(lambda _: rep, blk_shape),
                ).lower(c_shape, idx_aval)
            return lowered.compile()
        if op != "scatter":
            raise ValueError(f"unknown migration op {op!r}")
        scatter = steps_mod.make_page_scatter_fn()
        blk_shape = jax.eval_shape(gather, c_shape, idx_aval)
        if plan.single:
            return jax.jit(scatter, donate_argnums=(0,)).lower(
                c_shape, blk_shape, idx_aval
            ).compile()
        if plan.solo:
            pin = jax.sharding.SingleDeviceSharding(plan.device)
            return jax.jit(
                scatter, donate_argnums=(0,), in_shardings=pin,
                out_shardings=pin,
            ).lower(c_shape, blk_shape, idx_aval).compile()
        cache_sh = plan.paged_cache_shardings(c_shape)
        rep = shd.replicated(plan.mesh)
        with plan.mesh, shd.use_shard_hints(plan.mesh):
            lowered = jax.jit(
                scatter,
                donate_argnums=(0,),
                in_shardings=(
                    cache_sh,
                    jax.tree.map(lambda _: rep, blk_shape),
                    rep,
                ),
                out_shardings=cache_sh,
            ).lower(c_shape, blk_shape, idx_aval)
        return lowered.compile()

    @property
    def pool_pages(self) -> int:
        """Allocatable page count (excluding the null pages)."""
        if self.ecfg.num_pages > 0:
            return self.ecfg.num_pages
        return (self.ecfg.max_batch * self.ecfg.max_len) // self.ecfg.page_size

    @property
    def max_pages_per_req(self) -> int:
        """Per-request page cap: a full max_len sequence, pool permitting."""
        return min(
            self.pool_pages, -(-self.ecfg.max_len // self.ecfg.page_size)
        )

    @property
    def pool_shards(self) -> int:
        """Page-pool shard count: the widest warmed data-parallel degree.

        The pool's physical layout is fixed at construction (per-shard
        contiguous page blocks, ``runtime.kvcache``), so it is laid out
        for the *largest* warmed dp, and every other warmed mesh's dp must
        divide it — a topology rebind then never relabels a page id, only
        regroups whole shards per device.
        """
        meshes = self._warm_meshes()
        shards = max(shd.parse_mesh_name(m)[0] for m in meshes)
        for m in meshes:
            dp = shd.parse_mesh_name(m)[0]
            if shards % dp != 0:
                raise ValueError(
                    f"warmed mesh {m!r}: dp={dp} must divide the pool "
                    f"shard count {shards} (the widest warmed dp) so all "
                    f"topologies share one physical page layout."
                )
        return shards

    @property
    def pool_physical_pages(self) -> int:
        """Device page-axis extent: allocatable pages plus one null page
        per shard. ``shards == 1`` reproduces the classic
        ``pool_pages + 1`` layout exactly."""
        shards = self.pool_shards
        if self.pool_pages % shards:
            raise ValueError(
                f"num_pages={self.pool_pages} must divide evenly across "
                f"{shards} pool shards; pad EngineConfig.num_pages to a "
                f"multiple."
            )
        return self.pool_pages + shards

    # ----------------------------------------------- registry axis ladders
    # Each method below is a ``core.lanes.LaneAxis`` bucket ladder: the
    # registry's ``fanout`` calls it by name to enumerate one coordinate's
    # warmup values (DESIGN.md §12). Adding a coordinate = one LaneAxis in
    # the relevant specs + one ladder method here.
    def _chunk_buckets(self) -> list[int]:
        """The log-sized chunk-bucket fan-out {8, 16, ..., prefill_chunk}."""
        if self.ecfg.prefill_chunk <= 0:
            return []
        out, b = [], CHUNK_BUCKET_MIN
        while True:
            b = min(b, self.ecfg.prefill_chunk)
            out.append(b)
            if b >= self.ecfg.prefill_chunk:
                return out
            b *= 2

    def _row_buckets(self, slots: int) -> list[int]:
        """The row-bucket fan-out {1, 2, 4, ...} below ``slots``: the row
        counts a ``("pfr", ...)`` call may carry (DESIGN.md §10)."""
        out, r = [], 1
        while r < slots:
            out.append(r)
            r *= 2
        return out

    def _k_buckets(self) -> list[int]:
        """The log-sized k-bucket fan-out {1, 2, 4, ..., spec_k}."""
        if self.ecfg.spec_k <= 0:
            return []
        out, b = [], 1
        while True:
            b = min(b, self.ecfg.spec_k)
            out.append(b)
            if b >= self.ecfg.spec_k:
                return out
            b *= 2

    def _pages_buckets(self) -> list[int]:
        """The log-sized capacity-bucket fan-out {1, 2, ..., page cap}."""
        out, pb = [], 1
        while True:
            out.append(pb)
            if pb >= self.max_pages_per_req:
                return out
            pb = min(pb * 2, self.max_pages_per_req)

    def _warm_kv_dtypes(self) -> tuple[str, ...]:
        """The kv_dtype axis ladder (DESIGN.md §12): the active pool dtype
        plus any extra dtypes the config asks to keep warm, deduped — a
        pool flip across this set is a rebind, never a compile."""
        return tuple(
            dict.fromkeys((self.ecfg.kv_dtype,) + tuple(self.ecfg.kv_dtypes))
        )

    def _warm_draft_kv_dtypes(self) -> tuple[str, ...]:
        """The draft lanes' storage-dtype ladder (DESIGN.md §16): an int8
        draft cache pairs a cheap quantised drafter with a full-precision
        verify lane; extras keep a precision flip a rebind, never a
        compile."""
        return tuple(
            dict.fromkeys(
                (self.ecfg.draft_kv_dtype,)
                + tuple(self.ecfg.draft_kv_dtypes)
            )
        )

    def _warm_meshes(self) -> tuple[str, ...]:
        """The mesh-axis ladder (DESIGN.md §16): the active topology plus
        every standby shape to AOT-warm, canonicalised and deduped — a
        crossing inside this set (scale-out ``1x1 -> 2x2`` or a failover
        shrink ``2x2 -> 1x2``) flips warmed hot slots and ``device_put``s
        the live cache, never compiles."""
        names = (self.ecfg.mesh,) + tuple(self.ecfg.meshes)
        # parse_slice_name keeps "@OFF" slices (DESIGN.md §17) distinct
        # from their offset-0 twins in the ladder.
        return tuple(
            dict.fromkeys(
                shd.mesh_name(*shd.parse_slice_name(n)) for n in names
            )
        )

    # ------------------------------------------------- lane enable gates
    def _supports_chunked_prefill(self, ctx: Any = None) -> bool:
        """Chunked prefill is attention-only: SSM slots carry recurrent
        state and would need a per-chunk scan (ROADMAP open item)."""
        return self.ecfg.prefill_chunk > 0 and all(
            self.cfg.mixer_at(slot).startswith("attn")
            for slot in range(self.cfg.period)
        )

    def _supports_spec_decode(self) -> bool:
        """The verify lane rides the chunked scatter paths, so speculation
        shares chunked prefill's attention-only constraint."""
        return self.ecfg.spec_k > 0 and all(
            self.cfg.mixer_at(slot).startswith("attn")
            for slot in range(self.cfg.period)
        )

    def _spec_lanes_enabled(self, ctx: "_WarmCtx") -> bool:
        """Registry gate for the draft/verify lanes: per-batcher opt-in
        (``spec_decode=`` override) AND architectural support."""
        return bool(ctx.spec) and self._supports_spec_decode()

    def _row_prefill_enabled(self, ctx: "_WarmCtx") -> bool:
        """Registry gate for the row-bucketed prefill (DESIGN.md §10): off
        where a prefill row has to stay on its slot's row — with spec lanes
        on, the draft mirror reuses the call's per-slot windows against
        its dense per-slot cache; on a sharded pool, a row's padding must
        hit its own shard's null page."""
        return (
            self._supports_chunked_prefill()
            and not self._spec_lanes_enabled(ctx)
            and self.pool_shards == 1
        )

    def _disagg_lanes_enabled(self, ctx: "_WarmCtx") -> bool:
        """Registry gate for the KV-migration lane (DESIGN.md §17): only a
        batcher that opted into disaggregated prefill/decode pays for the
        gather/scatter transport cells (absent opt-in means disabled)."""
        return bool(getattr(ctx, "disagg", False))

    def _mg_ops(self) -> tuple[str, ...]:
        """The migration lane's op ladder: the export gather and the import
        scatter halves of the KV-page transport (DESIGN.md §17)."""
        return ("gather", "scatter")

    # ----------------------------------------------------- registry warmup
    # One warm method per LaneSpec (the spec's ``warmer`` hook): dummy-run
    # the freshly built executable through the *exact* runtime path (paper
    # §4.3 — converts, device reshapes, D2H pulls included) so the first
    # real dispatch pays neither compile nor program load, threading the
    # donated caches through the ctx. Warm inputs use length 0 / inactive
    # slots / null tables everywhere: no live cache row is written (paged
    # garbage lands in the reserved null page).
    def _warm_zeros(self, *shape: int) -> jax.Array:
        return jnp.asarray(np.zeros(shape, np.int32))

    def _warm_sampling(self, s: int) -> tuple:
        return (
            jnp.asarray(np.ones(s, np.float32)),
            jnp.asarray(np.ones(s, bool)),
            jnp.asarray(np.zeros((s, 2), np.uint32)),
        )

    def _draft_warm_cache(
        self, ctx: _WarmCtx, s: int, dt: str, m: str
    ) -> Any:
        """Lazily create the ``(draft_kv_dtype, mesh)`` draft warm cache —
        draft lanes only warm when the spec gate is on, so creation rides
        the first draft-lane warm instead of every warmup."""
        if ctx.draft_caches is None:
            ctx.draft_caches = {}
        cell = (dt, m)
        if cell not in ctx.draft_caches:
            ctx.draft_caches[cell] = models.init_cache(
                self.draft_cfg, s, self.ecfg.max_len, dt
            )
        return ctx.draft_caches[cell]

    def _warm_cb(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, m = key
        warm = exe(
            self.params,
            ctx.dense_caches[m],
            self._warm_zeros(s, 1),
            self._warm_zeros(s),
            jnp.asarray(np.zeros(s, bool)),
            *self._warm_sampling(s),
        )
        jax.block_until_ready(warm)
        nxt, ctx.dense_caches[m], pos, keys = warm[:4]
        _ = nxt[:, None]  # the sync loop's device-side tok reshape
        np.asarray(warm[5])  # the async loop's packed bundle pull
        np.asarray(nxt), np.array(pos, np.int32), np.array(keys, np.uint32)

    def _warm_cbp(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, pb, dt, m = key
        warm = exe(
            self.params,
            ctx.paged_caches[(dt, m)],
            self._warm_zeros(s, 1),
            self._warm_zeros(s),
            self._warm_zeros(s, pb),
            jnp.asarray(np.zeros(s, bool)),
            *self._warm_sampling(s),
        )
        jax.block_until_ready(warm)
        nxt, ctx.paged_caches[(dt, m)], pos, keys = warm[:4]
        _ = nxt[:, None]  # the sync loop's device-side tok reshape
        np.asarray(warm[5])  # the async loop's packed bundle pull
        np.asarray(nxt), np.array(pos, np.int32), np.array(keys, np.uint32)

    def _warm_pf(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, cb, dt, m = key
        warm = exe(
            self.params,
            ctx.paged_caches[(dt, m)],
            self._warm_zeros(s, cb),
            self._warm_zeros(s),
            self._warm_zeros(s, self.max_pages_per_req),
            self._warm_zeros(s),
            *self._warm_sampling(s),
        )
        jax.block_until_ready(warm)
        np.asarray(warm[0]), np.asarray(warm[2])
        # the real loop pulls through the packed-d2h helper; its jit cache
        # keys on input *placement*, so warm the pack against this mesh
        # cell's outputs too (an offset slice is a distinct variant — §17)
        np.asarray(steps_mod.pack_step_d2h(warm[0], warm[2]))
        ctx.paged_caches[(dt, m)] = warm[1]

    def _warm_pfr(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, r, dt, m = key
        warm = exe(
            self.params,
            ctx.paged_caches[(dt, m)],
            self._warm_zeros(r, self.ecfg.prefill_chunk),
            self._warm_zeros(s),
            self._warm_zeros(r, self.max_pages_per_req),
            self._warm_zeros(r),
            *self._warm_sampling(s),
            jnp.asarray(np.full(r, s, np.int32)),  # pad rows: all dropped
        )
        jax.block_until_ready(warm)
        np.asarray(steps_mod.pack_step_d2h(warm[0], warm[2]))
        ctx.paged_caches[(dt, m)] = warm[1]

    def _warm_pfd(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, cb, m = key
        warm = exe(
            self.params,
            ctx.dense_caches[m],
            self._warm_zeros(s, cb),
            self._warm_zeros(s),
            self._warm_zeros(s),
            *self._warm_sampling(s),
        )
        jax.block_until_ready(warm)
        np.asarray(warm[0]), np.asarray(warm[2])
        ctx.dense_caches[m] = warm[1]

    def _warm_vf(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, k, dt, m = key
        warm = exe(
            self.params,
            ctx.paged_caches[(dt, m)],
            self._warm_zeros(s, k + 1),
            self._warm_zeros(s),
            self._warm_zeros(s, self.max_pages_per_req),
            self._warm_zeros(s),
            *self._warm_sampling(s),
        )
        jax.block_until_ready(warm)
        np.asarray(warm[0]), np.asarray(warm[1])
        ctx.paged_caches[(dt, m)] = warm[2]

    def _warm_vfd(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, k, m = key
        warm = exe(
            self.params,
            ctx.dense_caches[m],
            self._warm_zeros(s, k + 1),
            self._warm_zeros(s),
            self._warm_zeros(s),
            *self._warm_sampling(s),
        )
        jax.block_until_ready(warm)
        np.asarray(warm[0]), np.asarray(warm[1])
        ctx.dense_caches[m] = warm[2]

    def _warm_dr(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, k, dt, m = key
        dcache = self._draft_warm_cache(ctx, s, dt, m)
        warm = exe(
            self.draft_params,
            dcache,
            self._warm_zeros(s, 1),
            self._warm_zeros(s),
            jnp.asarray(np.zeros(s, bool)),
            *self._warm_sampling(s),
        )
        jax.block_until_ready(warm)
        np.asarray(warm[0])
        ctx.draft_caches[(dt, m)] = warm[1]

    def _warm_drp(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        _, s, cb, dt, m = key
        dcache = self._draft_warm_cache(ctx, s, dt, m)
        warm = exe(
            self.draft_params,
            dcache,
            self._warm_zeros(s, cb),
            self._warm_zeros(s),
            self._warm_zeros(s),
            *self._warm_sampling(s),
        )
        jax.block_until_ready(warm)
        ctx.draft_caches[(dt, m)] = warm[1]

    def _warm_mg(self, key: tuple, exe: Callable, ctx: _WarmCtx) -> None:
        """Warm one migration-transport half (DESIGN.md §17) against its
        (dtype, mesh) cell's live cache. The idx rows all point at the
        shard-0 null page, so the scatter's donated write lands in reserved
        garbage space and no live page is touched."""
        _, op, dt, m = key
        idx = jnp.asarray(np.zeros(self.max_pages_per_req, np.int32))
        cache = ctx.paged_caches[(dt, m)]
        if op == "gather":
            jax.block_until_ready(exe(cache, idx))
            return
        blk = jax.tree.map(
            lambda x: jnp.zeros(
                (x.shape[0], self.max_pages_per_req) + x.shape[2:], x.dtype
            ),
            cache,
        )
        warm = exe(cache, blk, idx)
        jax.block_until_ready(warm)
        ctx.paged_caches[(dt, m)] = warm

    def _warm_lanes(
        self,
        kind: str,
        slots: int,
        ctx: _WarmCtx,
        pins: dict | None = None,
    ) -> None:
        """Registry-driven warmup (DESIGN.md §12): iterate every LaneSpec
        the ``kind`` engine warms, skip gated-off lanes, and for each key
        in the spec's ``fanout`` compile *and* dummy-run the executable.
        This single loop replaces the seven hand-edited per-lane warm
        blocks; adding a coordinate never touches it. ``pins`` holds axes
        down to one value (``warm_all_buckets=False``, active-dtype-only
        warms)."""
        pins = dict(pins or {})
        pins["slots"] = slots
        for spec in LANES.for_engine(kind):
            if spec.enabled is not None and not getattr(self, spec.enabled)(
                ctx
            ):
                continue
            lane_pins = {
                name: v for name, v in pins.items() if name in spec.axis_names
            }
            for key in spec.fanout(self, **lane_pins):
                exe = self._decode.build(key)
                if spec.warmer is not None:
                    getattr(self, spec.warmer)(key, exe, ctx)

    def mark_warm_boundary(self) -> None:
        """Warmup/steady-state separation (DESIGN.md §14): snapshot the
        dispatcher's compile/rebind counters and roll the metrics registry
        into its ``"warmup"`` section, so every post-warmup gate
        (``compiles_after_warmup == 0``, steady-state latency histograms)
        reads clean numbers by construction rather than by subtraction at
        each call site."""
        st = self._decode.stats
        self._warm_marks = {"compiles": st.misses, "rebinds": st.rebinds}
        self.telemetry.registry.rollover("warmup")
        rec = self.telemetry.trace_or_none()
        if rec is not None:
            rec.emit(
                "warm_boundary",
                "dispatcher",
                args={"compiles": st.misses, "rebinds": st.rebinds},
            )

    @property
    def post_warmup_compiles(self) -> int:
        """Dispatcher compiles since the last ``mark_warm_boundary`` (all
        compiles ever, if no boundary was marked)."""
        base = (self._warm_marks or {}).get("compiles", 0)
        return self._decode.stats.misses - base

    @property
    def post_warmup_rebinds(self) -> int:
        base = (self._warm_marks or {}).get("rebinds", 0)
        return self._decode.stats.rebinds - base

    def attach_faults(self, plan) -> None:
        """Arm a ``core.faults.FaultPlan`` at the engine's ``build`` site
        (the dispatcher's cold path). Batcher- and pool-side sites are
        armed on those objects directly."""
        self._decode.attach_faults(plan)

    def _warm_d2h_packs(self, slots: int) -> None:
        """Warm the packed-d2h helpers (``steps.pack_step_d2h`` /
        ``pack_verify_d2h``) for this slot bucket: they are plain ``jax.jit``
        functions outside the Dispatcher's key space, but their first trace
        must land in warmup so the serving loop never compiles mid-stream —
        async mode adds zero new dispatch keys (DESIGN.md §13)."""
        s = slots
        nxt = jnp.zeros((s,), jnp.int32)
        keys = jnp.zeros((s, 2), jnp.uint32)
        np.asarray(steps_mod.pack_step_d2h(nxt, keys))
        for k in self._k_buckets():
            rows = jnp.zeros((s, k + 1), jnp.int32)
            np.asarray(steps_mod.pack_verify_d2h(rows, nxt, keys))

    def _spec_dispatchers(
        self,
        slots: int,
        cache_is_paged: bool,
        kv_dtype: str = "fp32",
        draft_kv_dtype: str = "fp32",
        mesh_bind: dict | None = None,
    ) -> tuple[Callable, Callable, Callable]:
        """The speculative lanes' dispatch closures (DESIGN.md §11); the
        executables themselves were AOT-warmed by ``_warm_lanes``. The
        paged verify closure pins the batcher's ``kv_dtype`` coordinate;
        the draft lanes pin ``draft_kv_dtype``; every closure reads the
        batcher's live mesh binding, so a topology flip re-routes the spec
        lanes on their next dispatch with zero extra plumbing."""
        s = slots
        ddt = draft_kv_dtype
        mb = mesh_bind if mesh_bind is not None else {"mesh": "1x1"}

        def draft_dispatch(k: int) -> Callable:
            exe = self._decode.dispatch(
                lanes_mod.DR.key(s, k, ddt, mb["mesh"])
            )

            def bound_draft(dcache, tok, pos, active, temps, greedy, keys):
                self.stats["hot_calls"] += 1
                return exe(
                    self.draft_params, dcache, tok, pos, active, temps,
                    greedy, keys,
                )

            return bound_draft

        def draft_prefill_dispatch(chunk_bucket: int) -> Callable:
            # DRP is a prefill-group lane (LaneSpec.slice == "prefill"):
            # under disaggregation it routes to the prefill slice binding;
            # with no split configured it falls back to the shared mesh.
            drp_mesh = mb.get("prefill", mb["mesh"])
            exe = self._decode.dispatch(
                lanes_mod.DRP.key(s, chunk_bucket, ddt, drp_mesh)
            )
            drp_params = self._params_for_mesh(drp_mesh, draft=True)

            def bound_drp(dcache, tok, start, length, temps, greedy, keys):
                self.stats["hot_calls"] += 1
                return exe(
                    drp_params, dcache, tok, start, length, temps,
                    greedy, keys,
                )

            return bound_drp

        if cache_is_paged:

            def verify_dispatch(k: int) -> Callable:
                exe = self._decode.dispatch(
                    lanes_mod.VF.key(s, k, kv_dtype, mb["mesh"])
                )

                def bound_verify(
                    cache, tok, start, bt, length, temps, greedy, keys
                ):
                    self.stats["hot_calls"] += 1
                    return exe(
                        self.params, cache, tok, start, bt, length, temps,
                        greedy, keys,
                    )

                return bound_verify

        else:

            def verify_dispatch(k: int) -> Callable:
                exe = self._decode.dispatch(
                    lanes_mod.VFD.key(s, k, mb["mesh"])
                )

                def bound_verify(
                    cache, tok, start, length, temps, greedy, keys
                ):
                    self.stats["hot_calls"] += 1
                    return exe(
                        self.params, cache, tok, start, length, temps,
                        greedy, keys,
                    )

                return bound_verify

        return draft_dispatch, verify_dispatch, draft_prefill_dispatch

    def _make_mesh_ctl(
        self, mesh_bind: dict, cache_kind: str, hot_key: Callable
    ) -> Callable:
        """Build the batcher's topology-flip closure (DESIGN.md §16).

        ``mesh_ctl(name, cache, draft_cache, **hot)`` validates the target
        against the warmed ladder, ``device_put``s the live caches onto
        the new plan (pure data movement), mutates the shared mesh binding
        (so every dispatch closure routes to the new coordinate), and
        force-flips the decode hot slot via ``set_direction`` — the
        paper's patched-jmp move, a rebind and never a compile.
        ``hot_key(**hot)`` maps the batcher's current bucket state to the
        decode lane key under the *new* binding.
        """
        warm = self._warm_meshes()

        def mesh_ctl(name: str, cache: Any, draft_cache: Any, **hot: Any):
            if "prefill" in mesh_bind:
                raise ValueError(
                    "cannot rebind the decode mesh while disaggregated "
                    "prefill/decode is configured (DESIGN.md §17): the "
                    "decode slice anchors the page pool; use set_disagg "
                    "to split/collapse instead."
                )
            nm = shd.mesh_name(*shd.parse_slice_name(name))
            if nm not in warm:
                raise ValueError(
                    f"mesh {nm!r} is not in the warmed set {warm}; add it "
                    f"to EngineConfig.mesh/meshes so its lanes are AOT-"
                    f"warmed (a cold topology would compile mid-stream)."
                )
            if nm != mesh_bind["mesh"]:
                cache = self._reshard_cache(cache, nm, cache_kind)
                if draft_cache is not None:
                    draft_cache = self._reshard_cache(
                        draft_cache, nm, "dense"
                    )
                mesh_bind["mesh"] = nm
                self._decode.set_direction(hot_key(**hot))
                self.telemetry.registry.inc("mesh_rebinds_total")
                rec = self.telemetry.trace_or_none()
                if rec is not None:
                    rec.emit("mesh_rebind", "dispatcher", args={"mesh": nm})
            return nm, cache, draft_cache

        return mesh_ctl

    def set_mode(
        self, *, batch: int, sampling: int = GREEDY, warm: bool = True
    ) -> dict:
        """Cold path: bucket, compile-or-fetch, rebind the slot, warm."""
        t0 = time.perf_counter()
        bucket = bucket_multiple(
            batch, self.ecfg.batch_quantum, self.ecfg.max_batch
        )
        key = lanes_mod.BURST.key(bucket, sampling)
        exe = self._decode.dispatch(key)
        self._current = exe  # <- the jmp patch (engine-side mirror)
        self._current_key = key
        if warm:  # dummy-order warming (paper §4.3)
            cache = models.init_cache(self.cfg, bucket, self.ecfg.max_len)
            if self.cfg.input_kind == "tokens":
                tok = jnp.zeros((bucket, 1), jnp.int32)
            else:
                tok = jnp.zeros(
                    (bucket, 1, self.cfg.d_model), jnp.dtype(self.cfg.dtype)
                )
            out = exe(
                self.params, cache, tok, jnp.int32(0),
                jnp.zeros((2,), jnp.uint32),
            )
            jax.block_until_ready(out)
        self.stats["mode_switches"] += 1
        self.telemetry.registry.inc("mode_switches_total")
        return {
            "bucket": bucket,
            "key": key,
            "switch_s": time.perf_counter() - t0,
            "compiles": self._decode.stats.misses,
        }

    # ------------------------------------------------------------- hot path
    def decode_loop(
        self,
        cache: Any,
        first_token: jax.Array,
        start_pos: int,
        num_tokens: int,
        rng: jax.Array | None = None,
        on_step: Callable[[int, jax.Array], None] | None = None,
    ) -> tuple[np.ndarray, Any]:
        """The latency-critical loop: direct executable calls only.

        ``on_step(i, tok)`` (optional) observes each step's device output
        as it is issued — e.g. to timestamp the first token without
        serialising the rest of the loop."""
        exe = self._current
        assert exe is not None, "set_mode() before decode_loop() (cold path)"
        batch = int(first_token.shape[0])
        if num_tokens <= 0:
            return np.zeros((batch, 0), np.int32), cache
        if self.cfg.input_kind != "tokens" and num_tokens > 1:
            raise ValueError(
                f"{self.cfg.name} has a stub modality frontend (no token "
                f"embedding table): sampled ids cannot be fed back as "
                f"embeddings, so decode_loop supports num_tokens=1 only."
            )
        tok = first_token
        base_key = rng if rng is not None else jnp.zeros((2,), jnp.uint32)
        # One key per step, derived in the prologue: reusing a single key
        # across steps would correlate every sampled token in the burst.
        step_keys = jax.random.split(base_key, num_tokens)
        # Burst/continuous report parity (DESIGN.md §14): burst steps feed
        # the same registry families the batcher lanes do, under the
        # "burst" lane label. Handles are cached; the loop pays one counter
        # add, one histogram bisect, and a phase span's check per step.
        if self._burst_calls is None:
            reg = self.telemetry.registry
            self._burst_calls = reg.counter("lane_calls_total", lane="burst")
            self._burst_hist = reg.histogram("lane_step_ms", lane="burst")
        out = []
        pos = start_pos
        for i in range(num_tokens):
            # tokens arrive as [B,1]; stub-frontend embeddings as [B,D] and
            # need the singleton seq axis the model expects ([B,1,D]).
            tok2d = tok if self.cfg.input_kind == "tokens" else tok[:, None, :]
            t0_ns = time.perf_counter_ns()
            with self.telemetry.span(
                "serve.dispatch.issue", lane="burst", step=i
            ):
                tok, cache = exe(
                    self.params, cache, tok2d, jnp.int32(pos), step_keys[i]
                )
            dt_ns = time.perf_counter_ns() - t0_ns
            self._burst_calls.inc()
            self._burst_hist.observe(dt_ns / 1e6)
            out.append(tok)
            if on_step is not None:
                on_step(i, tok)
            tok = tok[:, None] if self.cfg.input_kind == "tokens" else tok
            pos += 1
            self.stats["hot_calls"] += 1
        self.stats["tokens"] += num_tokens * batch
        return np.stack([np.asarray(t) for t in out], axis=1), cache

    # -------------------------------------------------- continuous batching
    def continuous(
        self,
        *,
        slots: int | None = None,
        seed: int = 0,
        spec_decode: bool | None = None,
        async_steps: bool = False,
        async_depth: int = 2,
        mesh: str | None = None,
        draft_kv_dtype: str | None = None,
    ) -> ContinuousBatcher:
        """Cold path: build+warm every lane/bucket executable, return a
        batcher.

        This is the only compile the continuous path ever pays for a given
        bucket size; afterwards joins, leaves, greedy/sample flips, chunk
        sizes, and draft depths are pure hot-loop data or warmed rebinds.
        ``spec_decode`` overrides the engine config (None = on iff
        ``spec_k > 0``). ``async_steps`` turns on the software-pipelined
        step loop (DESIGN.md §13) — same lanes, same dispatch keys, same
        warmup; only the host's read schedule changes. ``async_depth``
        caps the in-flight pipeline (2 = classic one-ahead).
        """
        if self.cfg.input_kind != "tokens":
            raise ValueError(
                f"{self.cfg.name}: continuous batching feeds sampled ids "
                f"back as inputs and needs a token-input arch."
            )
        s = slots or self.ecfg.max_batch
        use_spec = (
            self.ecfg.spec_k > 0 if spec_decode is None else spec_decode
        )
        warm_meshes = self._warm_meshes()
        m0 = shd.mesh_name(*shd.parse_slice_name(mesh or self.ecfg.mesh))
        if m0 not in warm_meshes:
            raise ValueError(
                f"mesh={m0!r} is not in the warmed set {warm_meshes}; add "
                f"it to EngineConfig.mesh/meshes."
            )
        ddt = draft_kv_dtype or self.ecfg.draft_kv_dtype
        if ddt not in self._warm_draft_kv_dtypes():
            raise ValueError(
                f"draft_kv_dtype={ddt!r} is not in the warmed set "
                f"{self._warm_draft_kv_dtypes()}; add it to EngineConfig."
                f"draft_kv_dtype/draft_kv_dtypes."
            )
        # Registry-driven warmup (DESIGN.md §12): every enabled dense lane
        # (cb, pfd, vfd, dr, drp), every bucket in its fan-out, every
        # warmed mesh — compiled *and* dummy-run, one loop instead of
        # per-lane warm blocks. Each mesh warms against its own cache (a
        # donated cache comes back committed to its plan's sharding); the
        # batcher adopts the active mesh's cache.
        ctx = _WarmCtx(
            spec=use_spec,
            dense_caches={
                m: models.init_cache(self.cfg, s, self.ecfg.max_len)
                for m in warm_meshes
            },
        )
        self._warm_lanes("dense", s, ctx)
        self._warm_d2h_packs(s)
        mb = {"mesh": m0}
        cache = ctx.dense_caches[m0]

        def step_dispatch() -> Callable:
            exe = self._decode.dispatch(lanes_mod.CB.key(s, mb["mesh"]))

            def bound_step(cache, tok, pos, active, temps, greedy, keys):
                self.stats["hot_calls"] += 1
                return exe(
                    self.params, cache, tok, pos, active, temps, greedy,
                    keys,
                )

            return bound_step

        prefill_dispatch = None
        if self._supports_chunked_prefill():

            def prefill_dispatch(chunk_bucket: int) -> Callable:
                pf = self._decode.dispatch(
                    lanes_mod.PFD.key(s, chunk_bucket, mb["mesh"])
                )

                def bound_prefill(cache, tok, start, length, temps, greedy, keys):
                    self.stats["hot_calls"] += 1
                    return pf(
                        self.params, cache, tok, start, length, temps,
                        greedy, keys,
                    )

                return bound_prefill

        draft_dispatch = verify_dispatch = draft_prefill_dispatch = None
        if use_spec and self._supports_spec_decode():
            (
                draft_dispatch, verify_dispatch, draft_prefill_dispatch,
            ) = self._spec_dispatchers(
                s, cache_is_paged=False, draft_kv_dtype=ddt, mesh_bind=mb
            )

        mesh_ctl = self._make_mesh_ctl(
            mb, "dense", lambda: lanes_mod.CB.key(s, mb["mesh"])
        )
        bound_step = step_dispatch()  # pre-bind the hot slot before the
        # boundary so the first real step is a pure slot hit

        # Warmup is complete: everything from here on is steady state
        # (DESIGN.md §14). The batcher's registry handles are created after
        # the rollover, so its counters start from zero by construction.
        self.mark_warm_boundary()
        return ContinuousBatcher(
            step=bound_step,
            num_slots=s,
            max_len=self.ecfg.max_len,
            cache=cache,
            seed=seed,
            prefill_dispatch=prefill_dispatch,
            prefill_chunk=self.ecfg.prefill_chunk,
            token_budget=self.ecfg.token_budget,
            draft_dispatch=draft_dispatch,
            verify_dispatch=verify_dispatch,
            draft_prefill_dispatch=draft_prefill_dispatch,
            draft_cache=(
                ctx.draft_caches.get((ddt, m0))
                if ctx.draft_caches
                else None
            ),
            spec_k=self.ecfg.spec_k,
            async_steps=async_steps,
            async_depth=async_depth,
            telemetry=self.telemetry,
            mesh=m0,
            mesh_ctl=mesh_ctl,
            step_dispatch=step_dispatch,
        )


    # ---------------------------------------------- paged continuous batching
    def paged_continuous(
        self,
        *,
        slots: int | None = None,
        seed: int = 0,
        warm_all_buckets: bool = True,
        spec_decode: bool | None = None,
        kv_dtype: str | None = None,
        async_steps: bool = False,
        async_depth: int = 2,
        mesh: str | None = None,
        draft_kv_dtype: str | None = None,
        disagg: "bool | str | shd.DisaggPlan | None" = None,
    ) -> PagedContinuousBatcher:
        """Cold path: build the page pool + prefix cache and warm every
        paged lane through the registry; returns a paged batcher
        (DESIGN.md §9/§12).

        The decode key is ``("cbp", slots, pages_bucket, kv_dtype)``: one
        executable per capacity bucket *per page dtype*, found/rebound by
        the hysteresis policy as requests grow. The pooled page cache
        itself is bucket-independent — a rebind swaps the executable,
        never the cache.

        ``warm_all_buckets`` precompiles every bucket in every enabled
        lane's registry fan-out — including the full ``kv_dtype`` axis
        (``EngineConfig.kv_dtype`` + ``kv_dtypes``) — so bucket crossings
        *and* pool-dtype flips are pure rebinds with zero compiles; the
        opt-out pins the fan-out to the smallest capacity bucket and the
        active dtype. ``kv_dtype`` overrides the config's active pool
        dtype for this batcher; it must be inside the warmed set.

        ``disagg`` opts into disaggregated prefill/decode (DESIGN.md §17):
        a prefill slice name (``"1x1@1"``), a full ``shd.DisaggPlan`` whose
        decode slice must equal the active mesh, or ``True`` for the
        canonical slice on the devices right after the decode slice's.
        Both slices must sit in the warmed mesh ladder; the prefill lanes
        then pin to the prefill slice and a ``set_disagg`` crossing is a
        rebind, never a compile. ``async_depth`` caps the in-flight async
        pipeline (2 = classic one-ahead).
        """
        from repro.runtime.kvcache import PagePool, PrefixCache

        if self.cfg.input_kind != "tokens":
            raise ValueError(
                f"{self.cfg.name}: continuous batching feeds sampled ids "
                f"back as inputs and needs a token-input arch."
            )
        s = slots or self.ecfg.max_batch
        ecfg = self.ecfg
        dt = kv_dtype or ecfg.kv_dtype
        warm_dtypes = self._warm_kv_dtypes()
        if dt not in warm_dtypes:
            raise ValueError(
                f"kv_dtype={dt!r} is not in the warmed set {warm_dtypes}; "
                f"add it to EngineConfig.kv_dtype/kv_dtypes so its lanes "
                f"are AOT-warmed (a cold pool dtype would compile mid-"
                f"stream)."
            )
        use_spec = (
            self.ecfg.spec_k > 0 if spec_decode is None else spec_decode
        )
        warm_meshes = self._warm_meshes()
        m0 = shd.mesh_name(*shd.parse_slice_name(mesh or ecfg.mesh))
        if m0 not in warm_meshes:
            raise ValueError(
                f"mesh={m0!r} is not in the warmed set {warm_meshes}; add "
                f"it to EngineConfig.mesh/meshes."
            )
        # Disaggregated prefill/decode placement (DESIGN.md §17): resolve
        # the two pinned slices up front so every lane×slice cell warms.
        dg: shd.DisaggPlan | None = None
        if disagg:
            if isinstance(disagg, shd.DisaggPlan):
                dg = disagg
            elif isinstance(disagg, str):
                dg = shd.DisaggPlan(prefill=disagg, decode=m0)
            else:  # True: the devices right after the decode slice's
                dp, mp, off = shd.parse_slice_name(m0)
                dg = shd.DisaggPlan(
                    prefill=shd.mesh_name(1, mp, off + dp * mp), decode=m0
                )
            if dg.decode != m0:
                raise ValueError(
                    f"DisaggPlan.decode={dg.decode!r} must equal the active "
                    f"mesh {m0!r}: the decode slice anchors the page pool "
                    f"and the batcher's cache binding."
                )
            if dg.prefill not in warm_meshes:
                raise ValueError(
                    f"disagg prefill slice {dg.prefill!r} is not in the "
                    f"warmed set {warm_meshes}; add it to EngineConfig."
                    f"meshes so its lanes are AOT-warmed."
                )
            if use_spec and self._supports_spec_decode():
                raise ValueError(
                    "disaggregated prefill/decode does not compose with "
                    "speculative decoding yet: the draft cache is dense "
                    "(no page migration path); pass spec_decode=False."
                )
            if not self._supports_chunked_prefill():
                raise ValueError(
                    "disaggregated prefill/decode needs the chunked "
                    "prefill lane (EngineConfig.prefill_chunk > 0): "
                    "without it prompts teacher-force through the decode "
                    "lane and there is nothing to pin to a prefill slice."
                )
        ddt = draft_kv_dtype or ecfg.draft_kv_dtype
        if ddt not in self._warm_draft_kv_dtypes():
            raise ValueError(
                f"draft_kv_dtype={ddt!r} is not in the warmed set "
                f"{self._warm_draft_kv_dtypes()}; add it to EngineConfig."
                f"draft_kv_dtype/draft_kv_dtypes."
            )
        pool = PagePool(
            self.pool_pages, ecfg.page_size, kv_dtype=dt,
            telemetry=self.telemetry, shards=self.pool_shards,
        )
        prefix = PrefixCache(pool)
        # The prefill slice gets its own pool with identical geometry
        # (DESIGN.md §17): same shard layout means the two caches share
        # null-page ids, so migration idx padding is pool-agnostic. The
        # decode pool stays the id authority — the trie roots there and
        # every finished request's pages end up there.
        pf_pool = (
            PagePool(
                self.pool_pages, ecfg.page_size, kv_dtype=dt,
                telemetry=self.telemetry, shards=self.pool_shards,
            )
            if dg is not None
            else None
        )
        max_pages_per_req = self.max_pages_per_req
        # Registry-driven warmup (DESIGN.md §12): every enabled paged lane
        # (cbp, pf, pfr, vf, dr, drp), every bucket in its fan-out, every warmed
        # page dtype *and mesh* — compiled and dummy-run against a pooled
        # cache of the matching (dtype, mesh) cell. The batcher adopts the
        # active cell's cache; the rest existed only to warm executables.
        ctx = _WarmCtx(
            spec=use_spec,
            disagg=dg is not None,
            paged_caches={
                (d, m): models.init_paged_cache(
                    self.cfg, self.pool_physical_pages, ecfg.page_size, d
                )
                for d in warm_dtypes
                for m in warm_meshes
            },
        )
        pins = {} if warm_all_buckets else {
            "pages_bucket": 1, "kv_dtype": dt, "draft_kv_dtype": ddt,
        }
        self._warm_lanes("paged", s, ctx, pins=pins)
        self._warm_d2h_packs(s)
        # The shared mesh binding: "mesh" routes the decode-group lanes
        # and never changes while disaggregated; "prefill" (present only
        # when a DisaggPlan is configured) routes the prefill-group lanes
        # and flips between the prefill slice and the decode mesh — the
        # set_disagg rebind (DESIGN.md §17).
        mb = {"mesh": m0}
        if dg is not None:
            mb["prefill"] = dg.prefill
        cache = ctx.paged_caches[(dt, m0)]

        def dispatch(pages_bucket: int) -> Callable:
            exe = self._decode.dispatch(
                lanes_mod.CBP.key(s, pages_bucket, dt, mb["mesh"])
            )

            def bound_step(cache, tok, pos, bt, active, temps, greedy, keys):
                self.stats["hot_calls"] += 1
                return exe(
                    self.params, cache, tok, pos, bt, active, temps, greedy,
                    keys,
                )

            return bound_step

        prefill_dispatch = None
        if self._supports_chunked_prefill():

            def prefill_dispatch(chunk_bucket: int) -> Callable:
                # PF is a prefill-group lane (LaneSpec.slice): under a
                # live split it routes to the prefill slice binding.
                pf_mesh = mb.get("prefill", mb["mesh"])
                pf = self._decode.dispatch(
                    lanes_mod.PF.key(s, chunk_bucket, dt, pf_mesh)
                )
                pf_params = self._params_for_mesh(pf_mesh)

                def bound_prefill(
                    cache, tok, start, bt, length, temps, greedy, keys
                ):
                    self.stats["hot_calls"] += 1
                    return pf(
                        pf_params, cache, tok, start, bt, length, temps,
                        greedy, keys,
                    )

                return bound_prefill

        prefill_rows_dispatch = None
        if self._row_prefill_enabled(ctx) and self._row_buckets(s):

            def prefill_rows_dispatch(rows: int) -> Callable:
                # the same prefill-group routing as PF above
                pf_mesh = mb.get("prefill", mb["mesh"])
                pf = self._decode.dispatch(
                    lanes_mod.PFR.key(s, rows, dt, pf_mesh)
                )
                pf_params = self._params_for_mesh(pf_mesh)

                def bound_prefill_rows(
                    cache, tok, start, bt, length, temps, greedy, keys,
                    rows_of,
                ):
                    self.stats["hot_calls"] += 1
                    return pf(
                        pf_params, cache, tok, start, bt, length, temps,
                        greedy, keys, rows_of,
                    )

                return bound_prefill_rows

        draft_dispatch = verify_dispatch = draft_prefill_dispatch = None
        if use_spec and self._supports_spec_decode():
            (
                draft_dispatch, verify_dispatch, draft_prefill_dispatch,
            ) = self._spec_dispatchers(
                s, cache_is_paged=True, kv_dtype=dt, draft_kv_dtype=ddt,
                mesh_bind=mb,
            )

        mesh_ctl = self._make_mesh_ctl(
            mb, "paged",
            lambda pages_bucket: lanes_mod.CBP.key(
                s, pages_bucket, dt, mb["mesh"]
            ),
        )

        # Pre-bind the hot slot to the smallest bucket (cheap dispatch);
        # the registry warm already dummy-ran it.
        self._decode.dispatch(lanes_mod.CBP.key(s, 1, dt, m0))

        # Disaggregation control surfaces (DESIGN.md §17): the page
        # transport (gather on the source slice, device_put the replicated
        # block across, scatter donated on the destination slice) and the
        # split/collapse rebind.
        pf_cache = transport = disagg_ctl = pf_put = None
        if dg is not None:
            pf_cache = ctx.paged_caches[(dt, dg.prefill)]
            mg = {
                (op, m): self._decode.dispatch(lanes_mod.MG.key(op, dt, m))
                for op in self._mg_ops()
                for m in (m0, dg.prefill)
            }
            dec_plan = self._mesh_plan(m0)
            pf_plan = self._mesh_plan(dg.prefill)
            null0 = pool.null_page(0)

            def _pad_idx(ids):
                idx = np.full(max_pages_per_req, null0, np.int32)
                idx[: len(ids)] = ids
                return jnp.asarray(idx)

            def transport(
                src_cache, dst_cache, src_ids, dst_ids, to_prefill=False
            ):
                """Move page *contents* across slices; bookkeeping (the
                ids) moved separately via ``kvcache.migrate_pages``. Rows
                past ``len(ids)`` read and write null pages — reserved
                garbage on both ends, so a short migration reuses the
                same pinned-width executables."""
                src_m, dst_m = (
                    (m0, dg.prefill) if to_prefill else (dg.prefill, m0)
                )
                dst_plan = pf_plan if to_prefill else dec_plan
                blk = mg[("gather", src_m)](src_cache, _pad_idx(src_ids))
                blk = jax.device_put(
                    blk,
                    dst_plan.device
                    if dst_plan.solo
                    else shd.replicated(dst_plan.mesh),
                )
                return mg[("scatter", dst_m)](
                    dst_cache, blk, _pad_idx(dst_ids)
                )

            # Host inputs for prefill-slice executables go up in ONE hop
            # and ONE dispatch: a plain jnp.asarray lands on the default
            # device and XLA then forwards it to the slice, doubling the
            # upload latency of every chunk step. Takes a host array or a
            # pytree of them (batched upload).
            pf_target = (
                pf_plan.device
                if pf_plan.solo
                else shd.replicated(pf_plan.mesh)
            )

            def pf_put(host):
                return jax.device_put(host, pf_target)

            def disagg_ctl(on: bool) -> str:
                target = dg.prefill if on else m0
                if mb["prefill"] != target:
                    mb["prefill"] = target
                    self.telemetry.registry.inc("disagg_rebinds_total")
                    rec = self.telemetry.trace_or_none()
                    if rec is not None:
                        rec.emit(
                            "disagg_rebind", "dispatcher",
                            args={"prefill": target, "on": on},
                        )
                return target

        # COW device half (cold path): one jitted in-place page copy; the
        # batcher threads it through the same cache its steps donate.
        copy_jit = jax.jit(models.copy_cache_pages, donate_argnums=(0,))

        # Warmup is complete: everything from here on is steady state
        # (DESIGN.md §14).
        self.mark_warm_boundary()
        return PagedContinuousBatcher(
            dispatch_fn=dispatch,
            pool=pool,
            prefix_cache=prefix,
            cache=cache,
            num_slots=s,
            max_pages_per_req=max_pages_per_req,
            cache_copy=lambda c, src, dst: copy_jit(
                c, jnp.int32(src), jnp.int32(dst)
            ),
            seed=seed,
            prefill_dispatch=prefill_dispatch,
            prefill_chunk=self.ecfg.prefill_chunk,
            token_budget=self.ecfg.token_budget,
            prefill_rows_dispatch=prefill_rows_dispatch,
            draft_dispatch=draft_dispatch,
            verify_dispatch=verify_dispatch,
            draft_prefill_dispatch=draft_prefill_dispatch,
            draft_cache=(
                ctx.draft_caches.get((ddt, m0))
                if ctx.draft_caches
                else None
            ),
            spec_k=self.ecfg.spec_k,
            async_steps=async_steps,
            async_depth=async_depth,
            telemetry=self.telemetry,
            mesh=m0,
            mesh_ctl=mesh_ctl,
            pf_pool=pf_pool,
            pf_cache=pf_cache,
            transport=transport,
            pf_put=pf_put,
            disagg_ctl=disagg_ctl,
            disagg=dg is not None,
        )


# ------------------------------------------------------------ stream drivers
def run_continuous_stream(
    eng: Engine,
    requests: list[Request],
    *,
    slots: int | None = None,
    seed: int = 0,
    clock: Clock | None = None,
    async_steps: bool = False,
    async_depth: int = 2,
    mesh: str | None = None,
) -> dict:
    """Drive a request stream through continuous batching; return a report.

    The report's ``compiles_after_warmup`` is the acceptance metric: it must
    stay 0 for any mix of greedy/sample requests once the bucket executable
    exists. ``async_steps`` pipelines host scheduling against device
    execution (DESIGN.md §13; ``async_depth`` caps the in-flight pipeline);
    greedy token streams are bitwise identical either way. ``mesh``
    overrides the active topology (DESIGN.md §16); it must be inside the
    engine's warmed ladder.
    """
    cb = eng.continuous(  # warmup compile first...
        slots=slots, seed=seed, async_steps=async_steps,
        async_depth=async_depth, mesh=mesh,
    )
    clock = clock or Clock()  # ...so served latencies exclude it
    # continuous() marked the warm boundary (DESIGN.md §14); the report's
    # post-warmup counters read from it instead of local snapshots.
    q = RequestQueue(requests)
    finished: list[Request] = []
    while q or cb.has_work:
        now = clock.now()
        due = q.pop_due(now, limit=cb.free_slots)
        if due:
            cb.admit(due, now=now)
        if cb.has_work:
            finished.extend(cb.step(now=clock.now()))
        else:
            nxt = q.next_arrival()
            if nxt is None:
                break
            clock.jump_to(nxt)  # idle: fast-forward to the next arrival
    finished.extend(cb.flush(clock.now()))  # commit any in-flight step
    report = latency_report(finished, batcher=cb)
    report.update(
        engine="continuous",
        async_steps=cb.async_steps,
        mesh=cb.mesh,
        slots=cb.num_slots,
        steps=cb.stats.steps,
        occupancy=round(cb.stats.occupancy, 4),
        prefill_chunk=cb.prefill_chunk,
        prompt_tokens=cb.stats.prompt_tokens,
        prefill_chunks=cb.stats.prefill_chunks,
        chunk_bucket_crossings=cb.stats.chunk_bucket_crossings,
        h2d_uploads=cb.stats.h2d_uploads,
        h2d_overlapped=cb.stats.h2d_overlapped,
        spec_k=cb.spec_k,
        k_bucket_crossings=cb.stats.k_bucket_crossings,
        compiles_total=eng._decode.stats.misses,
        compiles_after_warmup=eng.post_warmup_compiles,
        rebinds=eng.post_warmup_rebinds,
    )
    return report


def run_burst_stream(
    eng: Engine, requests: list[Request], *, clock: Clock | None = None
) -> dict:
    """Per-burst baseline: every burst pays set_mode (dispatch + possible
    compile + rebind) before its hot loop; mixed modes split into separate
    bursts because the mode is baked into the executable."""
    clock = clock or Clock()
    q = RequestQueue(requests)
    rng = np.random.default_rng(0)
    finished: list[Request] = []
    compiles0 = eng._decode.stats.misses
    rebinds0 = eng._decode.stats.rebinds
    switches = 0
    while q:
        now = clock.now()
        due = q.pop_due(now)
        if not due:
            nxt = q.next_arrival()
            if nxt is None:
                break
            clock.jump_to(nxt)
            continue
        for r in due:  # same admission contract as ContinuousBatcher.admit
            if r.new_tokens > eng.ecfg.max_len:
                raise ValueError(
                    f"request {r.rid} wants {r.new_tokens} tokens but the "
                    f"engine's cache holds max_len={eng.ecfg.max_len}."
                )
        for bucket, greedy, chunk in form_bursts(
            due, quantum=eng.ecfg.batch_quantum, max_batch=eng.ecfg.max_batch
        ):
            mode = GREEDY if greedy else SAMPLE
            info = eng.set_mode(batch=len(chunk), sampling=mode)  # cold path
            switches += 1
            b = info["bucket"]
            cache = models.init_cache(eng.cfg, b, eng.ecfg.max_len)
            first = np.zeros((b, 1), np.int32)
            for i, r in enumerate(chunk):
                first[i, 0] = r.first_token
                r.t_admit = clock.now()
            steps = max(r.new_tokens for r in chunk)
            key = jnp.asarray(
                rng.integers(0, 2**32, size=2, dtype=np.uint32)
            )
            # TTFT anchor: timestamp the burst's first step when its output
            # actually exists on device — not when the whole burst returns
            # (that conflated TTFT with total latency in the report).
            first_t: dict = {}

            def note_first(i, tok, _first_t=first_t):
                if i == 0:
                    jax.block_until_ready(tok)
                    _first_t["t"] = clock.now()

            toks, _ = eng.decode_loop(  # hot path
                cache, jnp.asarray(first), 0, steps, rng=key,
                on_step=note_first,
            )
            done_t = clock.now()
            for i, r in enumerate(chunk):
                r.tokens = [int(t) for t in toks[i, : r.new_tokens]]
                r.t_first = first_t.get("t", done_t)
                r.t_done = done_t
                finished.append(r)
    report = latency_report(finished, registry=eng.telemetry.registry)
    report.update(
        engine="burst",
        mode_switches=switches,
        compiles_total=eng._decode.stats.misses,
        compiles_after_warmup=eng._decode.stats.misses - compiles0,
        rebinds=eng._decode.stats.rebinds - rebinds0,
    )
    return report


def run_paged_stream(
    eng: Engine,
    requests: list[Request],
    *,
    slots: int | None = None,
    seed: int = 0,
    clock: Clock | None = None,
    kv_dtype: str | None = None,
    async_steps: bool = False,
    async_depth: int = 2,
    mesh: str | None = None,
    disagg: "bool | str | shd.DisaggPlan | None" = None,
) -> dict:
    """Drive a request stream through the paged KV engine; return a report.

    The acceptance contract (ISSUE 2): the only post-warmup compiles are
    first sightings of a new ``pages_bucket`` — between bucket crossings the
    hot loop never recompiles, and sharing lets peak *logical* tokens exceed
    the pool's physical token capacity. ``kv_dtype`` overrides the engine
    config's active pool dtype (DESIGN.md §12) — it must be in the warmed
    set, and flipping it across streams on one engine is the dtype crossing
    ``benchmarks/quantkv_bench.py`` gates at zero compiles. ``mesh``
    likewise overrides the active topology (DESIGN.md §16) — it must be in
    the warmed ladder, and crossing it across streams is a rebind, never a
    compile (``benchmarks/sharding_bench.py`` gates this).
    """
    from repro.runtime.kvcache import sharing_report

    cb = eng.paged_continuous(  # warmup compile first
        slots=slots, seed=seed, kv_dtype=kv_dtype, async_steps=async_steps,
        async_depth=async_depth, mesh=mesh, disagg=disagg,
    )
    clock = clock or Clock()  # ...so served latencies exclude it
    # paged_continuous() marked the warm boundary (DESIGN.md §14).
    q = RequestQueue(requests)
    finished: list[Request] = []
    peak_share: dict = {"share_ratio": 1.0, "overcommit_ratio": 0.0,
                        "logical_tokens": 0}
    peak_concurrent = 0
    stall_steps = 0
    while q or cb.has_work:
        now = clock.now()
        due = q.pop_due(now, limit=cb.free_slots)
        deferred: list[Request] = []
        if due:
            deferred = cb.admit(due, now=now)
            for r in deferred:
                q.submit(r)  # deferred for pages: retried, never rejected
        if cb.has_work:
            finished.extend(cb.step(now=clock.now()))
            for r in cb.preempted:
                q.submit(r)
            cb.preempted.clear()
            peak_concurrent = max(peak_concurrent, cb.active_count)
            share = sharing_report(cb.live_tables(), cb.pool)
            if share["logical_tokens"] >= peak_share["logical_tokens"]:
                peak_share = share
            stall_steps = 0
            continue
        if deferred:
            # Queued work but nothing admissible and nothing running: drop
            # idle prefix pages and retry before declaring a stall.
            if cb.prefix.evict(cb.pool.num_pages) == 0:
                stall_steps += 1
                if stall_steps > 2:
                    break  # pool too small for any queued request
            continue
        nxt = q.next_arrival()
        if nxt is None:
            break
        clock.jump_to(nxt)  # idle: fast-forward to the next arrival
    finished.extend(cb.flush(clock.now()))  # commit any in-flight step
    report = latency_report(finished, batcher=cb)
    report.update(
        engine="paged",
        async_steps=cb.async_steps,
        mesh=cb.mesh,
        slots=cb.num_slots,
        steps=cb.stats.steps,
        occupancy=round(cb.stats.occupancy, 4),
        page_size=cb.pool.page_size,
        kv_dtype=cb.pool.kv_dtype,
        pool_shards=cb.pool.shards,
        pool_pages=cb.pool.num_pages,
        pool_tokens=cb.pool.total_tokens,
        pages_in_use_peak=cb.pool.stats.peak_in_use,
        peak_concurrent=peak_concurrent,
        peak_logical_tokens=peak_share["logical_tokens"],
        share_ratio=round(peak_share["share_ratio"], 4),
        overcommit_ratio=round(peak_share["overcommit_ratio"], 4),
        shared_prompt_tokens=cb.stats.shared_tokens,
        prompt_tokens=cb.stats.prompt_tokens,
        # throughput incl. teacher-forced prompt work (what the device did;
        # ``tok_per_s`` counts only emitted tokens)
        proc_tok_per_s=(
            round(
                (report.get("tokens", 0) + cb.stats.prompt_tokens)
                / report["span_s"],
                1,
            )
            if report.get("span_s")
            else 0.0
        ),
        preemptions=cb.stats.preemptions,
        starved_admissions=cb.stats.starved_admissions,
        rejected_oversize=cb.stats.rejected_oversize,
        bucket_crossings=cb.stats.bucket_crossings,
        prefill_chunk=cb.prefill_chunk,
        prefill_chunks=cb.stats.prefill_chunks,
        chunk_bucket_crossings=cb.stats.chunk_bucket_crossings,
        h2d_uploads=cb.stats.h2d_uploads,
        h2d_overlapped=cb.stats.h2d_overlapped,
        spec_k=cb.spec_k,
        k_bucket_crossings=cb.stats.k_bucket_crossings,
        cow_copies=cb.pool.stats.cow_copies,
        prefix_evictions=cb.pool.stats.prefix_evictions,
        unserved=len(requests) - len(finished),
        disagg=cb.disagg,
        migrations=cb.stats.migrations,
        migrated_pages=cb.stats.migrated_pages,
        disagg_rebinds=int(
            eng.telemetry.registry.value("disagg_rebinds_total")
        ),
        compiles_total=eng._decode.stats.misses,
        compiles_after_warmup=eng.post_warmup_compiles,
        rebinds=eng.post_warmup_rebinds,
    )
    return report


def run_overload_stream(
    eng: Engine,
    requests: list[Request],
    *,
    slots: int | None = None,
    seed: int = 0,
    clock: Clock | None = None,
    kv_dtype: str | None = None,
    async_steps: bool = False,
    capacity: int | None = None,
    shed_policy: str = "reject-new",
    queue_ttl_s: float | None = None,
    controller: "DegradeController | None" = None,
    degrade: bool = False,
    faults=None,
    watchdog: bool = True,
    heartbeat_timeout_steps: float = 2.0,
    max_steps: int | None = None,
) -> dict:
    """The overload-hardened paged stream driver (DESIGN.md §15).

    Everything ``run_paged_stream`` does, plus the hardening surfaces:

    * **bounded admission** — an :class:`~repro.runtime.admission.
      AdmissionQueue` with ``capacity``/``shed_policy``/``queue_ttl_s``;
    * **deadlines** — per-request ``ttl_s`` sheds in queue, ``deadline_s``
      cancels mid-stream (the batcher's ``_cancel_overdue``);
    * **degradation ladder** — a :class:`~repro.runtime.degrade.
      DegradeController` observed once per iteration; its rung changes
      actuate ``set_knobs`` over already-warmed keys, and an ``int8-pool``
      rung routes *new admissions* to a pre-warmed int8 standby batcher
      while the fp32 one drains (pools flip at the admission boundary —
      a live cache is never requantised);
    * **fault injection** — an armed ``core.faults.FaultPlan`` is attached
      to the dispatcher (``build``), pool (``pool_alloc``), and batchers
      (``step_output``/``d2h_stall``); ``heartbeat`` is driven here: each
      iteration beats a :class:`~repro.ft.failover.HeartbeatMonitor` on a
      *step-count* time axis (deterministic under the virtual clock), a
      fired fault suppresses the beat, and a lost heartbeat forces the
      controller to the bottom rung until beats resume;
    * **watchdog** — ``ft.failover.StepTimeWatchdog`` wired into the step
      loop; stragglers feed the controller.

    With every knob at its default (no capacity, no TTL, no controller, no
    faults) this is behaviourally ``run_paged_stream`` — the hardened loop
    is inert until configured.
    """
    from repro.ft.failover import HeartbeatMonitor, StepTimeWatchdog
    from repro.runtime.admission import AdmissionQueue
    from repro.runtime.degrade import (
        DegradeController, Rung, apply_rung, default_ladder,
    )

    registry = eng.telemetry.registry
    trace = eng.telemetry.trace_or_none()
    if faults is not None and faults.registry is None:
        faults.registry = registry
    if faults is not None:
        eng.attach_faults(faults)

    cb = eng.paged_continuous(  # warmup compile first
        slots=slots, seed=seed, kv_dtype=kv_dtype, async_steps=async_steps
    )
    base = Rung(
        "base",
        spec_k=cb.spec_k,
        prefill_chunk=cb.prefill_chunk,
        token_budget=cb.token_budget,
        kv_dtype=cb.pool.kv_dtype,
    )
    ctrl = controller
    if ctrl is None and degrade:
        ctrl = DegradeController(
            default_ladder(
                spec_k=cb.spec_k,
                prefill_chunk=cb.prefill_chunk,
                token_budget=cb.token_budget,
                int8_pool=(
                    "int8" in eng._warm_kv_dtypes()
                    and cb.pool.kv_dtype != "int8"
                ),
            ),
            registry=registry,
            trace=trace,
            queue_high=max(2 * cb.num_slots, 8),
            queue_low=max(cb.num_slots // 2, 1),
        )
    # int8 standby: pre-warm the flip target *before* the warm boundary
    # settles, so an int8-pool rung crossing is pure admission routing.
    cb8 = None
    if ctrl is not None and any(
        r.kv_dtype == "int8" for r in ctrl.rungs
    ) and base.kv_dtype != "int8":
        cb8 = eng.paged_continuous(
            slots=slots, seed=seed, kv_dtype="int8",
            async_steps=async_steps,
        )
    batchers = [b for b in (cb, cb8) if b is not None]
    if faults is not None:
        for b in batchers:
            b.attach_faults(faults)
            b.pool.attach_faults(faults)
    if watchdog:
        straggled = {"now": False}

        def _on_straggler(dt_s: float) -> None:
            straggled["now"] = True

        for b in batchers:
            b.attach_watchdog(StepTimeWatchdog(), _on_straggler)
    monitor = HeartbeatMonitor(
        ["engine"], timeout_s=heartbeat_timeout_steps
    )
    hb_lost = False

    clock = clock or Clock()
    # Open-loop traffic model: ``pending`` holds requests that have not
    # *arrived* yet; the bounded AdmissionQueue only ever sees arrived
    # requests, so capacity/TTL/shedding act on actual queue wait, never
    # on the future tail of the trace.
    pending = RequestQueue(requests)
    q = AdmissionQueue(
        (),
        capacity=capacity,
        shed_policy=shed_policy,
        queue_ttl_s=queue_ttl_s,
        registry=registry,
        trace=trace,
    )
    active = cb  # admission target; rung crossings may re-route it
    finished: list[Request] = []
    stall_steps = 0
    steps = 0

    def _has_work() -> bool:
        return any(b.has_work for b in batchers)

    while pending or q or _has_work():
        if max_steps is not None and steps >= max_steps:
            break
        steps += 1
        now = clock.now()
        for r in pending.pop_due(now):
            q.submit(r)  # arrival: the shed policy applies here
        # --- heartbeat (step-count time axis: deterministic) -------------
        beat = True
        if faults is not None and faults.fire("heartbeat") is not None:
            beat = False
        if beat:
            monitor.beat("engine", t=float(steps))
        healthy = not monitor.failed(now=float(steps))
        if not healthy and not hb_lost:
            hb_lost = True
            if faults is not None:
                faults.note_detected("heartbeat")
        elif healthy and hb_lost:
            hb_lost = False
            if faults is not None:
                # beats resumed and the stream kept serving: contained
                faults.note_contained("heartbeat")
        # --- controller ---------------------------------------------------
        if ctrl is not None:
            rung = ctrl.observe(
                now,
                queue_depth=len(q),
                pool_frac=(
                    active.pool.pages_in_use / active.pool.num_pages
                ),
                straggler=(
                    watchdog and straggled["now"]
                ),
                healthy=healthy,
            )
            if watchdog:
                straggled["now"] = False
            if rung is not None:
                for b in batchers:
                    apply_rung(b, rung, base)
                active = (
                    cb8
                    if (rung.kv_dtype == "int8" and cb8 is not None)
                    else cb
                )
        # --- admission ----------------------------------------------------
        due = q.pop_due(now, limit=active.free_slots)
        if due:
            for r in active.admit(due, now=now):
                q.submit(r)  # deferred for pages: retried, never rejected
        # --- step every batcher that holds work ---------------------------
        stepped = False
        for b in batchers:
            if not b.has_work:
                continue
            stepped = True
            finished.extend(b.step(now=clock.now()))
            for r in b.preempted:
                q.submit(r)
            b.preempted.clear()
            for r in b.requeued:  # quarantined: restart from scratch
                q.submit(r)
            b.requeued.clear()
        if stepped:
            stall_steps = 0
            continue
        if q:
            # arrived work but nothing admitted: reclaim prefix pages,
            # then declare a stall (pool too small for anything queued)
            if active.prefix.evict(active.pool.num_pages) == 0:
                stall_steps += 1
                if stall_steps > 2:
                    break
            continue
        nxt = pending.next_arrival()
        if nxt is None:
            break
        clock.jump_to(nxt)  # idle: fast-forward to the next arrival
    now = clock.now()
    for b in batchers:
        finished.extend(b.flush(now))
    if ctrl is not None:
        ctrl.finalize(now)
    # pool_alloc containment is the pre-existing evict/preempt/defer
    # machinery; if the stream drained (no injected exhaustion wedged it),
    # every injected alloc failure was absorbed.
    if faults is not None and not q and not pending:
        n_pa = sum(1 for site, _ in faults.injected if site == "pool_alloc")
        for _ in range(n_pa - faults.contained.get("pool_alloc", 0)):
            faults.note_contained("pool_alloc")

    cancelled = [r for b in batchers for r in b.cancelled_requests]
    failed = [r for b in batchers for r in b.failed_requests]
    report = latency_report(finished, batcher=cb, registry=registry)
    report.update(
        engine="overload",
        async_steps=cb.async_steps,
        slots=cb.num_slots,
        steps=sum(b.stats.steps for b in batchers),
        kv_dtype=active.pool.kv_dtype,
        capacity=capacity,
        shed_policy=shed_policy,
        shed=len(q.shed),
        cancelled=len(cancelled),
        failed=len(failed),
        deadline_missed=sum(b.stats.deadline_missed for b in batchers),
        stragglers=sum(b.stats.stragglers for b in batchers),
        preemptions=sum(
            getattr(b.stats, "preemptions", 0) for b in batchers
        ),
        unserved=len(requests)
        - len(finished) - len(q.shed) - len(cancelled) - len(failed),
        degrade_rung=(ctrl.rung.name if ctrl is not None else None),
        degrade_transitions=(
            [
                {"t": round(t, 4), "from": a, "to": b_, "why": w}
                for t, a, b_, w in ctrl.transitions
            ]
            if ctrl is not None
            else []
        ),
        faults=(faults.report() if faults is not None else None),
        compiles_total=eng._decode.stats.misses,
        compiles_after_warmup=eng.post_warmup_compiles,
        rebinds=eng.post_warmup_rebinds,
    )
    return report
