"""Dispatch-coordinate registry tests (DESIGN.md §12): typed DispatchKey
tuple-compat, LaneSpec arity/ladder validation, unknown-lane keys raising at
build/warmup time (the old silent key-sniffing fallthrough), round-tripping
every registered lane through key-build -> warmup -> lookup for both
engines, the full kv_dtype warmup fan-out (0 post-warmup compiles on dtype
crossings), and per-spec-name lane_calls reporting."""

import jax
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.core import (
    LANES,
    DispatchKey,
    LaneAxis,
    LaneRegistry,
    LaneSpec,
    UnknownLaneError,
    reset_entry_points,
)
from repro.runtime.scheduler import Request
from repro.runtime.serve import Engine, EngineConfig


@pytest.fixture(scope="module")
def smoke_setup():
    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **over):
    reset_entry_points()
    kw = dict(
        max_len=32,
        batch_quantum=2,
        max_batch=4,
        page_size=8,
        num_pages=20,
        prefill_chunk=8,
        spec_k=2,
        draft_layers=1,
    )
    kw.update(over)
    return Engine(cfg, params, EngineConfig(**kw))


def _prompt_reqs(cfg, n=3, prompt_len=12, new_tokens=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i, new_tokens=new_tokens, greedy=True, arrival_s=0.0,
            prompt=tuple(
                int(x) for x in rng.integers(0, cfg.vocab_size, prompt_len)
            ),
        )
        for i in range(n)
    ]


# ------------------------------------------------------------ DispatchKey
def test_dispatch_key_is_tuple_compatible():
    """The typed key hashes/compares exactly like the raw tuple it
    replaces: compile caches, pins, and stats keys are unchanged."""
    key = DispatchKey("cbp", (4, 8, "int8", "1x1"))
    assert key == ("cbp", 4, 8, "int8", "1x1")
    assert hash(key) == hash(("cbp", 4, 8, "int8", "1x1"))
    assert key.lane == "cbp" and key.coords == (4, 8, "int8", "1x1")
    assert {key: 1}[("cbp", 4, 8, "int8", "1x1")] == 1
    assert "DispatchKey" in repr(key)


def test_lane_spec_key_arity_and_coord_access():
    spec = LANES.get("cbp")
    key = spec.key(4, 2, "fp32", "1x1")
    assert key == ("cbp", 4, 2, "fp32", "1x1")
    assert spec.coord(key, "pages_bucket") == 2
    assert spec.coord(key, "kv_dtype") == "fp32"
    assert spec.coord(key, "mesh") == "1x1"
    with pytest.raises(UnknownLaneError):
        spec.key(4, 2, "fp32")  # missing mesh
    with pytest.raises(UnknownLaneError):
        spec.coord(key, "nope")
    with pytest.raises(UnknownLaneError):
        spec.coords(("cbp", 4))  # wrong arity


def test_registry_rejects_unknown_and_duplicate_lanes():
    with pytest.raises(UnknownLaneError):
        LANES.get("nope")
    with pytest.raises(UnknownLaneError):
        LANES.spec_for(("nope", 1, 2))
    with pytest.raises(UnknownLaneError):
        LANES.spec_for(17)  # not even a tuple
    with pytest.raises(UnknownLaneError):
        LANES.spec_for((4, 0))  # the old raw burst tuple: no lane name
    reg = LaneRegistry()
    reg.register(LaneSpec(name="x", role="r", axes=(), builder="_b"))
    with pytest.raises(UnknownLaneError):
        reg.register(LaneSpec(name="x", role="r", axes=(), builder="_b"))


def test_unpinned_axis_without_ladder_raises():
    ax = LaneAxis("slots")  # no ladder: must be pinned by the caller
    with pytest.raises(UnknownLaneError):
        ax.values(object())
    spec = LANES.get("cb")
    with pytest.raises(UnknownLaneError):
        spec.fanout(object())  # slots not pinned
    with pytest.raises(UnknownLaneError):
        spec.fanout(object(), slots=4, nope=1)  # unknown pin


# -------------------------------------------- unknown lanes raise (warmup)
def test_unknown_lane_raises_at_build_time(smoke_setup):
    """Satellite regression (ISSUE 5): before the registry an unrecognised
    key prefix fell through runtime/serve.py's sniffing chain silently;
    now any unregistered lane or malformed key raises UnknownLaneError on
    the cold path (build/warmup), never a silent skip."""
    cfg, params = smoke_setup
    eng = _engine(cfg, params)
    with pytest.raises(UnknownLaneError):
        eng._decode.build(("nope", 4))
    with pytest.raises(UnknownLaneError):
        eng._decode.build(("cb", 4, 8, "1x1"))  # arity mismatch for "cb"
    with pytest.raises(UnknownLaneError):
        eng._decode.build((4, 0))  # the pre-registry raw burst tuple
    with pytest.raises(UnknownLaneError):
        eng._decode.dispatch(("pf", 8))  # PR-4-era paged prefill key shape
    eng.close()


# ----------------------------------------------- round trip (both engines)
@pytest.mark.parametrize("engine_kind", ["paged", "dense"])
def test_registry_round_trip_all_lanes(smoke_setup, engine_kind):
    """Satellite (ISSUE 5): every registered LaneSpec the engine warms
    round-trips through key-build -> warmup -> lookup: each fanout key is
    in the compile cache after warmup and re-dispatching it moves no
    compile counter."""
    cfg, params = smoke_setup
    eng = _engine(cfg, params)
    s = 4
    if engine_kind == "paged":
        cb = eng.paged_continuous(slots=s)
    else:
        cb = eng.continuous(slots=s)
    ctx_spec = type("Ctx", (), {"spec": True})()
    misses = eng._decode.stats.misses
    seen = 0
    for spec in LANES.for_engine(engine_kind):
        if spec.enabled is not None and not getattr(eng, spec.enabled)(
            ctx_spec
        ):
            continue
        keys = spec.fanout(eng, slots=s)
        assert keys, f"lane {spec.name} warms an empty fan-out"
        for key in keys:
            assert key in eng._decode, (spec.name, key)
            eng._decode.dispatch(key)
            seen += 1
    assert seen > 0
    assert eng._decode.stats.misses == misses, (
        f"{engine_kind}: round-trip dispatch compiled after warmup"
    )
    eng.close()


def test_registry_covers_every_engine_kind():
    """Every registered lane belongs to at least one engine kind, and the
    seven serving lanes + burst + the migration transport are all present."""
    names = set(LANES.names())
    assert {"burst", "cb", "cbp", "pf", "pfd", "dr", "drp", "vf", "vfd",
            "mg"} <= names
    for spec in LANES:
        assert spec.engines, spec.name
        assert spec.role in ("decode", "prefill", "draft", "verify",
                             "migrate")


# --------------------------------------------------- kv_dtype completeness
def test_warmup_completeness_kv_dtype_fanout(smoke_setup):
    """Satellite (ISSUE 5): PR 4's warmup-completeness contract extended to
    the kv_dtype axis — with both dtypes configured, every paged lane key
    for *both* dtypes exists after one warmup, and serving a stream on
    either pool dtype (the dtype crossing) compiles nothing."""
    cfg, params = smoke_setup
    eng = _engine(cfg, params, kv_dtype="int8", kv_dtypes=("fp32",))
    s = 4
    cb8 = eng.paged_continuous(slots=s)
    assert cb8.kv_dtype == "int8"
    for dt in ("fp32", "int8"):
        for pb in eng._pages_buckets():
            assert ("cbp", s, pb, dt, "1x1") in eng._decode
        for c in eng._chunk_buckets():
            assert ("pf", s, c, dt, "1x1") in eng._decode
        for k in eng._k_buckets():
            assert ("vf", s, k, dt, "1x1") in eng._decode
    misses = eng._decode.stats.misses
    reqs = _prompt_reqs(cfg)
    cb8.admit(reqs, now=0.0)
    while cb8.has_work:
        cb8.step()
    assert all(r.done for r in reqs)
    # the crossing: a second batcher flips the pool to fp32 — rebinds only
    cb32 = eng.paged_continuous(slots=s, kv_dtype="fp32")
    assert cb32.kv_dtype == "fp32"
    reqs2 = _prompt_reqs(cfg)
    cb32.admit(reqs2, now=0.0)
    while cb32.has_work:
        cb32.step()
    assert all(r.done for r in reqs2)
    assert eng._decode.stats.misses == misses, "dtype crossing compiled"
    eng.close()


def test_unwarmed_kv_dtype_is_rejected(smoke_setup):
    """A pool dtype outside the warmed set would compile mid-stream; the
    engine refuses it loudly instead."""
    cfg, params = smoke_setup
    eng = _engine(cfg, params, spec_k=0, prefill_chunk=0)
    with pytest.raises(ValueError, match="warmed set"):
        eng.paged_continuous(slots=2, kv_dtype="int8")
    eng.close()


# ------------------------------------------------------ lane-name reports
def test_lane_calls_grouped_by_spec_name(smoke_setup):
    """latency_report groups per-lane executable calls under the registry's
    spec names (the tentpole's reporting half)."""
    from repro.runtime.serve import run_paged_stream

    cfg, params = smoke_setup
    eng = _engine(cfg, params)
    rep = run_paged_stream(eng, _prompt_reqs(cfg), slots=4)
    eng.close()
    calls = rep["lane_calls"]
    assert set(calls) <= set(LANES.names())
    assert calls.get("cbp", 0) + calls.get("vf", 0) > 0  # decode-side lanes
    assert calls.get("pf", 0) > 0  # prompts went through the paged chunk lane
    assert "cb" not in calls and "pfd" not in calls  # dense lanes untouched
    assert rep["kv_dtype"] == "fp32"


# ------------------------------------------------- row-bucketed prefill
def test_row_bucket_fanout_is_l_shaped_and_never_compiles(smoke_setup):
    """The paged prefill's row count is a semi-static coordinate beside the
    chunk width: after one warm-up every chunk bucket exists at the full
    slot count (today's ``pf`` keys) and every smaller row bucket at the
    top chunk bucket (``pfr``), an L-shaped set, not a product. A stream
    that moves from one prefilling request to every slot rebinds across
    them and compiles nothing."""
    cfg, params = smoke_setup
    eng = _engine(cfg, params, spec_k=0, token_budget=64)
    s = 4
    cb = eng.paged_continuous(slots=s)
    for c in eng._chunk_buckets():
        assert ("pf", s, c, "fp32", "1x1") in eng._decode
    assert eng._row_buckets(s) == [1, 2]
    assert LANES.get("pfr").fanout(eng, slots=s) == [
        ("pfr", s, 1, "fp32", "1x1"), ("pfr", s, 2, "fp32", "1x1"),
    ]
    for r in (1, 2):
        assert ("pfr", s, r, "fp32", "1x1") in eng._decode
    eng.telemetry.enable()
    reqs = _prompt_reqs(cfg, n=7)
    for group in (reqs[:1], reqs[1:3], reqs[3:]):  # 1, 2, then 4 prefill
        cb.admit(group, now=0.0)
        while cb.has_work:
            cb.step()
    assert all(r.done for r in reqs)
    rows = {e.args["rows"] for e in eng.telemetry.recorder.events()
            if e.name == "serve.batcher.pack" and e.args["lane"] == "pf"}
    assert rows == {1, 2, 4}
    assert cb.stats.prefill_rows < cb.stats.prefill_calls * s
    assert cb.stats.prefill_rows_padded < cb.stats.prefill_calls * s
    assert eng.post_warmup_compiles == 0
    eng.close()


def test_spec_lanes_keep_prefill_at_every_slot(smoke_setup):
    """With spec lanes on, the draft mirror reuses the prefill call's
    per-slot windows: no row bucket is warmed and every call carries
    every slot, as before."""
    cfg, params = smoke_setup
    eng = _engine(cfg, params, token_budget=64)
    s = 4
    cb = eng.paged_continuous(slots=s)
    assert cb._prefill_rows_dispatch is None
    for r in eng._row_buckets(s):
        assert ("pfr", s, r, "fp32", "1x1") not in eng._decode
    reqs = _prompt_reqs(cfg, n=2)
    cb.admit(reqs, now=0.0)
    while cb.has_work:
        cb.step()
    assert all(r.done for r in reqs)
    assert cb.stats.prefill_calls > 0
    assert cb.stats.prefill_rows_padded == cb.stats.prefill_calls * s
    eng.close()


def test_sharded_pool_gates_off_row_buckets(smoke_setup):
    """A warmed mesh whose data-parallel size is above 1 shards the page
    pool: each slot pads with its own shard's null page, so the prefill
    rows stay on their slots (the stream itself: tests/test_sharding.py)."""
    from repro.runtime.serve import _WarmCtx

    cfg, params = smoke_setup
    eng = _engine(cfg, params, spec_k=0, meshes=("2x1",))
    assert eng.pool_shards == 2
    assert not eng._row_prefill_enabled(_WarmCtx())
    eng.close()
    eng = _engine(cfg, params, spec_k=0)
    assert eng._row_prefill_enabled(_WarmCtx())
    eng.close()
