"""Dispatch-coordinate registry: lanes and their bucket axes as declarations.

Through PR 4 the serving engine's dispatch keys were ad-hoc tuples —
``("cb", slots, pages_bucket)``, ``("pf", chunk_bucket)``, ``("dr", slots,
k_bucket)`` — dispatched by ``len(key)`` and ``key[0] == ...`` string
sniffing in ``runtime/serve.py``. Every new coordinate (a bucket axis, a
dtype) meant hand-editing seven builder branches, seven warmup loops, and
the report plumbing, and an unrecognised key prefix fell through silently.

This module makes the key space first-class (DESIGN.md §12):

* ``LaneAxis``    — one coordinate of a lane's key: a name plus the *bucket
                    ladder* that enumerates its warmup fan-out (an engine
                    method name, e.g. ``"_chunk_buckets"``), or ``None``
                    for axes the caller pins per batcher (``slots``);
                    ``per`` sizes a ladder by a pinned axis (``rows``
                    below ``slots``).
* ``LaneSpec``    — one lane's declaration: name, role (stats grouping),
                    ordered axes, and the engine hook names that build
                    (``builder``), dummy-run (``warmer``), and gate
                    (``enabled``) its executables. ``fanout`` expands the
                    axis ladders into the complete warmup key set.
* ``DispatchKey`` — the typed key: a tuple subclass ``(lane, *coords)``,
                    hash/eq-compatible with the raw tuples it replaces, so
                    the ``core.dispatch.Dispatcher``'s cache and every
                    stats counter work unchanged.
* ``LaneRegistry``— name -> spec, with ``spec_for(key)`` raising
                    ``UnknownLaneError`` on unregistered lanes or arity
                    mismatches — the warmup fallthrough hazard is now a
                    loud cold-path error, never a silent skip.

The registry holds *declarations only* (method names, not callables), so it
stays importable without jax and carries no reference to a live engine.
Adding a coordinate is one ``LaneAxis`` in the relevant specs plus the
ladder method — the builders, warmup iteration, and lookup plumbing never
change; ``kv_dtype`` (quantised int8 KV pages, DESIGN.md §12) is the first
axis added this way.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Hashable, Iterable, Iterator

from .dispatch import DispatchError


class UnknownLaneError(DispatchError):
    """An unregistered lane name (or malformed key) reached the dispatcher.

    Raised at build/warmup time: before the registry, an unrecognised key
    prefix fell through ``runtime/serve.py``'s sniffing chain silently."""


class DispatchKey(tuple):
    """Typed dispatch key ``(lane, coord_0, ..., coord_{n-1})``.

    A tuple subclass so it hashes and compares exactly like the raw tuples
    it replaces (compile caches, pinned-slot bookkeeping, and stats keys
    are unchanged), while giving the registry and reports structured
    access to the lane name and coordinates.
    """

    __slots__ = ()

    def __new__(cls, lane: str, coords: Iterable[Hashable] = ()):
        return super().__new__(cls, (lane, *coords))

    @property
    def lane(self) -> str:
        return self[0]

    @property
    def coords(self) -> tuple:
        return tuple(self[1:])

    def __repr__(self) -> str:  # debuggable: DispatchKey('pf', 4, 16, 'int8')
        return f"DispatchKey({self[0]!r}, {self.coords!r})"


@dataclass(frozen=True)
class LaneAxis:
    """One coordinate axis of a lane's dispatch key.

    ``ladder`` names the engine method returning the axis's warmup fan-out
    (ordered bucket values, e.g. the log-sized chunk set {8, 16, ...});
    ``None`` marks an axis the caller pins per warmup (``slots`` — chosen
    at batcher-creation time, not derivable from the engine config alone).
    """

    name: str
    ladder: str | None = None
    # The pinned axis this ladder is sized by: the ladder method receives
    # that axis's value (the row buckets below a batcher's ``slots``).
    per: str | None = None

    def values(self, engine: Any, pinned: dict | None = None) -> tuple:
        if self.ladder is None:
            raise UnknownLaneError(
                f"axis {self.name!r} has no ladder; pin it via fanout(..., "
                f"{self.name}=value)"
            )
        if self.per is None:
            return tuple(getattr(engine, self.ladder)())
        if not pinned or self.per not in pinned:
            raise UnknownLaneError(
                f"axis {self.name!r} is sized per {self.per!r}; pin it via "
                f"fanout(..., {self.per}=value)"
            )
        return tuple(getattr(engine, self.ladder)(pinned[self.per]))


@dataclass(frozen=True)
class LaneSpec:
    """One lane's declaration: key shape + engine hooks, no live state.

    ``builder``/``warmer``/``enabled`` are *engine method names* — the
    registry stays declarative and importable anywhere; the engine supplies
    behaviour. ``engines`` says which warmup drivers iterate this spec
    ({"dense"}, {"paged"}, {"burst"}, or combinations); ``role`` groups the
    lane in per-lane reports (prefill/draft/verify/decode/burst).
    """

    name: str
    role: str
    axes: tuple[LaneAxis, ...]
    builder: str
    warmer: str | None = None
    engines: frozenset[str] = field(default_factory=frozenset)
    enabled: str | None = None
    doc: str = ""
    # Which mesh slice the lane's calls route to under disaggregated
    # prefill/decode (DESIGN.md §17): "prefill" lanes follow the
    # DisaggPlan's prefill slice, everything else stays on the decode
    # (= base) mesh. With disaggregation off both resolve to the same
    # mesh, so the field is inert outside a split.
    slice: str = "decode"

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(a.name for a in self.axes)

    def key(self, *coords: Hashable) -> DispatchKey:
        """Build this lane's typed key; arity-checked at construction."""
        if len(coords) != len(self.axes):
            raise UnknownLaneError(
                f"lane {self.name!r} takes {len(self.axes)} coordinates "
                f"{self.axis_names}, got {len(coords)}: {coords!r}"
            )
        return DispatchKey(self.name, coords)

    def coords(self, key: tuple) -> tuple:
        """Validate ``key`` against this spec and return its coordinates."""
        if len(key) != len(self.axes) + 1:
            raise UnknownLaneError(
                f"lane {self.name!r} key must be (name, {', '.join(self.axis_names)}), "
                f"got {tuple(key)!r}"
            )
        return tuple(key[1:])

    def coord(self, key: tuple, axis: str) -> Hashable:
        """One named coordinate out of a validated key."""
        try:
            i = self.axis_names.index(axis)
        except ValueError:
            raise UnknownLaneError(
                f"lane {self.name!r} has no axis {axis!r} "
                f"(axes: {self.axis_names})"
            ) from None
        return self.coords(key)[i]

    def fanout(self, engine: Any, **pinned: Hashable) -> list[DispatchKey]:
        """The lane's complete warmup key set: the cartesian product of
        every axis's ladder, with ``pinned`` axes held at one value. This
        is what makes "add a coordinate" one declaration: a new axis
        automatically multiplies into every lane that carries it."""
        extra = set(pinned) - set(self.axis_names)
        if extra:
            raise UnknownLaneError(
                f"lane {self.name!r}: pinned unknown axes {sorted(extra)} "
                f"(axes: {self.axis_names})"
            )
        ranges = [
            ((pinned[a.name],) if a.name in pinned else a.values(engine, pinned))
            for a in self.axes
        ]
        return [self.key(*combo) for combo in itertools.product(*ranges)]


class LaneRegistry:
    """Name -> ``LaneSpec``; the single source of truth for the key space."""

    def __init__(self) -> None:
        self._specs: dict[str, LaneSpec] = {}

    def register(self, spec: LaneSpec) -> LaneSpec:
        if spec.name in self._specs:
            raise UnknownLaneError(
                f"lane {spec.name!r} registered twice; lane names are the "
                f"dispatch namespace and must be unique"
            )
        self._specs[spec.name] = spec
        return spec

    def get(self, name: str) -> LaneSpec:
        try:
            return self._specs[name]
        except KeyError:
            raise UnknownLaneError(
                f"unknown lane {name!r}; registered lanes: "
                f"{sorted(self._specs)}"
            ) from None

    def spec_for(self, key: Hashable) -> LaneSpec:
        """Resolve a dispatch key to its spec, arity-validated.

        This is the warmup/build-time gate: raw tuples with unregistered
        prefixes (or the wrong coordinate count) raise ``UnknownLaneError``
        instead of falling through a sniffing chain.
        """
        if not isinstance(key, tuple) or not key or not isinstance(key[0], str):
            raise UnknownLaneError(
                f"dispatch key must be (lane_name, *coords), got {key!r}"
            )
        spec = self.get(key[0])
        spec.coords(key)  # arity check
        return spec

    def for_engine(self, kind: str) -> list[LaneSpec]:
        """Specs a given engine kind warms, in registration (= warm) order."""
        return [s for s in self._specs.values() if kind in s.engines]

    def __iter__(self) -> Iterator[LaneSpec]:
        return iter(self._specs.values())

    def __len__(self) -> int:
        return len(self._specs)

    def __contains__(self, name: str) -> bool:
        return name in self._specs

    def names(self) -> tuple[str, ...]:
        return tuple(self._specs)


# --------------------------------------------------------------- the registry
# The serving engine's lanes (DESIGN.md §12). Registration order IS warmup
# order per engine kind: decode capacity first (establishes the warm cache),
# then prompt ingestion, then the verify/draft pair (the draft lanes build
# the draft cache), so each warm call threads the previous call's cache.
LANES = LaneRegistry()

_SLOTS = LaneAxis("slots")  # pinned per batcher (continuous(slots=...))
_PAGES = LaneAxis("pages_bucket", "_pages_buckets")
_CHUNK = LaneAxis("chunk_bucket", "_chunk_buckets")
# The row buckets {1, 2, 4, ...} below the batcher's slot count: how many
# rows a row-bucketed prefill call carries (DESIGN.md §10/§12).
_ROWS = LaneAxis("rows", "_row_buckets", per="slots")
_KBUCKET = LaneAxis("k_bucket", "_k_buckets")
_KVDTYPE = LaneAxis("kv_dtype", "_warm_kv_dtypes")
# Draft lanes carry their own storage-dtype ladder: an int8 draft cache can
# pair with an fp32 verify pool (DESIGN.md §16) without multiplying the
# verify lanes' fan-out. The axis *name* is distinct from the pool lanes'
# "kv_dtype" so a warmup pin on the pool dtype never pins the draft's.
_DRAFT_KVDTYPE = LaneAxis("draft_kv_dtype", "_warm_draft_kv_dtypes")
# The device topology as a trailing coordinate on every continuous lane
# (DESIGN.md §16): "DPxMP" mesh names, warmed like any bucket ladder, so a
# topology change is a rebind over compiled keys — never a compile.
_MESH = LaneAxis("mesh", "_warm_meshes")

BURST = LANES.register(LaneSpec(
    name="burst", role="decode",
    axes=(LaneAxis("batch_bucket"), LaneAxis("mode")),
    builder="_build_burst_decode",
    engines=frozenset({"burst"}),
    doc="Per-burst decode: sampling mode baked into the executable "
        "(the paper's construct; built on demand by set_mode, no warm "
        "fan-out).",
))

CB = LANES.register(LaneSpec(
    name="cb", role="decode",
    axes=(_SLOTS, _MESH),
    builder="_build_slot_decode", warmer="_warm_cb",
    engines=frozenset({"dense"}),
    doc="Dense continuous decode: one executable per slot count, sampling "
        "params as data (DESIGN.md §4).",
))

CBP = LANES.register(LaneSpec(
    name="cbp", role="decode",
    axes=(_SLOTS, _PAGES, _KVDTYPE, _MESH),
    builder="_build_paged_slot_decode", warmer="_warm_cbp",
    engines=frozenset({"paged"}),
    doc="Paged continuous decode: capacity bucket + page dtype as "
        "semi-static coordinates (DESIGN.md §9/§12).",
))

PF = LANES.register(LaneSpec(
    name="pf", role="prefill",
    axes=(_SLOTS, _CHUNK, _KVDTYPE, _MESH),
    builder="_build_paged_prefill", warmer="_warm_pf",
    engines=frozenset({"paged"}), enabled="_supports_chunked_prefill",
    slice="prefill",
    doc="Paged chunked prefill, batched: every prefilling slot the budget "
        "covers rides one call (DESIGN.md §10/§12).",
))

PFR = LANES.register(LaneSpec(
    name="pfr", role="prefill",
    axes=(_SLOTS, _ROWS, _KVDTYPE, _MESH),
    builder="_build_paged_prefill_rows", warmer="_warm_pfr",
    engines=frozenset({"paged"}), enabled="_row_prefill_enabled",
    slice="prefill",
    doc="Paged chunked prefill sized by the slots that prefill: the "
        "chunks packed into a row bucket below the slot count, at the top "
        "chunk bucket; per-slot inputs and outputs stay [slots] "
        "(DESIGN.md §10/§12).",
))

PFD = LANES.register(LaneSpec(
    name="pfd", role="prefill",
    axes=(_SLOTS, _CHUNK, _MESH),
    builder="_build_slot_prefill", warmer="_warm_pfd",
    engines=frozenset({"dense"}), enabled="_supports_chunked_prefill",
    slice="prefill",
    doc="Dense chunked prefill, batched (DESIGN.md §10).",
))

VF = LANES.register(LaneSpec(
    name="vf", role="verify",
    axes=(_SLOTS, _KBUCKET, _KVDTYPE, _MESH),
    builder="_build_paged_verify", warmer="_warm_vf",
    engines=frozenset({"paged"}), enabled="_spec_lanes_enabled",
    doc="Paged verify: K+1 window through the chunk path (DESIGN.md §11).",
))

VFD = LANES.register(LaneSpec(
    name="vfd", role="verify",
    axes=(_SLOTS, _KBUCKET, _MESH),
    builder="_build_slot_verify", warmer="_warm_vfd",
    engines=frozenset({"dense"}), enabled="_spec_lanes_enabled",
    doc="Dense verify (DESIGN.md §11).",
))

DR = LANES.register(LaneSpec(
    name="dr", role="draft",
    axes=(_SLOTS, _KBUCKET, _DRAFT_KVDTYPE, _MESH),
    builder="_build_draft", warmer="_warm_dr",
    engines=frozenset({"dense", "paged"}), enabled="_spec_lanes_enabled",
    doc="Draft lane: K scanned decode steps of the truncated-layer view "
        "(DESIGN.md §11; the draft cache is dense for both engines).",
))

DRP = LANES.register(LaneSpec(
    name="drp", role="draft",
    axes=(_SLOTS, _CHUNK, _DRAFT_KVDTYPE, _MESH),
    builder="_build_draft_prefill", warmer="_warm_drp",
    engines=frozenset({"dense", "paged"}), enabled="_spec_lanes_enabled",
    slice="prefill",
    doc="Draft prompt mirror: chunked dense ingestion over the draft view "
        "(DESIGN.md §11).",
))

MG = LANES.register(LaneSpec(
    name="mg", role="migrate",
    axes=(LaneAxis("op", "_mg_ops"), _KVDTYPE, _MESH),
    builder="_build_migrate", warmer="_warm_mg",
    engines=frozenset({"paged"}), enabled="_disagg_lanes_enabled",
    doc="KV-page migration transport (DESIGN.md §17): gather pages out of "
        "one pool's cache tree / scatter them into another's, per fixed "
        "page-index bucket. Warmed over the mesh ladder so both slices of "
        "a DisaggPlan carry compiled gather+scatter cells.",
))
