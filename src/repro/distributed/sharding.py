"""Rule-based sharding: param/optimizer/activation PartitionSpecs per mesh.

Strategy (DESIGN.md §5):
  * TP over "model": attention heads / mlp ffn / experts / vocab
  * FSDP over "data": the d_model-ish dim of every weight
  * DP over ("pod","data") for the batch; ZeRO-over-pod optionally upgrades the
    FSDP dim of optimizer moments to ("data","pod")
  * divisibility-checked fallback chains — a dim is sharded only if the mesh
    axis divides it, so every assigned arch (40-head qwen3, 49155-vocab
    granite, ...) resolves without uneven sharding

Rules are (path-regex, [(dim_from_right, [axis candidates])...]) resolved
greedily in listed order; each mesh axis is used at most once per tensor.
"""

from __future__ import annotations

import contextlib
import contextvars
import re
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig

Axes = Any  # str | tuple[str, ...]

# (regex over "a/b/c" param path, [(neg dim index, [candidates in priority])])
PARAM_RULES: list[tuple[str, list[tuple[int, list[Axes]]]]] = [
    (r"embed/embedding$", [(-2, ["model"]), (-1, [("data", "model"), "data"])]),
    (r"head/lm_head$", [(-1, ["model"]), (-2, [("data", "model"), "data"])]),
    (r"attn/w[qkv]$", [(-2, ["model"]), (-3, ["data"])]),
    (r"attn/wo$", [(-3, ["model"]), (-1, ["data"])]),
    (r"attn/[qk]_scale$", []),
    (r"mlp/w_(gate|up)$", [(-1, ["model"]), (-2, ["data"])]),
    (r"mlp/w_down$", [(-2, ["model"]), (-1, ["data"])]),
    (r"moe/router$", [(-2, ["data"])]),
    (r"moe/w_(gate|up)$", [(-3, ["model"]), (-1, ["model"]), (-2, ["data"])]),
    (r"moe/w_down$", [(-3, ["model"]), (-2, ["model"]), (-1, ["data"])]),
    (r"ssm/w[zx]$", [(-1, ["model"]), (-2, ["data"])]),
    (r"ssm/w[BC]$", [(-2, ["data"])]),
    (r"ssm/wdt$", [(-1, ["model"]), (-2, ["data"])]),
    (r"ssm/conv$", [(-1, ["model"])]),
    (r"ssm/out$", [(-2, ["model"]), (-1, ["data"])]),
]


def _axes_in_mesh(cand: Axes, mesh: Mesh) -> tuple[str, ...] | None:
    names = (cand,) if isinstance(cand, str) else tuple(cand)
    if all(n in mesh.axis_names for n in names):
        return names
    return None


def _axes_size(names: Sequence[str], mesh: Mesh) -> int:
    s = 1
    for n in names:
        s *= mesh.shape[n]
    return s


def _resolve(
    shape: tuple[int, ...],
    rule: list[tuple[int, list[Axes]]],
    mesh: Mesh,
) -> P:
    assign: dict[int, tuple[str, ...]] = {}
    used: set[str] = set()
    for neg_dim, candidates in rule:
        dim = len(shape) + neg_dim
        if dim < 0:
            continue  # tensor has fewer dims than the rule expects
        for cand in candidates:
            names = _axes_in_mesh(cand, mesh)
            if names is None or any(n in used for n in names):
                continue
            if shape[dim] % _axes_size(names, mesh) == 0 and shape[dim] > 0:
                assign[dim] = names
                used.update(names)
                break
    parts = [
        (assign[d][0] if len(assign.get(d, ())) == 1 else assign.get(d))
        for d in range(len(shape))
    ]
    return P(*[p if p else None for p in parts])


def _path_str(path) -> str:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return "/".join(out)


def param_pspec_tree(params_shape: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree for a param tree (of ShapeDtypeStructs or arrays)."""

    def one(path, leaf):
        pstr = _path_str(path)
        for pat, rule in PARAM_RULES:
            if re.search(pat, pstr):
                return _resolve(tuple(leaf.shape), rule, mesh)
        return P()  # norms, scalars, biases: replicated

    return jax.tree_util.tree_map_with_path(one, params_shape)


def opt_pspec_tree(
    cfg: ArchConfig, param_specs: Any, params_shape: Any, mesh: Mesh
) -> Any:
    """Moment shardings = param shardings, optionally ZeRO'd over the pod axis."""
    if not (cfg.zero_over_pod and "pod" in mesh.axis_names):
        return param_specs

    def upgrade(spec: P, leaf) -> P:
        parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for i, (p, size) in enumerate(zip(parts, leaf.shape)):
            names = (p,) if isinstance(p, str) else tuple(p or ())
            if "data" in names and "pod" not in names:
                new = names + ("pod",)
                if size % _axes_size(new, mesh) == 0:
                    parts[i] = new
                    return P(*parts)
        return P(*parts)

    return jax.tree.map(upgrade, param_specs, params_shape)


# -------------------------------------------------------------- activations
def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _first_divisible(size: int, chains: list[Axes], mesh: Mesh):
    for cand in chains:
        names = _axes_in_mesh(cand, mesh)
        if names and size % _axes_size(names, mesh) == 0:
            return names if len(names) > 1 else names[0]
    return None


def data_pspec(shape: tuple[int, ...], mesh: Mesh) -> P:
    """Inputs/labels [B, S, ...]: batch over (pod, data) when divisible."""
    b = _first_divisible(shape[0], [("pod", "data"), "data", "pod"], mesh)
    return P(*([b] + [None] * (len(shape) - 1)))


def cache_pspec_tree(cfg: ArchConfig, cache_shape: Any, mesh: Mesh) -> Any:
    """KV / SSM cache shardings (stacked [m, ...] leaves).

    KV [m,B,S,KH,dh]: batch over (pod,data) + seq over model; with B=1
    (long-context) the sequence dim takes every available axis instead.
    SSM conv [m,B,K-1,C] / state [m,B,H,P,N]: batch + channel/head over model.
    """

    def one(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        parts: list = [None] * len(shape)
        b = _first_divisible(shape[1], [("pod", "data"), "data"], mesh)
        parts[1] = b
        if pstr.endswith("/k") or pstr.endswith("/v"):
            seq_chains = (
                ["model"]
                if b is not None
                else [("pod", "data", "model"), ("data", "model"), "model"]
            )
            parts[2] = _first_divisible(shape[2], seq_chains, mesh)
        elif pstr.endswith("/conv"):
            parts[3] = _first_divisible(shape[3], ["model"], mesh)
        elif pstr.endswith("/state"):
            parts[2] = _first_divisible(shape[2], ["model"], mesh)
        return P(*parts)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


# ---------------------------------------------------- activation hints
# GSPMD alone happily replicates the batch inside a scanned layer body and
# shards contraction dims instead (verified in the dry-run: attention ran with
# the full global batch per device). Production frameworks pin activations
# with with_sharding_constraint; model code calls hint() with semantic dim
# names and the ambient mesh (set by the step builders) resolves them — or
# no-ops entirely outside a mesh context (CPU unit tests).

_MESH_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_shard_mesh", default=None
)


@contextlib.contextmanager
def use_shard_hints(mesh: Mesh | None):
    tok = _MESH_CTX.set(mesh)
    try:
        yield
    finally:
        _MESH_CTX.reset(tok)


def hint(x: jax.Array, *names: str | None) -> jax.Array:
    """Constrain activation sharding by semantic dim names.

    names per dim: "batch" -> ("pod","data"); "model" -> "model";
    "data" -> "data"; None -> unconstrained. Dims that don't divide the axis
    size are silently left unconstrained (qwen3's 40 heads, batch=1 decode).
    """
    mesh = _MESH_CTX.get()
    if mesh is None or len(names) != x.ndim:
        return x
    parts: list = []
    used: set[str] = set()
    for dim, name in enumerate(names):
        assigned = None
        if name == "batch":
            axes = tuple(
                a for a in ("pod", "data")
                if a in mesh.axis_names and a not in used
            )
            if axes and x.shape[dim] % _axes_size(axes, mesh) == 0:
                assigned = axes if len(axes) > 1 else axes[0]
        elif name in ("model", "data", "pod"):
            if (
                name in mesh.axis_names
                and name not in used
                and x.shape[dim] % mesh.shape[name] == 0
            ):
                assigned = name
        if assigned is not None:
            used.update((assigned,) if isinstance(assigned, str) else assigned)
        parts.append(assigned)
    if all(p is None for p in parts):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts))
    )


def hint_attn_q(q: jax.Array) -> jax.Array:
    """Shard full-seq attention q [B,S,H,dh]: heads over model when divisible,
    else (perf opt, seq_shard_fallback) the *query sequence* over model —
    context-parallel attention for 40-head qwen3 / 24-head musicgen /
    14-head internvl2, where head TP is impossible on a 16-way axis."""
    from repro import perf

    mesh = _MESH_CTX.get()
    if mesh is None or q.ndim != 4:
        return q
    model = mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1
    if model > 1 and q.shape[2] % model == 0:
        return hint(q, "batch", None, "model", None)
    if (
        perf.current().seq_shard_fallback
        and model > 1
        and q.shape[1] % model == 0
    ):
        return hint(q, "batch", "model", None, None)
    return hint(q, "batch", None, None, None)


def to_named(tree_of_pspecs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_of_pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ------------------------------------------------------- serving mesh plans
# The serving engine treats the device topology as a dispatch coordinate
# (DESIGN.md §16): every lane executable is AOT-compiled per mesh *name*
# ("1x1", "1x2", "2x2", ... = data x model) and a topology change at run
# time is a hot-slot flip plus a device_put of the live cache — never a
# compile. A MeshPlan owns the NamedSharding trees for one such name.

SERVING_AXES = ("data", "model")


def parse_mesh_name(name: str) -> tuple[int, int]:
    """"2x2" / "2,2" -> (dp, mp). dp shards slots/pages, mp shards params.

    Offset slice names ("1x1@1", DESIGN.md §17) parse to the same (dp, mp)
    shape — callers that only care about the mesh *shape* (pool shard
    derivation, ladder fan-out) see slices and plain meshes uniformly; use
    :func:`parse_slice_name` when the device offset matters."""
    return parse_slice_name(name)[:2]


def parse_slice_name(name: str) -> tuple[int, int, int]:
    """"DPxMP[@OFF]" -> (dp, mp, off). A mesh *slice* (DESIGN.md §17) is an
    ordinary DPxMP mesh placed at device offset OFF instead of device 0 —
    the coordinate disaggregated prefill/decode pins its lane groups to.
    Plain names carry offset 0."""
    body, _, off_s = str(name).strip().lower().partition("@")
    parts = re.split(r"[x,]", body)
    if len(parts) != 2:
        raise ValueError(
            f"mesh name must be 'DPxMP[@OFF]' (e.g. '1x2', '1x1@1'), "
            f"got {name!r}"
        )
    try:
        dp, mp = int(parts[0]), int(parts[1])
        off = int(off_s) if off_s else 0
    except ValueError as e:
        raise ValueError(
            f"mesh name must be 'DPxMP[@OFF]', got {name!r}"
        ) from e
    if dp < 1 or mp < 1:
        raise ValueError(f"mesh sizes must be >= 1, got {name!r}")
    if off < 0:
        raise ValueError(f"mesh offset must be >= 0, got {name!r}")
    return dp, mp, off


def mesh_name(dp: int, mp: int, off: int = 0) -> str:
    return f"{dp}x{mp}" if off == 0 else f"{dp}x{mp}@{off}"


class MeshPlan:
    """Sharding plan for one serving-mesh coordinate.

    ``single`` plans ("1x1") carry no jax Mesh at all: builders take the
    exact unsharded code path, which is what makes the 1x1 lane bitwise
    identical to the pre-mesh engine. Non-single plans lazily build a
    ``Mesh((dp, mp), ("data", "model"))`` over the first dp*mp devices
    (redco-style dp/mp) and hand out NamedSharding trees for params,
    caches, and per-slot row arrays.

    Offset slices ("1x1@1", DESIGN.md §17) are *never* single even at
    dp=mp=1 — they must not take the default-device path — but a
    one-device slice is ``solo``: its executables lower through plain
    ``jax.jit`` pinned to ``devices[off]`` via ``SingleDeviceSharding``
    rather than under a one-device Mesh. GSPMD adds real per-call cost
    (sharded in/out wrappers, slower D2H) that a one-device slice gets
    nothing for; the pinned plain path keeps prefill-slice calls as cheap
    as default-device ones.
    """

    def __init__(self, name: str):
        self.dp, self.mp, self.offset = parse_slice_name(name)
        self.name = mesh_name(self.dp, self.mp, self.offset)
        self._mesh: Mesh | None = None

    @property
    def single(self) -> bool:
        return self.dp == 1 and self.mp == 1 and self.offset == 0

    @property
    def solo(self) -> bool:
        """One-device plan at any offset: no Mesh, no GSPMD — plain jit
        pinned to ``self.device`` (``single`` plans skip even the pin)."""
        return self.dp == 1 and self.mp == 1

    @property
    def device(self):
        """The pinned device of a solo plan."""
        avail = len(jax.devices())
        if self.offset >= avail:
            raise ValueError(
                f"mesh {self.name!r} needs device {self.offset}, only "
                f"{avail} visible (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N for CPU runs)"
            )
        return jax.devices()[self.offset]

    @property
    def num_devices(self) -> int:
        return self.dp * self.mp

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            avail = len(jax.devices())
            if self.offset + self.num_devices > avail:
                raise ValueError(
                    f"mesh {self.name!r} needs devices "
                    f"[{self.offset}, {self.offset + self.num_devices}), "
                    f"only {avail} visible (set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=N for CPU runs)"
                )
            if self.offset == 0:
                self._mesh = jax.make_mesh(
                    (self.dp, self.mp), SERVING_AXES,
                    axis_types=(AxisType.Auto,) * 2,
                )
            else:
                devs = np.asarray(
                    jax.devices()[self.offset : self.offset + self.num_devices]
                ).reshape(self.dp, self.mp)
                self._mesh = Mesh(
                    devs, SERVING_AXES, axis_types=(AxisType.Auto,) * 2
                )
        return self._mesh

    # --- spec builders (all return NamedSharding trees / values) ---
    def _named(self, spec_tree: Any) -> Any:
        return to_named(spec_tree, self.mesh)

    def param_shardings(self, params_shape: Any) -> Any:
        """TP-only param shardings: PARAM_RULES with the FSDP ('data')
        assignments stripped — serving replicates weights across the data
        axis; only the 'model' axis splits them."""
        specs = param_pspec_tree(params_shape, self.mesh)
        return self._named(
            jax.tree.map(
                lambda s: _strip_axes(s, ("data", "pod")),
                specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        )

    def row_sharding(self, shape: tuple[int, ...]) -> NamedSharding:
        """Per-slot arrays (tok [S,1], pos [S], bt [S,PB], keys [S,2], ...):
        slots over 'data' when divisible, else replicated."""
        parts: list = [None] * len(shape)
        if shape and shape[0] % self.dp == 0:
            parts[0] = "data"
        return NamedSharding(self.mesh, P(*parts))

    def row_shardings(self, avals: Sequence[Any]) -> tuple:
        return tuple(self.row_sharding(tuple(a.shape)) for a in avals)

    def dense_cache_shardings(self, cache_shape: Any) -> Any:
        """Dense per-slot caches (leaves stacked [m, S, ...]): slots over
        'data'; attention KV [m,S,L,KH,dh] also takes heads over 'model'
        when divisible (falling back to the seq dim, flash-decode style)."""

        def one(path, leaf):
            shape = tuple(leaf.shape)
            parts: list = [None] * len(shape)
            if len(shape) >= 2 and shape[1] % self.dp == 0:
                parts[1] = "data"
            pstr = _path_str(path)
            if pstr.endswith("/k") or pstr.endswith("/v"):
                if len(shape) == 5 and shape[3] % self.mp == 0:
                    parts[3] = "model"
                elif len(shape) == 5 and shape[2] % self.mp == 0:
                    parts[2] = "model"
            return P(*parts)

        return self._named(
            jax.tree_util.tree_map_with_path(one, cache_shape)
        )

    def paged_cache_shardings(self, cache_shape: Any) -> Any:
        """Paged pools (kv leaves [m, P, ps, KH, dh], int8 scale leaves
        [m, P, ps]): the physical page axis over 'data' (the host-side
        pool hands each shard a contiguous page block, kvcache.py), heads
        over 'model' when divisible."""

        def one(leaf):
            shape = tuple(leaf.shape)
            parts: list = [None] * len(shape)
            if len(shape) >= 2 and shape[1] % self.dp == 0:
                parts[1] = "data"
            if len(shape) == 5 and shape[3] % self.mp == 0:
                parts[3] = "model"
            return P(*parts)

        return self._named(jax.tree.map(one, cache_shape))

    def __repr__(self) -> str:
        return f"MeshPlan({self.name!r})"


@dataclass(frozen=True)
class DisaggPlan:
    """Disaggregated prefill/decode placement (DESIGN.md §17).

    Two warmed mesh slices out of one device fleet: the prefill lanes
    (``pf``/``pfd``/``drp`` — ``LaneSpec.slice == "prefill"``) pin to
    ``prefill``, everything else (decode/draft/verify/burst) to ``decode``.
    Both names must sit in the ``EngineConfig.meshes`` warm ladder so every
    lane×slice cell is AOT-compiled; the split itself is then a semi-static
    rebind (``set_disagg``) — flipping which slice the prefill dispatch
    closures read, never a compile.
    """

    prefill: str  # slice name the prefill lanes pin to (e.g. "1x1@1")
    decode: str  # slice name the decode/draft/verify lanes pin to

    def __post_init__(self) -> None:
        pf, dec = MeshPlan(self.prefill), MeshPlan(self.decode)
        pf_devs = set(range(pf.offset, pf.offset + pf.num_devices))
        dec_devs = set(range(dec.offset, dec.offset + dec.num_devices))
        if pf_devs & dec_devs:
            raise ValueError(
                f"disagg slices overlap: prefill {self.prefill!r} and "
                f"decode {self.decode!r} share devices "
                f"{sorted(pf_devs & dec_devs)}"
            )
        object.__setattr__(self, "prefill", pf.name)
        object.__setattr__(self, "decode", dec.name)

    @classmethod
    def split(cls, base: "MeshPlan | str") -> "DisaggPlan":
        """Derive the canonical split from a base mesh: the last data-
        parallel row becomes the prefill slice, the rest keep decoding.
        A 2x1 base splits into decode "1x1" + prefill "1x1@1" — the
        two-fake-device CPU harness's shape."""
        plan = base if isinstance(base, MeshPlan) else MeshPlan(base)
        if plan.dp < 2:
            raise ValueError(
                f"disagg split needs dp >= 2 on the base mesh, got "
                f"{plan.name!r} (one data row must become the prefill slice)"
            )
        dec_dp = plan.dp - 1
        return cls(
            prefill=mesh_name(1, plan.mp, plan.offset + dec_dp * plan.mp),
            decode=mesh_name(dec_dp, plan.mp, plan.offset),
        )


def _strip_axes(spec: P, drop: tuple[str, ...]) -> P:
    parts: list = []
    for p in tuple(spec):
        names = (p,) if isinstance(p, str) else tuple(p or ())
        keep = tuple(n for n in names if n not in drop)
        parts.append(keep[0] if len(keep) == 1 else (keep or None))
    return P(*parts)
