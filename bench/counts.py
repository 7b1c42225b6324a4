"""Operations and bytes that serving work requires, from shapes alone.

These count the algorithm's work, not the implementation's: a decode row
reads the weights once (shared by the batch), its live context's K/V and
writes one new K/V row; a prefill token costs two operations per
non-embedding parameter it uses plus its causal attention, and a prompt's
last row pays the output head. Gather padding, idle batch rows and per-step
copies of the page pool are not work and are not counted, so a faster
implementation raises a share without changing the yardstick. Peaks come
from ``peaks.json``, keyed by the device kind JAX reports.

Each layer is counted by its kind, as the configuration's
``layer_pattern`` and ``mlp_pattern`` cycle over the layers:

- ``attn``: a token at position p attends its p + 1 keys.
- ``attn_local`` (window W): a token at position p attends min(p + 1, W)
  keys, so a decode row reads min(context, W) K/V rows and a prefill
  segment from ``start`` reads the min(start, W - 1) earlier rows its first
  token reaches. Every row is still written.
- ``mlp``: the SwiGLU MLP, 3·d·f weights, all read and all used.
- ``moe`` (E experts, top k, width f): per token the float32 router,
  2·d·E operations, and k experts, 3·2·d·f each. A call reads the router
  and the experts its T tokens touch. Routing is taken as uniform, each
  token picking k distinct experts: an expert escapes one token with
  probability 1 - k/E, so E·(1 - (1 - k/E)^T) experts are touched. A
  skewed router touches fewer, so for it the count is an upper bound.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
ROUTER_BYTES = 4  # the router is kept in float32 whatever the served dtype


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def period(model: dict) -> list[tuple[str, str]]:
    """The (mixer, mlp) kind of each layer of one period of ``model``: the
    two patterns cycled to their least common length (one dense
    ``("attn", "mlp")`` layer where the file gives neither)."""
    mixers = tuple(model.get("layer_pattern", ("attn",)))
    mlps = tuple(model.get("mlp_pattern", ("mlp",)))
    n = math.lcm(len(mixers), len(mlps))
    return [(mixers[i % len(mixers)], mlps[i % len(mlps)]) for i in range(n)]


@dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    bytes_per_el: int  # the served dtype's width
    kinds: tuple = (("attn", "mlp"),)  # one period, see ``period``
    window: int | None = None  # attn_local layers' keys
    experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0

    @classmethod
    def of(cls, model: dict) -> "Shapes":
        return cls(
            layers=model["num_layers"], d_model=model["d_model"],
            heads=model["num_heads"], kv_heads=model["num_kv_heads"],
            head_dim=model["head_dim"], d_ff=model["d_ff"],
            vocab=model["vocab_size"],
            bytes_per_el=DTYPE_BYTES[model["dtype"]],
            kinds=tuple(period(model)),
            window=model.get("sliding_window"),
            experts=model.get("num_experts", 0),
            top_k=model.get("top_k", 0),
            expert_d_ff=model.get("expert_d_ff", 0) or model["d_ff"],
        )

    def stack(self) -> list[tuple[int | None, bool, int]]:
        """(window or None, routed, number of layers) per layer kind."""
        reps = self.layers // len(self.kinds)
        out: dict[tuple, int] = {}
        for mixer, mlp in self.kinds:
            key = (self.window if mixer == "attn_local" else None, mlp == "moe")
            out[key] = out.get(key, 0) + reps
        return [(w, routed, n) for (w, routed), n in out.items()]

    @property
    def attn_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim
        return 2 * d * q + 2 * d * kv

    @property
    def nonembed_params(self) -> int:
        return sum(
            n * (self.attn_params + (
                self.router_params + self.experts * self.expert_params
                if routed else self.mlp_params
            ))
            for _, routed, n in self.stack()
        )

    @property
    def mlp_params(self) -> int:
        return 3 * self.d_model * self.d_ff

    @property
    def router_params(self) -> int:
        return self.d_model * self.experts

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.expert_d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def kv_row_bytes(self) -> int:
        """K and V of one position in one layer."""
        return 2 * self.kv_heads * self.head_dim * self.bytes_per_el

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over every layer."""
        return self.layers * self.kv_row_bytes

    def touched_experts(self, tokens: int) -> float:
        """Experts a call of ``tokens`` tokens reads, under uniform routing."""
        return self.experts * (1.0 - (1.0 - self.top_k / self.experts) ** tokens)

    def layer_work(self, routed: bool, tokens: int, keys: int, kv_read: int):
        """(operations, bytes) of one layer over a call's ``tokens`` tokens,
        which attend ``keys`` (query, key) pairs and read ``kv_read`` K/V
        rows from earlier calls or the live context."""
        b = self.bytes_per_el
        flops = 2 * self.attn_params * tokens + 4 * self.heads * self.head_dim * keys
        nbytes = self.attn_params * b + (kv_read + tokens) * self.kv_row_bytes
        if routed:
            flops += tokens * (2 * self.router_params + self.top_k * 2 * self.expert_params)
            nbytes += (self.router_params * ROUTER_BYTES
                       + self.touched_experts(tokens) * self.expert_params * b)
        else:
            flops += 2 * self.mlp_params * tokens
            nbytes += self.mlp_params * b
        return flops, nbytes


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def seconds(self, peak: dict) -> float:
        """The least time the chip could take: the larger of the two bounds."""
        return max(
            self.flops / peak["bf16_flops_per_s"],
            self.bytes / peak["hbm_bytes_per_s"],
        )


ZERO = Work(0.0, 0.0)


def _capped_sum(lo: int, hi: int, cap: int | None) -> int:
    """The sum of min(v, cap) over v = lo .. hi (no cap: of v)."""
    top = hi if cap is None else min(hi, cap)
    below = (lo + top) * (top - lo + 1) // 2 if top >= lo else 0
    return below + (0 if cap is None else cap * max(0, hi - max(lo - 1, cap)))


def decode_call(s: Shapes, keys: list[int]) -> Work:
    """One decode call over the rows in ``keys``: each row's new token
    attends ``keys[i]`` positions (its context, the new one included), or
    the last W of them on a windowed layer."""
    rows = len(keys)
    if rows == 0:
        return ZERO
    flops = 2 * s.head_params * rows
    nbytes = (
        s.head_params * s.bytes_per_el
        + rows * s.d_model * s.bytes_per_el  # embedding rows
    )
    for window, routed, n in s.stack():
        # the context read, the new row included
        seen = int(sum(keys if window is None else (min(k, window) for k in keys)))
        f, b = s.layer_work(routed, rows, seen, seen)
        flops += n * f
        nbytes += n * b
    return Work(float(flops), float(nbytes))


def prefill_call(s: Shapes, segments: list[tuple[int, int, bool]]) -> Work:
    """One prefill call over ``segments``: (start, count, ends_prompt) per
    request in the call. Token ``start + i`` attends ``start + i + 1``
    positions (at most W on a windowed layer); a segment that ends its
    prompt pays one head row."""
    if not segments:
        return ZERO
    tokens = sum(c for _, c, _ in segments)
    heads = sum(1 for _, _, last in segments if last)
    flops = 2 * s.head_params * heads
    nbytes = (
        s.head_params * s.bytes_per_el
        + tokens * s.d_model * s.bytes_per_el  # embedding rows
    )
    for window, routed, n in s.stack():
        pairs = sum(_capped_sum(st + 1, st + c, window) for st, c, _ in segments)
        # earlier chunks' K/V read
        earlier = sum(st if window is None else min(st, window - 1)
                      for st, _, _ in segments)
        f, b = s.layer_work(routed, tokens, pairs, earlier)
        flops += n * f
        nbytes += n * b
    return Work(float(flops), float(nbytes))
