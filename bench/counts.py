"""Operations and bytes that serving work requires, from shapes alone.

These count the algorithm's work, not the implementation's: a decode row
reads the weights once (shared by the batch), its live context's K/V and
writes one new K/V row; a prefill token costs two operations per
non-embedding parameter plus its causal attention, and a prompt's last row
pays the output head. Gather padding, idle batch rows and per-step copies of
the page pool are not work and are not counted, so a faster implementation
raises a share without changing the yardstick. Peaks come from
``peaks.json``, keyed by the device kind JAX reports.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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

    @classmethod
    def of(cls, model: dict) -> "Shapes":
        return cls(
            layers=model["num_layers"], d_model=model["d_model"],
            heads=model["num_heads"], kv_heads=model["num_kv_heads"],
            head_dim=model["head_dim"], d_ff=model["d_ff"],
            vocab=model["vocab_size"],
            bytes_per_el=DTYPE_BYTES[model["dtype"]],
        )

    @property
    def layer_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim
        return 2 * d * q + 2 * d * kv + 3 * d * self.d_ff

    @property
    def nonembed_params(self) -> int:
        return self.layers * self.layer_params

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over every layer."""
        return self.layers * 2 * self.kv_heads * self.head_dim * self.bytes_per_el

    @property
    def weight_bytes(self) -> int:
        """Weights one forward step reads: the layers and the head (a tied
        head is the embedding table, read once)."""
        return (self.nonembed_params + self.head_params) * self.bytes_per_el

    def attn_flops(self, keys: int) -> int:
        """QK^T and PV for ``keys`` (query, key) pairs over every layer."""
        return 4 * self.layers * self.heads * self.head_dim * keys


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


def decode_call(s: Shapes, keys: list[int]) -> Work:
    """One decode call over the rows in ``keys``: each row's new token
    attends ``keys[i]`` positions (its context, the new one included)."""
    rows, total = len(keys), int(sum(keys))
    if rows == 0:
        return ZERO
    flops = (
        2 * s.nonembed_params * rows + s.attn_flops(total)
        + 2 * s.head_params * rows
    )
    nbytes = (
        s.weight_bytes
        + total * s.kv_bytes_per_token  # context read (the new row included)
        + rows * s.kv_bytes_per_token  # new row written
        + rows * s.d_model * s.bytes_per_el  # embedding rows
    )
    return Work(float(flops), float(nbytes))


def prefill_call(s: Shapes, segments: list[tuple[int, int, bool]]) -> Work:
    """One prefill call over ``segments``: (start, count, ends_prompt) per
    request in the call. Token ``start + i`` attends ``start + i + 1``
    positions; a segment that ends its prompt pays one head row."""
    if not segments:
        return ZERO
    tokens = sum(c for _, c, _ in segments)
    pairs = sum(c * st + c * (c + 1) // 2 for st, c, _ in segments)
    heads = sum(1 for _, _, last in segments if last)
    context = sum(st for st, _, _ in segments)
    flops = (
        2 * s.nonembed_params * tokens + s.attn_flops(pairs)
        + 2 * s.head_params * heads
    )
    nbytes = (
        s.weight_bytes
        + context * s.kv_bytes_per_token  # earlier chunks' K/V read
        + tokens * s.kv_bytes_per_token  # this chunk's K/V written
        + tokens * s.d_model * s.bytes_per_el  # embedding rows
    )
    return Work(float(flops), float(nbytes))
