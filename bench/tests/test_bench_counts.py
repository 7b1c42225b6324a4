"""Operations and bytes from shapes, against hand-computed values at the
published widths, for windowed and routed layers, and the peak table."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counts  # noqa: E402

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
DATA = Path(__file__).resolve().parent / "data"


def shapes(name, where=CONFIGS):
    return counts.Shapes.of(json.loads((where / f"{name}.json").read_text())["model"])


def test_olmo_1b_sizes():
    s = shapes("olmo-1b")
    # per layer: q, o 2048x2048 each; k, v 2048x2048 each; 3 x 2048x8192
    assert s.attn_params + s.mlp_params == 67_108_864
    assert s.nonembed_params == 1_073_741_824
    assert s.head_params == 103_022_592  # tied: the embedding, read once
    # every weight once, in bf16
    assert (s.nonembed_params + s.head_params) * s.bytes_per_el == 2_353_528_832
    assert s.kv_bytes_per_token == 131_072  # 16 layers x K,V x 2048 x 2 B


def test_olmo_1b_decode_call():
    w = counts.decode_call(shapes("olmo-1b"), [100, 200])
    # 2 rows x 2N + 4 x 16 x 16 x 128 x 300 pairs + 2 rows x 2 x head
    assert w.flops == 4_746_379_264
    # weights + 300 K/V rows read + 2 written + 2 embedding rows
    assert w.bytes == 2_393_120_768
    assert counts.decode_call(shapes("olmo-1b"), []) == counts.ZERO


def test_deepseek_67b_sizes():
    s = shapes("deepseek-67b")
    assert s.attn_params + s.mlp_params == 692_060_160
    assert s.nonembed_params == 2_768_240_640  # 4 of 95 layers
    assert s.head_params == 838_860_800
    assert s.kv_bytes_per_token == 16_384  # 4 layers x K,V x 8 x 128 x 2 B


def test_deepseek_67b_prefill_call():
    s = shapes("deepseek-67b")
    w = counts.prefill_call(s, [(0, 64, False), (1000, 24, True)])
    # 88 tokens x 2N; pairs 64*65/2 + 24*1000 + 24*25/2 = 26380; one head row
    assert w.flops == 492_345_753_600
    # weights + 1000 context rows read + 88 rows written + 88 embedding rows
    assert w.bytes == 7_233_470_464


def test_roofline_time_takes_the_larger_bound():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 1.97e14
    assert peak["hbm_bytes_per_s"] == 8.19e11
    memory_bound = counts.Work(flops=1e9, bytes=8.19e11)
    assert memory_bound.seconds(peak) == pytest.approx(1.0)
    compute_bound = counts.Work(flops=1.97e14, bytes=1.0)
    assert compute_bound.seconds(peak) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    assert "source" in counts.peaks("TPU v5 lite")


# Each dense configuration's calls as the counts gave them before layers were
# counted by kind: (flops, bytes) for three decode calls and three prefill
# calls, the last a full one (31 rows at the context and one short; eight
# 256-token chunks of one prompt and a short prompt).
DENSE_CALLS = {
    "olmo-1b": {
        "decode": [
            (2353659904.0, 2353795072.0),
            (4746379264.0, 2393120768.0),
            (83636649984.0, 10681581568.0),
        ],
        "prefill": [
            (2353659904.0, 2353664000.0),
            (192642285568.0, 2496495616.0),
            (4684210110464.0, 3570552832.0),
        ],
    },
    "deepseek-67b": {
        "decode": [
            (7214333952.0, 7214252032.0),
            (14467727360.0, 7219183616.0),
            (239178219520.0, 8255717376.0),
        ],
        "prefill": [
            (7214333952.0, 7214235648.0),
            (492345753600.0, 7233470464.0),
            (11644765601792.0, 7398916096.0),
        ],
    },
}
DECODE_KEYS = [[1], [100, 200], [2048] * 31 + [17]]
PREFILL_SEGMENTS = [
    [(0, 1, True)],
    [(0, 64, False), (1000, 24, True)],
    [(256 * i, 256, i == 7) for i in range(8)] + [(0, 5, True)],
]


@pytest.mark.parametrize("name", sorted(DENSE_CALLS))
@pytest.mark.parametrize("case", range(3))
def test_dense_calls_count_as_before(name, case):
    s = shapes(name)
    assert s.stack() == [(None, False, s.layers)]
    w = counts.decode_call(s, DECODE_KEYS[case])
    assert (w.flops, w.bytes) == DENSE_CALLS[name]["decode"][case]
    w = counts.prefill_call(s, PREFILL_SEGMENTS[case])
    assert (w.flops, w.bytes) == DENSE_CALLS[name]["prefill"][case]


def test_capped_sum_by_hand():
    assert counts._capped_sum(1, 10, None) == 55
    assert counts._capped_sum(1, 10, 4) == 1 + 2 + 3 + 4 * 7
    assert counts._capped_sum(30, 40, 24) == 24 * 11
    assert counts._capped_sum(20, 30, 24) == sum(range(20, 25)) + 24 * 6
    assert counts._capped_sum(5, 4, 3) == 0


def test_patterned_stack_by_kind():
    s = shapes("tiny-patterned", DATA)
    # three windowed layers and one full one, each routed over 4 experts
    assert sorted(s.stack(), key=str) == [(24, True, 3), (None, True, 1)]
    assert s.expert_params == 3 * 64 * 32 and s.router_params == 64 * 4
    # 4 x (attention 2*64*64 + 2*64*32, router 64*4, 4 experts of 3*64*32)
    assert s.nonembed_params == 4 * (12_288 + 256 + 24_576)
    olmo = json.loads((CONFIGS / "olmo-1b.json").read_text())["model"]
    halves = counts.Shapes.of(
        {**olmo, "layer_pattern": ["attn_local", "attn"], "sliding_window": 1024}
    )
    assert halves.stack() == [(1024, False, 8), (None, False, 8)]


def test_windowed_decode_row_by_hand():
    """One row at position 99 (100 keys) of tiny-patterned: the three
    windowed layers see the last 24 keys, the full layer all 100."""
    s = shapes("tiny-patterned", DATA)
    w = counts.decode_call(s, [100])
    attn, kv_row = 12_288, 2 * 2 * 16 * 4  # K and V, 2 heads x 16, float32
    router, expert = 256, 6_144
    touched = 4 * (1 - (1 - 2 / 4) ** 1)  # one token: its 2 experts
    assert touched == 2.0
    per_layer_flops = 2 * attn + 2 * router + 2 * 2 * expert  # one token
    flops = (
        4 * per_layer_flops
        + 4 * 4 * 16 * (3 * 24 + 100)  # QK^T and PV, 4 heads x 16
        + 2 * 64 * 4096  # the head
    )
    nbytes = (
        64 * 4096 * 4 + 64 * 4  # head, the embedding row
        + 4 * (attn * 4 + router * 4 + touched * expert * 4)
        + (3 * (24 + 1) + (100 + 1)) * kv_row  # K/V read, the new row written
    )
    assert w.flops == float(flops)
    assert w.bytes == pytest.approx(nbytes, rel=1e-15)


def test_routed_prefill_call_by_hand():
    """A chunk of 16 tokens from position 30 and a whole 8-token prompt:
    24 tokens touch E(1 - (1 - k/E)^24) of the 4 experts in each layer."""
    s = shapes("tiny-patterned", DATA)
    w = counts.prefill_call(s, [(30, 16, False), (0, 8, True)])
    attn, kv_row, router, expert = 12_288, 256, 256, 6_144
    tokens = 24
    touched = 4 * (1 - 0.5 ** 24)
    # keys: full layer 31..46 and 1..8; windowed layers 24 each, and 1..8
    full_pairs = sum(range(31, 47)) + sum(range(1, 9))
    local_pairs = 24 * 16 + sum(range(1, 9))
    flops = (
        4 * tokens * (2 * attn + 2 * router + 2 * 2 * expert)
        + 4 * 4 * 16 * (3 * local_pairs + full_pairs)
        + 2 * 64 * 4096  # one prompt ends: one head row
    )
    # earlier rows read: 30 on the full layer, 23 (W - 1) on a windowed one
    nbytes = (
        64 * 4096 * 4 + tokens * 64 * 4
        + 4 * (attn * 4 + router * 4 + touched * expert * 4)
        + (3 * (23 + tokens) + (30 + tokens)) * kv_row
    )
    assert w.flops == float(flops)
    assert w.bytes == pytest.approx(nbytes, rel=1e-15)
