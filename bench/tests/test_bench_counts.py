"""Operations and bytes from shapes, against hand-computed values at the
published widths, and the peak table."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counts  # noqa: E402

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def shapes(name):
    return counts.Shapes.of(json.loads((CONFIGS / f"{name}.json").read_text())["model"])


def test_olmo_1b_sizes():
    s = shapes("olmo-1b")
    # per layer: q, o 2048x2048 each; k, v 2048x2048 each; 3 x 2048x8192
    assert s.layer_params == 67_108_864
    assert s.nonembed_params == 1_073_741_824
    assert s.head_params == 103_022_592  # tied: the embedding, read once
    assert s.weight_bytes == 2_353_528_832
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
    assert s.layer_params == 692_060_160
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
