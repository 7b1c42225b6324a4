"""The traffic generator: seeded, the stated medians and clips, the same
work under every seed in another order, and arrivals, bursts and shared
prefixes as the mix file states them."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import loadgen  # noqa: E402

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def take(mix, n, seed, context=2048, vocab=50304):
    gen = loadgen.stream(mix, context=context, vocab=vocab, seed=seed)
    return [next(gen) for _ in range(n)]


def test_same_seed_same_stream_large_seed():
    mix = loadgen.load_mix(TRAFFIC, "chat")
    a = take(mix, 70, 2**31 + 17)
    b = take(mix, 70, 2**31 + 17)
    assert [(d.gap_s, d.new_tokens) for d in a] == [
        (d.gap_s, d.new_tokens) for d in b
    ]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = take(mix, 70, 2**31 + 18)
    assert [d.new_tokens for d in a] != [d.new_tokens for d in c]
    # another seed, other arrival times
    assert [d.gap_s for d in a] != [d.gap_s for d in c]


def test_every_seed_gets_the_same_block_of_work():
    mix = loadgen.load_mix(TRAFFIC, "chat")
    block = mix["block"]
    for lo in (0, block):
        sets = [
            sorted((len(d.prompt), d.new_tokens, round(d.gap_s, 12))
                   for d in take(mix, 2 * block, seed)[lo:lo + block])
            for seed in (1, 99, 2**33)
        ]
        lens = [sorted(x[0] for x in s) for s in sets]
        outs = [sorted(x[1] for x in s) for s in sets]
        gaps = [sorted(x[2] for x in s) for s in sets]
        assert lens[0] == lens[1] == lens[2]
        assert outs[0] == outs[1] == outs[2]
        assert gaps[0] == gaps[1] == gaps[2]


def test_chat_medians_and_clips():
    mix = loadgen.load_mix(TRAFFIC, "chat")
    ds = take(mix, mix["block"], 5)
    prompts = np.array([len(d.prompt) for d in ds])
    outs = np.array([d.new_tokens for d in ds])
    assert np.median(prompts) == 1020
    assert np.median(outs) == 129
    assert prompts.min() >= 32 and prompts.max() == 1536
    assert outs.min() >= 1 and outs.max() <= 512
    assert (prompts + outs).max() <= 2048
    # the block's gaps average to the stated rate
    rate = 1 / np.mean([d.gap_s for d in ds])
    assert rate == pytest.approx(mix["arrivals"]["rate_hz"], rel=1e-12)


def test_code_prompt_clip_follows_the_context():
    mix = loadgen.load_mix(TRAFFIC, "code")
    for context, top in ((4096, 3840), (2048, 1792)):
        ds = take(mix, mix["block"], 3, context=context)
        prompts = np.array([len(d.prompt) for d in ds])
        outs = np.array([d.new_tokens for d in ds])
        assert prompts.max() == top
        assert np.median(outs) == 13
        assert (prompts + outs).max() <= context
        assert prompts.min() >= 64


def test_tokens_lie_in_the_vocabulary():
    mix = loadgen.load_mix(TRAFFIC, "code")
    for d in take(mix, 20, 11, context=4096, vocab=102400):
        assert d.prompt.dtype == np.int32
        assert d.prompt.min() >= 0 and d.prompt.max() < 102400


@pytest.mark.parametrize("name", ["chat", "code"])
def test_every_mix_states_what_a_run_reads(name):
    mix = loadgen.load_mix(TRAFFIC, name)
    arr = mix["arrivals"]
    assert arr["process"] in ("poisson", "gamma", "closed")
    assert arr["clients"] if loadgen.is_closed(mix) else arr["rate_hz"] > 0
    assert mix["lead_in_s"] > 0 and mix["trace_slice_s"] > 0
    for law in (mix["prompt"], mix["output"]):
        assert 1 <= law["min"] <= law["median"] <= law["max"] and law["sigma"] > 0
    assert mix["check"]["max_requests"] >= 1 and mix["check"]["min_tokens"] >= 100


def test_stratified_order_is_a_permutation():
    for offset in (0, 5, 63):
        for stride in (1, 13, 37):
            order = loadgen.stratified_order(64, offset, stride)
            assert sorted(order) == list(range(64))
    with pytest.raises(ValueError):
        loadgen.stratified_order(48, 0, 1)


def test_a_short_run_of_requests_sees_nearly_the_same_work_under_any_seed():
    mix = loadgen.load_mix(TRAFFIC, "chat")
    means = []
    for seed in (1, 2, 3, 2**31 + 9, 2**33):
        ds = take(mix, 24, seed)
        means.append(np.mean([len(d.prompt) for d in ds]))
    spread = (max(means) - min(means)) / np.mean(means)
    assert spread < 0.08


def _lag1(x):
    x = np.asarray(x) - np.mean(x)
    return float(np.dot(x[:-1], x[1:]) / np.dot(x, x))


def test_gaps_are_shuffled_not_smoothed():
    """A short gap is as likely after a short gap as after a long one: the
    seed shuffles each block's gaps, with no pattern that evens arrivals."""
    mix = loadgen.load_mix(TRAFFIC, "chat")
    lags = [_lag1([d.gap_s for d in take(mix, 4 * mix["block"], seed)])
            for seed in range(40)]
    assert abs(np.mean(lags)) < 0.05
    gaps = [d.gap_s for d in take(mix, mix["block"], 3)]
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)


def test_gamma_arrivals_are_burstier_at_the_same_rate():
    base = loadgen.load_mix(TRAFFIC, "chat")
    bursty = {**base, "arrivals": {"process": "gamma",
                                   "rate_hz": base["arrivals"]["rate_hz"], "cv": 3.0}}
    gaps = np.array([d.gap_s for d in take(bursty, base["block"], 8)])
    assert 1 / gaps.mean() == pytest.approx(bursty["arrivals"]["rate_hz"], rel=1e-12)
    assert gaps.std() / gaps.mean() > 2.0
    with pytest.raises(ValueError):
        loadgen.gap_quantiles({"process": "uniform", "rate_hz": 1.0}, 8)


def test_shared_prefix_leads_every_prompt():
    mix = {**loadgen.load_mix(TRAFFIC, "chat"), "prefix": {"tokens": 512, "groups": 2}}
    ds = take(mix, 64, 2**31 + 5)
    heads = {tuple(d.prompt[:512]) for d in ds}
    assert len(heads) == 2
    assert max(len(d.prompt) + d.new_tokens for d in ds) <= 2048
    assert min(len(d.prompt) for d in ds) >= 512 + mix["prompt"]["min"]
    other = {tuple(d.prompt[:512]) for d in take(mix, 64, 2**31 + 6)}
    assert heads.isdisjoint(other)


def test_closed_loop_sends_a_clients_next_request_when_one_finishes():
    mix = loadgen.load_mix(TRAFFIC, "code")
    arr = loadgen.Arrivals(mix, loadgen.stream(mix, context=4096, vocab=100,
                                               seed=4), num_slots=16)
    first = arr.start(10.0)
    assert len(first) == 16 and all(t == 10.0 for t, _ in first)
    assert arr.due(1e9) == [] and arr.next_due is None
    (t, d), = arr.finished(12.5)
    assert t == 12.5 and d.index == 16


def test_open_loop_is_due_gap_after_gap_and_scales_its_rate():
    mix = loadgen.load_mix(TRAFFIC, "chat")
    arr = loadgen.Arrivals(mix, loadgen.stream(mix, context=2048, vocab=100,
                                               seed=4), num_slots=32)
    assert arr.start(0.0) == [] and arr.finished(1.0) == []
    got = arr.due(300.0)
    dues = [t for t, _ in got]
    gaps = [d.gap_s for _, d in got]
    assert np.allclose(dues, np.cumsum(gaps))
    assert 150 < len(got) < 240  # 0.64 req/s for 300 s
    arr.rate_scale = 2.0
    t0 = arr.next_due
    later = arr.due(t0 + 100.0)
    assert len(later) > 90  # about 128 at twice the rate
