"""The one traffic generator: a mix file's parameters in, requests and the
times they are due out.

A mix (``bench/traffic/<name>.json``) states:

- ``prompt`` and ``output``: lognormal length laws (median, sigma, clip).
  The prompt law is the part of a prompt that is the request's own;
- ``prefix`` (optional): ``{"tokens": n, "groups": g}``, a shared system
  prompt of ``n`` tokens in front of every prompt, one of ``g`` such
  prefixes per request, picked by the seed;
- ``arrivals``: an open loop's renewal process,
  ``{"process": "poisson", "rate_hz": r}`` or, burstier than Poisson for
  ``cv`` over 1, ``{"process": "gamma", "rate_hz": r, "cv": c}``; or a closed
  loop, ``{"process": "closed", "clients": n}`` (``"slots"`` for one client
  per slot), in which a client sends its next request when its last one
  finishes;
- the lead-in, trace slice and check sizes of a run.

Every seed gets the same work in another order. Requests come in blocks of
``block`` (a power of two): each block holds the same ``block`` prompt
lengths, output lengths and inter-arrival gaps, taken at the evenly spaced
quantiles of their laws, the gaps scaled so that a block's mean gap is
exactly ``1 / rate_hz``. The seed shuffles each block's gaps at random, so
arrivals are as bursty as the law within a block and differ from seed to
seed, while every block of every seed offers the same load. Lengths are
visited in bit-reversed order from a seeded start, so that any run of
consecutive requests spreads evenly over each length law. The seed also
draws the prompt tokens.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Iterator

import numpy as np
from scipy.special import gammaincinv


def load_mix(traffic_dir: Path, name: str) -> dict:
    return json.loads((traffic_dir / f"{name}.json").read_text())


def prefix_tokens(mix: dict) -> int:
    return int(mix.get("prefix", {}).get("tokens", 0))


def clip_lengths(mix: dict, context: int) -> tuple[dict, dict]:
    """Prompt and output length laws with the prompt's upper clip lowered so
    that the shared prefix, the longest prompt and the longest output fit
    ``context``."""
    prompt, output = dict(mix["prompt"]), dict(mix["output"])
    prompt["max"] = min(prompt["max"], context - output["max"] - prefix_tokens(mix))
    if prompt["max"] < prompt["min"]:
        raise ValueError(
            f"context {context} leaves no room for prompts of at least "
            f"{prompt['min']} tokens beside outputs of {output['max']}"
        )
    return prompt, output


def lognormal_quantiles(law: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles (i + 0.5) / n of a lognormal
    with the law's median and sigma, clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(law["median"] * np.exp(law["sigma"] * z)).astype(np.int64)
    return np.clip(x, law["min"], law["max"])


def gap_quantiles(arrivals: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles (i + 0.5) / n of the
    arrival law (Poisson: exponential gaps; gamma: shape ``1 / cv**2``),
    scaled so that their mean is ``1 / rate_hz``."""
    process = arrivals["process"]
    if process not in ("poisson", "gamma"):
        raise ValueError(f"no open-loop arrival process {process!r}")
    cv = float(arrivals.get("cv", 1.0)) if process == "gamma" else 1.0
    q = (np.arange(n) + 0.5) / n
    g = gammaincinv(1.0 / cv**2, q)
    return g / g.mean() / float(arrivals["rate_hz"])


def is_closed(mix: dict) -> bool:
    return mix["arrivals"]["process"] == "closed"


@dataclass(frozen=True)
class Draw:
    index: int
    gap_s: float  # after the previous draw's due time (open loop only)
    prompt: np.ndarray  # int32 token ids, the shared prefix first
    new_tokens: int


def stratified_order(block: int, offset: int, stride: int) -> np.ndarray:
    """Quantile index for each position of a block: the bit reversal of
    ``(offset + stride * k) mod block``. A bijection for odd ``stride``."""
    bits = block.bit_length() - 1
    if block != 1 << bits or stride % 2 == 0:
        raise ValueError(f"block {block} must be a power of two, stride odd")
    k = (offset + stride * np.arange(block)) % block
    rev = np.zeros(block, np.int64)
    for b in range(bits):
        rev |= ((k >> b) & 1) << (bits - 1 - b)
    return rev


def _seed_words(seed: int) -> list[int]:
    """A non-negative entropy list for numpy from any whole number."""
    return [seed % 2**63, 1 if seed < 0 else 0]


def stream(mix: dict, *, context: int, vocab: int, seed: int) -> Iterator[Draw]:
    """The seeded request stream of a mix, without end."""
    prompt_law, output_law = clip_lengths(mix, context)
    block = int(mix.get("block", 64))
    prompts = lognormal_quantiles(prompt_law, block)
    outputs = lognormal_quantiles(output_law, block)
    gaps = np.zeros(block) if is_closed(mix) else gap_quantiles(mix["arrivals"], block)
    words = _seed_words(seed)
    starts = np.random.default_rng([*words, 2**32]).integers(0, block, size=2)
    p_ord = stratified_order(block, int(starts[0]), 1)
    o_ord = stratified_order(block, int(starts[1]), 37 % block or 1)
    pre = mix.get("prefix", {})
    pre_rng = np.random.default_rng([*words, 2**32 + 1])
    prefixes = [
        pre_rng.integers(0, vocab, size=prefix_tokens(mix), dtype=np.int32)
        for _ in range(int(pre.get("groups", 1)))
    ]
    index = 0
    for b in range(2**62):
        rng = np.random.default_rng([*words, b])
        g_ord = rng.permutation(block)
        for i in range(block):
            n_prompt = int(prompts[p_ord[i]])
            own = rng.integers(0, vocab, size=n_prompt, dtype=np.int32)
            group = int(rng.integers(0, len(prefixes)))
            yield Draw(
                index=index,
                gap_s=float(gaps[g_ord[i]]),
                prompt=np.concatenate([prefixes[group], own]),
                new_tokens=int(outputs[o_ord[i]]),
            )
            index += 1


class Arrivals:
    """When each request of the stream is due: an open loop draws the next
    due time from the gaps, a closed loop's client sends its next request
    when its last one finishes. ``rate_scale`` multiplies an open loop's
    offered rate (the knee sweep steps it)."""

    def __init__(self, mix: dict, draws: Iterator[Draw], num_slots: int):
        arr = mix["arrivals"]
        self.closed = is_closed(mix)
        clients = arr.get("clients", 0)
        self.clients = num_slots if clients == "slots" else int(clients)
        self.draws = draws
        self.rate_scale = 1.0
        self._next: Draw | None = None
        self._next_due: float | None = None

    def start(self, t0: float) -> list[tuple[float, Draw]]:
        """The requests due at ``t0``."""
        if self.closed:
            return [(t0, next(self.draws)) for _ in range(self.clients)]
        self._next = next(self.draws)
        self._next_due = t0 + self._next.gap_s
        return []

    def due(self, now: float) -> list[tuple[float, Draw]]:
        """An open loop's requests due by ``now``, each at its due time."""
        out = []
        while not self.closed and self._next_due <= now:
            out.append((self._next_due, self._next))
            self._next = next(self.draws)
            self._next_due += self._next.gap_s / self.rate_scale
        return out

    def finished(self, t: float) -> list[tuple[float, Draw]]:
        """A closed loop's client sends its next request at ``t``."""
        return [(t, next(self.draws))] if self.closed else []

    @property
    def next_due(self) -> float | None:
        return None if self.closed else self._next_due


__all__ = [
    "Arrivals", "Draw", "clip_lengths", "gap_quantiles", "is_closed",
    "load_mix", "lognormal_quantiles", "prefix_tokens", "stratified_order",
    "stream",
]
