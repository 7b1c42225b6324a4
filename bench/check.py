"""Whether what the timed path served is correct.

After the window, a seeded sample of the requests it finished, the longest
among them, is run through the plain reference on the same weights: each
prompt with its served tokens, teacher-forced. At every served position the
number compared is the gap by which the served token's reference logit lies
below the reference's best logit there. Greedy serving of an exact model
gives 0; the served dtype's rounding gives near-ties a small gap; a wrong
page, position, layer or token gives an ordinary draw against the
reference, far below its best. The widest gap over the sample is held to the
configuration's limit (``limits.logit_gap`` in its file, set from the program's
readings and the int8 control's, as ``PERF.md`` records).

A control run puts the control's tokens in the served tokens' place: at each
served position, the token that the reference computed one precision lower
puts first. They go through the same comparison and the same limit, so the
run reports ``correct`` false where the limit separates the two.
"""

from __future__ import annotations

import numpy as np


def sample(done: list, seed: int, max_requests: int, min_tokens: int) -> list:
    """The longest finished request, then others in a seeded order, until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.req.tokens), -s.rid))
    rest = [s for s in done if s is not longest]
    order = np.random.default_rng([seed % 2**63, 7]).permutation(len(rest))
    picked, total = [longest], len(longest.req.tokens)
    for i in order:
        if total >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        total += len(rest[i].req.tokens)
    return picked


def gaps(model: dict, params, served, ref, quant: str | None = None) -> dict:
    """Reference gaps of one request's served tokens. With ``quant`` the
    control's tokens take their place: the tokens that the reference in the
    lower precision puts first, over the same served sequence."""
    prompt = np.asarray(served.prompt, np.int32)
    tokens = np.asarray(served.req.tokens, np.int32)
    seq = np.concatenate([prompt, tokens[:-1]])  # the last token feeds nothing
    rows = np.arange(len(prompt) - 1, len(seq))
    logits = ref.logits_rows(model, params, seq, rows)
    best = logits.max(-1)
    out = {"program_gap": best - logits[np.arange(len(rows)), tokens]}
    if quant is not None:
        low = ref.logits_rows(model, params, seq, rows, quant=quant)
        tokens = low.argmax(-1)
    out.update(
        gap=best - logits[np.arange(len(rows)), tokens],
        exact=logits.argmax(-1) == tokens,
        finite=bool(np.isfinite(logits).all()),
    )
    return out


def compare(
    model: dict, params, done: list, seed: int, sizes: dict, limits: dict, ref,
    control: str | None = None,
) -> dict:
    """The check of a run; with ``control`` (a lower precision) the control
    run's, with the program's own widest gap on the same sample beside it."""
    picked = sample(
        done, seed, int(sizes.get("max_requests", 8)),
        int(sizes.get("min_tokens", 256)),
    )
    limit = float(limits["logit_gap"])
    worst, positions, exact, finite = 0.0, 0, 0, True
    program_worst = 0.0
    malformed = 0
    for s in picked:
        toks = np.asarray(s.req.tokens)
        if len(toks) != s.new_tokens or toks.min() < 0 or toks.max() >= model["vocab_size"]:
            malformed += 1
            continue
        g = gaps(model, params, s, ref, quant=control)
        worst = max(worst, float(g["gap"].max()))
        program_worst = max(program_worst, float(g["program_gap"].max()))
        positions += len(g["gap"])
        exact += int(g["exact"].sum())
        finite &= g["finite"]
    checks = {
        "logit_gap": {"value": worst, "limit": limit},
        "malformed_requests": {"value": malformed, "limit": 0},
    }
    correct = bool(picked) and finite and malformed == 0 and worst <= limit
    out = {
        "correct": correct,
        "checks": checks,
        "requests": len(picked),
        "positions": positions,
        "exact": exact,
    }
    if control is not None:
        out["program_gap"] = program_worst
    return out
