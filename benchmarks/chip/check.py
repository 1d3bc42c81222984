"""Whether what the timed path served is correct: the comparison with the
configuration's plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed, is run through the float32
reference over its prompt and the tokens it was served.  The number
compared is ``token_deficit``: the widest gap, over every served token of
the sample, by which the served token's reference logit lies below the
reference's best logit at that position, in units of that row's logit
standard deviation.  Greedy decoding picks the best logit of the program's
own bfloat16 forward; where two logits lie closer than the program's
rounding the pick may fall on the second, so a sound run reads a small gap
and not zero.  A cache written at the wrong place, a token altered, or a
step that reads stale state reads a gap of the order of the logits' spread.
A served token outside the vocabulary fails its request outright.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict

import numpy as np


@dataclasses.dataclass
class Verdict:
    correct: bool
    attempted: int
    failed: int
    checks: Dict[str, Dict[str, float]]


def sample(requests, n: int, seed: int):
    """``n`` finished requests, drawn from the seed: one of the longest,
    then for each of the other ``n - 1`` the request nearest it in time
    from a slot spaced evenly through the batch from its slot, so that every
    part of the batch is in the sample (one wave, where waves are lock-step)."""
    done = [r for r in requests if r.done]
    if not done:
        return []
    rng = np.random.default_rng([seed % 2**64, 2])
    longest = max(len(r.tokens) for r in done)
    first = [r for r in done if len(r.tokens) == longest][
        rng.integers(sum(len(r.tokens) == longest for r in done))]
    B = 1 + max(r.slot for r in requests)
    chosen = [first]
    for k in range(1, min(n, B)):
        slot = (first.slot + k * B // min(n, B)) % B
        there = [r for r in done if r.slot == slot]
        if there:
            chosen.append(min(there, key=lambda r: abs(r.submit - first.submit)))
    return sorted(chosen, key=lambda r: r.slot)


def served_gaps(ref, conf, seed: int, chosen):
    """The reference's gap of every served token of ``chosen``, run once for
    each group of requests of one prompt and served length."""
    groups = {}
    for r in chosen:
        groups.setdefault((len(r.prompt), len(r.tokens)), []).append(r)
    gaps, stages = [], {}
    for rs in groups.values():
        g = ref.token_gaps(conf, seed, np.stack([r.prompt for r in rs]),
                           np.stack([r.tokens for r in rs]))
        gaps.append(g["served"].ravel())
        for k, v in g["stage_s"].items():
            stages[k] = stages.get(k, 0.0) + v
    return np.concatenate(gaps), stages


def compare(cell, ref, seed: int, requests) -> Verdict:
    V = cell.conf["vocab_size"]
    attempted = len(requests)
    failed = sum(bool(((r.tokens < 0) | (r.tokens >= V)).any()) for r in requests)
    limit = cell.limits["token_deficit"]["limit"]
    chosen = sample(requests, cell.traffic["check_requests"], seed)
    deficit = None                  # nothing finished, or a token outside the vocabulary
    if chosen and not failed:
        gaps, stages = served_gaps(ref, cell.conf, seed, chosen)
        deficit = float(np.max(gaps))
        print("reference seconds: " + " ".join(
            f"{k} {v:.3f}" for k, v in stages.items()), file=sys.stderr)
    checks = {"token_deficit": {"value": deficit, "limit": limit}}
    return Verdict(correct=deficit is not None and deficit <= limit, attempted=attempted,
                   failed=failed, checks=checks)
