"""One traffic generator for every mix, driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) states distributions, not
draws.  Every length and every arrival gap is drawn in stratified blocks
of ``BLOCK`` requests: a block holds the ``BLOCK`` quantiles
``F^-1((i + 1/2) / BLOCK)`` of the stated distribution, and the seed only
permutes their order inside the block, picks the token ids and assigns
the shared system prompts.  Any span of whole blocks therefore holds the
same multiset of lengths and offers the same load whatever the seed,
while the distributions keep their heavy tails and the order stays
random.  The permutation keeps one constraint: each quarter of a block
holds one quantile from each quarter of the distribution, so that a
window that cuts a block holds nearly the same mix too (a lone long
prompt at a window's edge otherwise moves a rate by several percent).
The program under test sees only the generated requests.

A length is lognormal, clipped to ``[min, max]``:

  {"kind": "lognormal", "median": m, "sigma": s, "min": a, "max": b}

Arrivals: ``"arrivals": "closed"`` makes every request due at time 0
and lets backpressure pace them (an offline batch: a queue that never
drains); ``"arrivals": "poisson"`` draws gaps from the exponential
distribution of the rate the cell file fixes, stratified the same way.
The stream has no end: the harness stops drawing when its window
closes.

A closed batch admits its first requests together, and they would
retire together.  ``"residual_first": true`` makes the first block a
head whose output lengths are the ``BLOCK`` quantiles of the
residual-life distribution of the stated one (the remaining length of a
request found in flight at a random time), so retirements start out of
phase, as in a steady batch, without paying a warm-up for it.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

BLOCK = 16
SUB = 4          # quarters of a block, each one quantile per quarter


@dataclasses.dataclass
class Req:
    rid: int
    due_s: float            # offset from the traffic's time zero
    tokens: np.ndarray      # (S,) int32 prompt
    max_new: int
    system: int             # index of the shared system prompt, -1: none


def _clip(x: float, spec: Dict) -> float:
    lo = spec.get("min", -math.inf)
    hi = spec.get("max", math.inf)
    return min(max(x, lo), hi)


def quantile(spec: Dict, u: float) -> float:
    """``F^-1(u)`` of a length distribution spec, clipped."""
    if spec["kind"] != "lognormal":
        raise ValueError(f"unknown distribution kind {spec['kind']!r}")
    x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
    return _clip(x, spec)


def block_lengths(spec: Dict, n: int = BLOCK) -> np.ndarray:
    """The ``n`` stratified quantiles of a length spec, as whole tokens
    (at least 1)."""
    return np.asarray([max(1, int(round(quantile(spec, (i + 0.5) / n))))
                       for i in range(n)], np.int64)


def block_gaps(rate: float, n: int = BLOCK) -> np.ndarray:
    """The ``n`` stratified quantiles of exponential gaps at ``rate``
    per second: ``-ln(1 - u) / rate``."""
    return np.asarray([-math.log(1.0 - (i + 0.5) / n) / rate
                       for i in range(n)])


def residual_lengths(values: np.ndarray, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the residual life of the discrete
    distribution that puts equal mass on ``values``: length-biased pick
    of a value, then a uniform point inside it.  Its CDF is
    ``F_R(r) = E[min(X, r)] / E[X]``, inverted here on whole tokens."""
    x = np.sort(np.asarray(values, np.float64))
    mean = x.mean()
    r = np.arange(1, int(x.max()) + 1, dtype=np.float64)
    cdf = np.minimum(x[None, :], r[:, None]).mean(axis=1) / mean
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        out.append(int(r[min(np.searchsorted(cdf, u), len(r) - 1)]))
    return np.asarray(out, np.int64)


def order(rng: np.random.Generator, n: int = BLOCK) -> np.ndarray:
    """A random order of the ``n`` sorted quantiles of a block in which
    each run of ``n // SUB`` holds one quantile of each of the ``SUB``
    ranges ``[j n/SUB, (j+1) n/SUB)``: the seed picks which quantile of
    each range goes to which quarter, the quarters' order and the order
    inside each quarter."""
    k = n // SUB
    ranges = [rng.permutation(np.arange(j * k, (j + 1) * k))
              for j in range(SUB)]
    out = []
    for i in rng.permutation(k):
        out.extend(rng.permutation([r[i] for r in ranges]))
    return np.asarray(out, np.int64)


def stream(traffic: Dict, cell: Dict, seed: int, vocab: int
           ) -> Iterator[Req]:
    """The run's requests in arrival order, block after block."""
    rng = np.random.default_rng(seed)
    prompt_q = block_lengths(traffic["prompt"])
    out_q = block_lengths(traffic["output"])
    systems: List[np.ndarray] = []
    sp = traffic.get("system_prompts")
    if sp:
        systems = [rng.integers(0, vocab, sp["length"]).astype(np.int32)
                   for _ in range(sp["count"])]
        assert BLOCK % sp["count"] == 0, "system prompts must tile a block"
    closed = traffic["arrivals"] == "closed"
    gaps_q = None if closed else block_gaps(cell["rate_per_s"])
    rid = 0
    t = 0.0
    head = closed and traffic.get("residual_first")
    while True:
        plen = prompt_q[order(rng)]
        olen = (residual_lengths(out_q, BLOCK) if head else out_q)[order(rng)]
        head = False
        gaps = None if closed else gaps_q[order(rng)]
        sys_ids = (rng.permutation(np.arange(BLOCK) % len(systems))
                   if systems else np.full(BLOCK, -1))
        for i in range(BLOCK):
            if gaps is not None:
                t += float(gaps[i])
            max_new = int(olen[i])
            user = rng.integers(0, vocab, int(plen[i])).astype(np.int32)
            s = int(sys_ids[i])
            tokens = np.concatenate([systems[s], user]) if s >= 0 else user
            yield Req(rid=rid, due_s=t, tokens=tokens, max_new=max_new,
                      system=s)
            rid += 1


def take(reqs: Iterator[Req], n: int) -> List[Req]:
    """The first ``n`` requests of a stream."""
    return [next(reqs) for _ in range(n)]


def offered(reqs: List[Req]) -> Dict[str, float]:
    """What a stream's first requests offer: their prompt and output
    tokens and, for open loops, when the last is due."""
    return {"requests": len(reqs),
            "prompt_tokens": int(sum(len(r.tokens) for r in reqs)),
            "output_tokens": int(sum(r.max_new for r in reqs)),
            "end_s": reqs[-1].due_s if reqs else 0.0}
