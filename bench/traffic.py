"""The one traffic generator: a serving mix's parameters -> requests.

Every seed gets the same schedule: the sizes and inter-arrival gaps are
drawn at stratified quantiles of the mix's distributions and put in one
order, with each request's system prompt, fixed by ``SCHEDULE_SEED``.
The run's seed draws the token contents alone.  So two seeds differ in what is sent, never in how much
work a run holds or when each size comes: a tail such as the p95 gap
between tokens cannot move from one plateau to the next with the seed.

Mix keys read here (all under the mix file's top level):
  arrivals       {"kind": "poisson", "rate_per_s": r}: open loop, one request
                 due every 1/r s on average over the run's window, or
                 {"kind": "backlog", "requests": n}: n requests all due at 0
  shared_prefix  {"count": k, "tokens": t, "zipf": a} or null: k system
                 prompts of t tokens, request shares Zipf(a) by rank
  prompt         length distribution of each request's own prompt tokens
  output         length distribution of the tokens each request asks for
A length distribution is {"kind": "lognormal", "median", "sigma", "min",
"max"} or {"kind": "uniform", "min", "max"}.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

TAG_ORDER, TAG_TOKENS = 11, 12
SCHEDULE_SEED = 0


@dataclasses.dataclass
class Planned:
    """One request as planned: due time (s from window start), the index
    of its system prompt (or -1) and its token lists."""

    rid: int
    due: float
    system: int
    prompt: np.ndarray
    max_new: int


def _quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def lengths(dist, n):
    """``n`` lengths at stratified quantiles of ``dist``, ascending."""
    lo, hi = dist["min"], dist["max"]
    if dist["kind"] == "lognormal":
        z = [NormalDist().inv_cdf(u) for u in _quantiles(n)]
        raw = [dist["median"] * math.exp(dist["sigma"] * x) for x in z]
    elif dist["kind"] == "uniform":
        raw = [lo + u * (hi - lo) for u in _quantiles(n)]
    else:
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    return [int(min(max(round(x), lo), hi)) for x in raw]


def zipf_counts(k, n, a):
    """How many of ``n`` requests each of ``k`` ranks gets under Zipf(a),
    apportioned by largest remainder."""
    w = [1.0 / (r + 1) ** a for r in range(k)]
    share = [n * x / sum(w) for x in w]
    counts = [int(s) for s in share]
    rest = sorted(range(k), key=lambda r: counts[r] - share[r])
    for r in rest[:n - sum(counts)]:
        counts[r] += 1
    return counts


def request_count(mix, seconds):
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        return max(1, round(arr["rate_per_s"] * seconds))
    if arr["kind"] == "backlog":
        return arr["requests"]
    raise ValueError(f"unknown arrivals {arr['kind']!r}")


def due_times(mix, n, seconds, rng):
    arr = mix["arrivals"]
    if arr["kind"] == "backlog":
        return [0.0] * n
    gaps = np.asarray([-math.log(1 - u) for u in _quantiles(n)])
    rng.shuffle(gaps)
    t = np.cumsum(gaps)
    # the n-th request falls due n/(n+1) of the way through the window
    return list(t / t[-1] * seconds * n / (n + 1))


def system_prompts(mix, seed, vocab):
    sp = mix.get("shared_prefix")
    if not sp:
        return []
    rng = np.random.default_rng([seed, TAG_TOKENS, 0])
    return [rng.integers(0, vocab, sp["tokens"], dtype=np.int32)
            for _ in range(sp["count"])]


def plan(mix, seed: int, seconds: float, vocab: int):
    """The run's requests, in due order."""
    n = request_count(mix, seconds)
    rng = np.random.default_rng([SCHEDULE_SEED, TAG_ORDER])
    plens = lengths(mix["prompt"], n)
    outs = lengths(mix["output"], n)
    rng.shuffle(plens)
    rng.shuffle(outs)
    due = due_times(mix, n, seconds, rng)
    systems = system_prompts(mix, seed, vocab)
    sys_of = [-1] * n
    if systems:
        sp = mix["shared_prefix"]
        sys_of = [r for r, c in enumerate(zipf_counts(sp["count"], n,
                                                      sp["zipf"]))
                  for _ in range(c)]
        rng.shuffle(sys_of)
    tok = np.random.default_rng([seed, TAG_TOKENS, 1])
    out = []
    for i in range(n):
        own = tok.integers(0, vocab, plens[i], dtype=np.int32)
        prompt = (np.concatenate([systems[sys_of[i]], own])
                  if sys_of[i] >= 0 else own)
        out.append(Planned(rid=i, due=float(due[i]), system=sys_of[i],
                           prompt=prompt, max_new=int(outs[i])))
    return out
