"""Open-loop traffic from a mix file (``traffic/<mix>.json``) and a seed.

A mix is data: a generator name, an arrival rate, a lead-in, and the
distributions of prompt and output lengths.  This one module reads every
mix, so a new mix is a new data file.

Generators:

* ``poisson`` — exponential gaps at ``rate_rps``, as the repository's
  ``serving/requests.py:poisson_trace`` draws them, but with every seed
  offering the same work in the lead-in and the same in the window: each
  of the two spans gets ``n = ceil(rate * span)`` arrivals whose gaps and
  prompt and output lengths are the quantiles of their distributions at
  ``(i + 0.5) / n``, each permuted by the seed over the whole span.  Gaps
  are exchangeable, so arrivals cluster as Poisson arrivals do.
* ``bursty_diurnal`` — non-homogeneous Poisson by thinning over a sin^2
  envelope between ``base_rps`` and ``peak_rps`` with periodic bursts (the
  repository's ``bursty_diurnal_trace``, copied).

Token ids are drawn uniformly over the configuration's real vocabulary.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

import numpy as np


@dataclass(frozen=True)
class Arrival:
    t: float                 # seconds after the start of the schedule
    rid: int
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int


def load(path: str | Path) -> dict:
    return json.loads(Path(path).read_text())


def _ppf(dist: dict, q: np.ndarray) -> np.ndarray:
    """Quantile function of a length distribution, clipped and rounded."""
    kind = dist["dist"]
    if kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + q * (dist["max"] + 1 - dist["min"])
        v = np.floor(v)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def _draw(dist: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    return _ppf(dist, rng.random(n).clip(1e-12, 1 - 1e-12))


def _seed_seq(seed: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed % 2**32, seed // 2**32 % 2**32, 7])


def schedule(mix: dict, seconds: float, seed: int, vocab: int
             ) -> list[Arrival]:
    """Arrivals over about ``mix["lead_in_s"] + seconds``, sorted by
    time."""
    rng = np.random.default_rng(_seed_seq(seed))
    horizon = mix["lead_in_s"] + seconds
    gen = mix["generator"]
    if gen == "poisson":
        parts = [_same_work(rng, mix, t0, span)
                 for t0, span in ((0.0, mix["lead_in_s"]),
                                  (mix["lead_in_s"], seconds))]
        times, plens, olens = (np.concatenate(x) for x in zip(*parts))
    elif gen == "bursty_diurnal":
        times = _thinned_times(rng, mix, horizon)
        plens = _draw(mix["prompt"], rng, len(times))
        olens = _draw(mix["output"], rng, len(times))
    else:
        raise ValueError(f"unknown generator {gen!r}")
    return [Arrival(t=float(times[i]), rid=i,
                    prompt=rng.integers(0, vocab, int(plens[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(olens[i]))
            for i in range(len(times))]


def _same_work(rng, mix: dict, t0: float, span: float):
    """Arrival times in ``[t0, t0 + span)`` and their prompt and output
    lengths: the quantiles of each distribution, permuted."""
    rate = mix["rate_rps"]
    n = max(1, math.ceil(rate * span))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    # the quantile gaps sum to about span; never past it
    gaps *= min(1.0, 0.999 * span / gaps.sum())
    return (t0 + np.cumsum(gaps), rng.permutation(_ppf(mix["prompt"], q)),
            rng.permutation(_ppf(mix["output"], q)))


def _thinned_times(rng, mix: dict, horizon: float) -> np.ndarray:
    base, peak = mix["base_rps"], mix["peak_rps"]
    period, factor = mix["period_s"], mix.get("burst_factor", 1.0)
    every, blen = mix.get("burst_every_s"), mix.get("burst_len_s", 0.0)
    rmax = peak * factor

    def rate(t):
        r = base + (peak - base) * math.sin(math.pi * t / period) ** 2
        if every and (t % every) < blen:
            r *= factor
        return r

    times, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rmax)
        if t >= horizon:
            break
        if rng.random() < rate(t) / rmax:
            times.append(t)
    return np.asarray(times)


def buckets_reached(mix: dict, buckets: tuple[int, ...]) -> list[int]:
    """Prompt buckets that a mix's prompts can land in (the engine
    prefills ``len(prompt) - 1`` tokens into the smallest covering
    bucket)."""
    lo, hi = mix["prompt"]["min"] - 1, mix["prompt"]["max"] - 1
    out = []
    for i, b in enumerate(sorted(buckets)):
        prev = sorted(buckets)[i - 1] if i else 0
        if lo <= b and hi > prev:
            out.append(b)
    return out
