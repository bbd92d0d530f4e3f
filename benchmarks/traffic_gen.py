"""The one generator of serving traffic, driven by a traffic file.

Every seed offers the same requests and the same gaps in another order:
the lengths are the quantiles of the file's log-normal distributions, paired
once by a fixed shuffle, and the gaps between arrivals are the quantiles of
the file's gap distribution (Weibull of shape `gap_shape`; shape 1, the
default, is the exponential, a Poisson process's gaps; under 1 arrivals come
in bursts), so a run's count of requests, its tokens and its total offered
time do not depend on the seed. Which request arrives when does: the seed
permutes the requests and the gaps freely. With `"arrivals": "backlog"`
every request is due at time 0, `backlog_requests` of them: far more than a
window finishes, so a window sees only the head of the queue. To give every
seed's window the same requests all the same, the lengths are the quantiles
over `lengths_round` requests (about what a window admits) and the queue is
that round again and again, each round in an order of the seed's own.

Token ids are drawn uniformly over the whole vocabulary from the seed.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Offered:
    rid: str
    due: float                 # seconds after the window opens
    prompt: tuple
    max_new: int


def lognormal_quantiles(spec: dict, n: int) -> List[int]:
    """n lengths: quantiles (i + 0.5)/n of a log-normal with the given
    median and sigma, clipped to [min, max]."""
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        x = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def gap_quantiles(rate: float, n: int, shape: float = 1.0) -> List[float]:
    """n gaps: quantiles (i + 0.5)/n of a Weibull distribution of the given
    shape (1: exponential), scaled so that they sum to n/rate exactly."""
    raw = [(-math.log(1.0 - (i + 0.5) / n)) ** (1.0 / shape)
           for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def length_pairs(traffic: dict, n: int) -> List[tuple]:
    """The multiset of (prompt length, output length), the same for every
    seed: the two quantile lists paired by one fixed shuffle."""
    prompts = lognormal_quantiles(traffic["prompt_len"], n)
    outputs = lognormal_quantiles(traffic["output_len"], n)
    np.random.default_rng(20250101).shuffle(outputs)
    return list(zip(prompts, outputs))


def n_requests(traffic: dict, seconds: float) -> int:
    if traffic.get("arrivals") == "backlog":
        return int(traffic["backlog_requests"])
    return max(1, int(round(traffic["rate_rps"] * seconds)))


def offered(traffic: dict, seed: int, seconds: float, vocab: int
            ) -> List[Offered]:
    n = n_requests(traffic, seconds)
    rng = np.random.default_rng([seed, 0x73657276])
    round_len = int(traffic.get("lengths_round", n))
    pairs = length_pairs(traffic, round_len)
    order = np.concatenate([rng.permutation(round_len)
                            for _ in range(-(-n // round_len))])[:n]
    if traffic.get("arrivals") == "backlog":
        dues = [0.0] * n
    else:
        # the first request is due when the window opens, and n - 1 gaps
        # lie between the n requests
        gaps = np.asarray(gap_quantiles(traffic["rate_rps"], n - 1,
                                        float(traffic.get("gap_shape", 1.0))))
        dues = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))]
                              ).tolist()
    out = []
    for i, j in enumerate(order):
        p_len, o_len = pairs[j]
        prompt = tuple(int(t) for t in rng.integers(0, vocab, p_len))
        out.append(Offered(f"r{i:05d}", float(dues[i]), prompt, int(o_len)))
    return out
