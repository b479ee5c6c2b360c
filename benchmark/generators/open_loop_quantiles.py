"""Open-loop request generator whose MIX does not depend on the seed.

The traffic file fixes two log-normal length distributions (prompt and
output: median, 95th percentile, clip range, rounding) and a rate.  A run of
``seconds`` offers ``n = round(rate_rps * seconds)`` requests whose prompt
lengths are the ``n`` evenly spaced quantiles of the prompt distribution
and whose output lengths are the ``n`` quantiles of the output one.  The
seed only (1) pairs outputs with prompts by a permutation, (2) permutes the
order of the requests, (3) draws the arrival instants, ``n`` uniform order
statistics over the window (a Poisson process conditioned on its count),
and (4) draws the token ids.  So every seed offers the same load and the
same multiset of requests, and only the interleaving changes.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_Z95 = statistics.NormalDist().inv_cdf(0.95)


def _quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a log-normal given by its median
    and 95th percentile, clipped to ``[lo, hi]`` and rounded up to a
    multiple of ``round_to``."""
    sigma = math.log(spec["p95"] / spec["median"]) / _Z95
    nd = statistics.NormalDist()
    raw = [spec["median"] * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
           for i in range(n)]
    step = int(spec.get("round_to", 1))
    out = [min(max(int(math.ceil(v / step)) * step, int(spec["lo"])),
               int(spec["hi"])) for v in raw]
    return np.asarray(out, np.int64)


def count(traffic: dict, seconds: float, rate_rps: float | None = None) -> int:
    rate = traffic["rate_rps"] if rate_rps is None else rate_rps
    return max(1, int(round(float(rate) * float(seconds))))


def multiset(traffic: dict, n: int):
    """``(prompt lengths, output lengths)``, each sorted ascending."""
    return (_quantile_lengths(traffic["prompt_tokens"], n),
            _quantile_lengths(traffic["output_tokens"], n))


def generate(traffic: dict, seed: int, seconds: float, vocab: int,
             rate_rps: float | None = None) -> list[dict]:
    """The schedule: ``[{"due_s", "prompt", "max_new"}]`` in arrival
    order."""
    n = count(traffic, seconds, rate_rps)
    prompts, outputs = multiset(traffic, n)
    rng = np.random.default_rng([int(seed), 0x0CEA])
    outputs = outputs[rng.permutation(n)]
    order = rng.permutation(n)
    due = np.sort(rng.uniform(0.0, float(seconds), n))
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(0, vocab, int(prompts[j])).astype(
                 np.int32),
             "max_new": int(outputs[j])}
            for i, j in enumerate(order)]
