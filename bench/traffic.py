"""The one traffic generator: reads a mix file and yields requests with due times.

A mix (``bench/traffic/<name>.json``) gives the arrival process, the
length distributions and the public trace they follow (``source``).
Requests come in blocks of ``block``.  Every block holds the same multiset
of prompt lengths, output lengths and inter-arrival gaps: the quantiles
``(k + 0.5) / block`` of each distribution.  ``--seed`` draws their order
within each block, the prompts' token ids and (in ``bench/weights.py``) the
weights.  So every seed serves the same work, as its own schedule: a change
tuned to one order of requests meets another on the next seed, while the
amount of work, and with it a rate's spread, stays the mix's own.  A
queue builds and drains inside a block; a block's gaps sum to its
``block / rate`` seconds.

Arrival kinds:

* ``backlog``: every request is due at time 0 (an offline batch job).
* ``poisson``: exponential inter-arrival gaps at ``rate`` requests/s.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List, Mapping

import numpy as np

_NORMAL = NormalDist()


@dataclass
class Arrival:
    rid: int
    due_s: float  # seconds after the traffic's origin
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def quantile_lengths(spec: Mapping, n: int) -> List[int]:
    """``n`` lengths at the quantiles ``(k + 0.5) / n`` of a clipped
    lognormal with the given ``median`` and ``sigma``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out = []
    for k in range(n):
        z = _NORMAL.inv_cdf((k + 0.5) / n)
        x = round(spec["median"] * math.exp(spec["sigma"] * z))
        out.append(int(min(max(x, spec["min"]), spec["max"])))
    return out


def quantile_gaps(rate: float, n: int) -> List[float]:
    """``n`` exponential inter-arrival gaps at the quantiles ``(k + 0.5) / n``."""
    return [-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)]


def _check(mix: Mapping) -> None:
    kind = mix["arrival"]["kind"]
    if kind not in ("backlog", "poisson"):
        raise ValueError(f"unknown arrival kind {kind!r}")
    if kind == "poisson" and not mix["arrival"]["rate"] > 0:
        raise ValueError("a poisson mix needs a positive rate")
    if int(mix["block"]) < 1:
        raise ValueError("block must be at least 1")


def arrivals(mix: Mapping, vocab_size: int, seed: int) -> Iterator[Arrival]:
    """Endless requests of ``mix`` in order of due time; ``seed`` draws each
    block's order and the token ids."""
    _check(mix)
    n = int(mix["block"])
    prompts = quantile_lengths(mix["prompt_len"], n)
    outputs = quantile_lengths(mix["output_len"], n)
    poisson = mix["arrival"]["kind"] == "poisson"
    gaps = quantile_gaps(float(mix["arrival"]["rate"]), n) if poisson else [0.0] * n
    rid, due, block = 0, 0.0, 0
    while True:
        rng = np.random.default_rng([seed, block])
        p_order, o_order, g_order = (rng.permutation(n) for _ in range(3))
        for k in range(n):
            due += gaps[g_order[k]]
            prompt = rng.integers(1, vocab_size, size=prompts[p_order[k]])
            yield Arrival(rid, due, prompt.astype(np.int32), outputs[o_order[k]])
            rid += 1
        block += 1

