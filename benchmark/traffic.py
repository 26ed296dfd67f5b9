"""Traffic: a mix is a data file of parameters (``traffic/<name>.json``), a
cell adds its offered rate, and the mix names the generator that reads it
(``generators/<name>.py``, found by name like a kind or a reader). Nothing
here knows a cell, a mix or a generator by name. This module holds what
generators share: the request, the seeded streams and the draws.

Two seeds, on purpose. The SCHEDULE (arrival instants, lengths, tenants) is
a genuine draw from the mix's distributions, Poisson clusters and length
tails included, from the mix file's own ``traffic_seed``: every run of a
cell offers the same work at the same instants, and what differs between
two runs is the system. ``--seed`` gives the token values and the weights.
Why the schedule does not follow ``--seed``, with readings (my chip runs,
PR 24, six seeds a set): drawn from ``--seed``, TTFT p90 spread 18 % and
49 % over the seeds, one draw in six building a queue, while two runs of ONE
draw agreed to 0.1 %: the seed was changing the work. An earlier version
replayed evenly spread quantiles instead of a draw; that removed the
clusters the TTFT tail is made of, and was withdrawn on review.

Arrivals are drawn at unit rate and scaled, and each attribute has a stream
of its own, so request i has the same sizes at every rate: a rate sweep
offers one pattern faster or slower.

Copied in idea from ``experiments/serve_replay.py`` (tenants that share a
prefix, on/off bursts), which is not imported.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np


@dataclass
class Request:
    index: int
    due_s: float          # offset from the start of the window
    tenant: int           # -1: no shared prefix
    prompt: List[int]
    shared_tokens: int    # leading tokens shared with the tenant's others
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Seeds run past 2**31: numpy takes any non-negative whole number."""
    return np.random.default_rng([int(seed), int(stream)])


def draw_lengths(spec: Dict[str, Any], n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """n whole-number lengths drawn from a distribution.

    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` clipped to
    [min, max]; ``{"dist": "uniform", "min", "max"}`` (both ends included);
    ``{"dist": "fixed", "value"}``."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        x = spec["min"] + rng.random(n) * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(spec: Dict[str, Any], rate_rps: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """The arrival instants inside [0, seconds) of the process at a mean
    rate of ``rate_rps``.

    ``poisson``: exponential gaps. ``onoff``: bursts of ``burst_requests``
    Poisson arrivals, a pause of ``gap_s`` after each, and the rate inside a
    burst raised so that the mean rate holds."""
    n = int(2 * rate_rps * seconds) + 64
    unit = rng.exponential(1.0, n)        # the same pattern at every rate
    if spec["process"] == "poisson":
        gaps = unit / rate_rps
    elif spec["process"] == "onoff":
        k, pause = int(spec["burst_requests"]), float(spec["gap_s"])
        on_time = k / rate_rps - pause
        if on_time <= 0:
            raise ValueError("onoff pauses leave no time for the bursts")
        gaps = unit * (on_time / k)
        gaps[k::k] += pause
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    due = np.cumsum(gaps)
    if due[-1] < seconds:
        raise ValueError("too few arrivals drawn for the window")
    return due[due < seconds]


def draw_zipf(tenants: int, alpha: float, n: int,
              rng: np.random.Generator) -> np.ndarray:
    """n tenants drawn with popularity proportional to rank ** -alpha."""
    w = 1.0 / np.arange(1, tenants + 1) ** alpha
    return np.searchsorted(np.cumsum(w / w.sum()), rng.random(n),
                           side="right").clip(0, tenants - 1)


def _generator(mix: Dict[str, Any]):
    from benchmark import manifest

    return manifest.load_module(manifest.generator_path(mix["generator"]))


def generate(mix: Dict[str, Any], rate_rps: float, seconds: float,
             seed: int, vocab: int) -> List[Request]:
    """The requests due inside a window of ``seconds`` at ``rate_rps``, by
    the generator the mix names."""
    return _generator(mix).generate(mix, rate_rps, seconds, seed, vocab)


def warm_prompts(mix: Dict[str, Any], seed: int,
                 vocab: int) -> List[List[int]]:
    """Prompts that set-up serves once because the traffic needs them there
    (shared prefixes in the trie before the window's first request)."""
    return _generator(mix).warm_prompts(mix, seed, vocab)
