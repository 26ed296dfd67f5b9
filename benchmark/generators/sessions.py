"""The general generator of request traffic: a request is its tenant's
shared prefix (none when the mix has no tenants) + unshared session history
+ a new turn, and asks for a number of output tokens. Parameters, all from
the mix file: ``traffic_seed``, ``arrivals``, ``tenants``,
``shared_prefix_tokens``, ``popularity.zipf``, ``history_tokens``,
``turn_tokens``, ``output_tokens`` (``benchmark/traffic.py`` has the
distributions and says why the schedule has a seed of its own).
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.traffic import (Request, arrival_times, draw_lengths,
                               draw_zipf, rng_for)

#: one stream of ``traffic_seed`` per attribute
_STREAMS = {"arrivals": 0, "history_tokens": 1, "turn_tokens": 2,
            "output_tokens": 3, "tenant": 4}


def tenant_prefixes(mix: Dict[str, Any], seed: int,
                    vocab: int) -> List[List[int]]:
    n = int(mix.get("tenants", 0))
    length = int(mix.get("shared_prefix_tokens", 0))
    rng = rng_for(seed, 1)
    return [rng.integers(0, vocab, length).tolist() for _ in range(n)]


def warm_prompts(mix: Dict[str, Any], seed: int,
                 vocab: int) -> List[List[int]]:
    return [p + [1] for p in tenant_prefixes(mix, seed, vocab)]


def schedule(mix: Dict[str, Any], rate_rps: float,
             seconds: float) -> Dict[str, np.ndarray]:
    """Everything about the window's requests but their token values: the
    same for every ``--seed``."""
    def stream(name):
        return rng_for(mix["traffic_seed"], _STREAMS[name])

    due = arrival_times(mix["arrivals"], rate_rps, seconds,
                        stream("arrivals"))
    n = len(due)
    out = {"due_s": due}
    for name in ("history_tokens", "turn_tokens", "output_tokens"):
        out[name] = draw_lengths(mix[name], n, stream(name))
    tenants = int(mix.get("tenants", 0))
    out["tenant"] = (draw_zipf(tenants, float(mix["popularity"]["zipf"]), n,
                               stream("tenant"))
                     if tenants else np.full(n, -1))
    return out


def generate(mix: Dict[str, Any], rate_rps: float, seconds: float,
             seed: int, vocab: int) -> List[Request]:
    s = schedule(mix, rate_rps, seconds)
    rng = rng_for(seed, 0)
    prefixes = tenant_prefixes(mix, seed, vocab)
    reqs = []
    for i, due in enumerate(s["due_s"]):
        own = rng.integers(
            0, vocab, int(s["history_tokens"][i] + s["turn_tokens"][i]))
        tenant = int(s["tenant"][i])
        shared = prefixes[tenant] if tenant >= 0 else []
        reqs.append(Request(i, float(due), tenant, shared + own.tolist(),
                            len(shared), int(s["output_tokens"][i])))
    return reqs
