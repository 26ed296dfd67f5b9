"""Request traffic of several length CLASSES in one queue: each request
belongs to a class drawn with the class's ``share`` and takes its prompt's
length from that class's own distribution; nothing is shared between
requests (no tenants, no prefix). ``generators/sessions.py`` draws ONE
length distribution for all; a queue of chat turns and whole documents is
two, and a clipped mixture of log-normals is not what either looks like.

Parameters, all from the mix file: ``traffic_seed``, ``arrivals``,
``classes`` (a list of ``{"name", "share", "prompt"}``: ``prompt`` names the
mix's key that holds the class's prompt-length distribution; the shares sum
to one) and ``output_tokens`` (one distribution for every class).
``benchmark/traffic.py`` has the distributions and says why the schedule has
a seed of its own. Each attribute has a stream of its own and every class's
lengths are drawn for every request, so request i has the same class and
sizes at every rate: a rate sweep offers one pattern faster or slower.

``seed_by_rule`` is the mix's rule for its ``traffic_seed``, as code: the
mixes of ``sessions`` state it in words; here the count of the rarest class
is part of it and a reader should not have to redo it by hand.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.traffic import Request, arrival_times, draw_lengths, rng_for

#: one stream of ``traffic_seed`` per attribute; class ``j``'s prompt
#: lengths come from stream ``_CLASS_STREAMS + j``
_STREAMS = {"arrivals": 0, "class": 1, "output_tokens": 3}
_CLASS_STREAMS = 8


def warm_prompts(mix: Dict[str, Any], seed: int,
                 vocab: int) -> List[List[int]]:
    return []       # nothing shared: nothing for the trie to hold


def _shares(mix: Dict[str, Any]) -> np.ndarray:
    shares = np.array([float(c["share"]) for c in mix["classes"]])
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError(f"class shares sum to {shares.sum()}, not 1")
    return shares


def schedule(mix: Dict[str, Any], rate_rps: float,
             seconds: float) -> Dict[str, np.ndarray]:
    """Everything about the window's requests but their token values: the
    same for every ``--seed``."""
    def stream(name):
        return rng_for(mix["traffic_seed"], name)

    due = arrival_times(mix["arrivals"], rate_rps, seconds,
                        stream(_STREAMS["arrivals"]))
    n = len(due)
    cls = np.searchsorted(np.cumsum(_shares(mix)),
                          stream(_STREAMS["class"]).random(n),
                          side="right").clip(0, len(mix["classes"]) - 1)
    by_class = np.stack([
        draw_lengths(mix[c["prompt"]], n, stream(_CLASS_STREAMS + j))
        for j, c in enumerate(mix["classes"])])
    return {"due_s": due, "class": cls,
            "prompt_tokens": by_class[cls, np.arange(n)],
            "output_tokens": draw_lengths(
                mix["output_tokens"], n, stream(_STREAMS["output_tokens"]))}


def generate(mix: Dict[str, Any], rate_rps: float, seconds: float,
             seed: int, vocab: int) -> List[Request]:
    s = schedule(mix, rate_rps, seconds)
    rng = rng_for(seed, 0)
    return [Request(i, float(due), -1,
                    rng.integers(0, vocab,
                                 int(s["prompt_tokens"][i])).tolist(),
                    0, int(s["output_tokens"][i]))
            for i, due in enumerate(s["due_s"])]


def expected_tokens(mix: Dict[str, Any]) -> Dict[str, float]:
    """The mix's mean prompt and output tokens a request, from a large fixed
    draw of its distributions (clipping included)."""
    n = 200_000
    prompt = sum(share * draw_lengths(mix[c["prompt"]], n,
                                      rng_for(0, 100 + j)).mean()
                 for j, (share, c) in enumerate(zip(_shares(mix),
                                                    mix["classes"])))
    return {"prompt": float(prompt),
            "output": float(draw_lengths(mix["output_tokens"], n,
                                         rng_for(0, 99)).mean())}


def seed_by_rule(mix: Dict[str, Any], rate_rps: float, seconds: float,
                 limit: int = 10_000) -> int:
    """The first of 1, 2, 3, ... whose window of ``seconds`` at ``rate_rps``
    holds ``rate x seconds`` requests to within 2.5, the mix's expected
    prompt and output tokens to within 5 %, and of every class its share of
    the requests to within ONE request: so the cell offers the load its
    rate says, the rare class included."""
    want = expected_tokens(mix)
    shares = _shares(mix)
    for seed in range(1, limit):
        s = schedule({**mix, "traffic_seed": seed}, rate_rps, seconds)
        n = len(s["due_s"])
        if abs(n - rate_rps * seconds) > 2.5:
            continue
        if any(abs(s[k + "_tokens"].sum() / (n * want[k]) - 1.0) > 0.05
               for k in ("prompt", "output")):
            continue
        counts = np.bincount(s["class"], minlength=len(shares))
        if np.all(np.abs(counts - shares * n) <= 1.0):
            return seed
    raise ValueError("no seed under the limit fits the rule")
