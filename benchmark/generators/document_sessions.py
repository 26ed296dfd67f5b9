"""``sessions`` for tenants whose shared prefix is a DOCUMENT that outlives a
draw: the same schedule, lengths, tenants and unshared tokens as
``generators/sessions.py`` gives (its functions are called, not copied), with
the tenants' shared prefixes taken from ``seed mod DRAWS_APART``.

Why: a cell of kind ``serve_state_family`` offers TWO draws of its mix in one
run, the pre-roll's with ``seed + 1_000_003``
(``kinds/serve_state_family.py::pre_roll_requests``: "tokens of its own") and
the window's with ``seed``, and warms the trie with ``warm_prompts(mix,
seed)`` alone. Under ``sessions`` the pre-roll's requests would then ask of
six documents of their own that nobody served: every one of them a cold
prefill of 33k-41k tokens where the mix says a prefix hit (read on the chip,
PR 37: at 0.6 req/s five such requests held the engine for the whole window,
prefix hits 20 %). The tenants and their documents are the deployment's, not
a draw's; what a draw makes its own is each request's history, turn and
answer. So the documents follow the seed modulo the distance between the two
draws, which is the same for both, differs from ``--seed`` to ``--seed`` and
from rate to rate of a sweep (``seed + i``), and the unshared tokens follow
the draw's own seed as before.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.generators import sessions
from benchmark.traffic import Request

#: the offset between the seeds of the two draws of one run
#: (``kinds/serve_state_family.py::pre_roll_requests``)
DRAWS_APART = 1_000_003

schedule = sessions.schedule


def tenant_prefixes(mix: Dict[str, Any], seed: int,
                    vocab: int) -> List[List[int]]:
    return sessions.tenant_prefixes(mix, seed % DRAWS_APART, vocab)


def warm_prompts(mix: Dict[str, Any], seed: int,
                 vocab: int) -> List[List[int]]:
    return [p + [1] for p in tenant_prefixes(mix, seed, vocab)]


def generate(mix: Dict[str, Any], rate_rps: float, seconds: float,
             seed: int, vocab: int) -> List[Request]:
    documents = tenant_prefixes(mix, seed, vocab)
    reqs = sessions.generate(mix, rate_rps, seconds, seed, vocab)
    for r in reqs:
        if r.tenant >= 0:
            r.prompt = documents[r.tenant] + r.prompt[r.shared_tokens:]
    return reqs
