"""Multi-turn conversations that GROW: a request is the next turn of one of
``sessions`` conversations alive when the window opens, its prompt the
conversation's whole history so far plus a new turn, and after it the
history is that prompt plus an answer's worth of tokens. So a turn shares
everything but the previous answer and its own new message with the turn
before it, and the prefix a turn can reuse ends where the LAST turn's prompt
ended, which did not exist when the window opened: the engine has to have
kept it in the window. ``generators/sessions.py`` draws every request's
history afresh: nothing of one request is a prefix of the next.

Parameters, all from the mix file: ``traffic_seed``, ``arrivals``,
``sessions``, ``popularity.zipf``, ``min_turn_gap_s``, ``max_prompt_tokens``
and the three length distributions ``history_tokens`` (a conversation's
history at set-up), ``turn_tokens`` (a new message) and ``output_tokens`` (an
answer). ``benchmark/traffic.py`` has the distributions and says why the
schedule has a seed of its own.

- **Set-up** (``warm_prompts``): each conversation's history, served once, so
  that the engine holds it when the window opens.
- **A request** is due at a Poisson instant and belongs to a conversation
  drawn Zipf over those whose last turn was DUE at least ``min_turn_gap_s``
  ago (nobody sends a turn before the answer to the last one is back; where
  none is, the one that has waited longest): prompt = history + a new turn,
  ``max_new`` = the answer's length.
- **The conversation grows**: its history becomes that prompt + as many
  generator-drawn tokens as the answer is long. The model's own greedy
  answer is not known to a generator, and with seeded weights either is
  noise; what the next turn shares with this one is this one's PROMPT.
- **It starts over** from its set-up history where the next prompt would pass
  ``max_prompt_tokens`` (a user clears the chat): the context stays under the
  engine's ``max_len`` and the old chain is nobody's prefix any more. A
  set-up history may itself lie within a turn of the cap (ISSUE 50 draws
  them up to the cap): the turn that follows a fresh start is cut to what
  the cap leaves, and a history that would leave less than the shortest turn
  is drawn again at set-up.

The SCHEDULE (instants, conversations, lengths, restarts) follows
``traffic_seed`` alone and is the same for every ``--seed``; the token values
follow ``--seed``, a stream a conversation. A request's ``shared_tokens`` are
the tokens of its prompt that were a prompt before (the last turn's, or the
set-up history after a restart): what a prefix cache can serve at best.

``seed_by_rule`` is the mix's rule for its ``traffic_seed``, as code.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.traffic import Request, arrival_times, draw_lengths, rng_for

#: one stream of ``traffic_seed`` per attribute
_STREAMS = {"arrivals": 0, "session": 1, "turn_tokens": 2,
            "output_tokens": 3, "history_tokens": 4}
#: ``--seed``'s stream of conversation ``s``'s token values
_TOKEN_STREAMS = 16


def _histories(mix: Dict[str, Any]) -> np.ndarray:
    """The conversations' histories at set-up; one that could not take even
    the shortest turn under ``max_prompt_tokens`` is drawn again."""
    rng = rng_for(mix["traffic_seed"], _STREAMS["history_tokens"])
    room = int(mix["max_prompt_tokens"]) - int(mix["turn_tokens"]["min"])
    out = draw_lengths(mix["history_tokens"], int(mix["sessions"]), rng)
    while (out > room).any():
        again = np.flatnonzero(out > room)
        out[again] = draw_lengths(mix["history_tokens"], len(again), rng)
    return out


def schedule(mix: Dict[str, Any], rate_rps: float,
             seconds: float) -> Dict[str, np.ndarray]:
    """Everything about the window's requests but their token values: the
    same for every ``--seed``. Per request: ``due_s``, ``session``,
    ``prompt_tokens``, ``turn_tokens``, ``output_tokens``, ``shared_tokens``
    (of the prompt, what was a prompt before) and ``restart`` (the
    conversation started over from its set-up history)."""
    def stream(name):
        return rng_for(mix["traffic_seed"], _STREAMS[name])

    due = arrival_times(mix["arrivals"], rate_rps, seconds,
                        stream("arrivals"))
    n, sessions = len(due), int(mix["sessions"])
    turn = draw_lengths(mix["turn_tokens"], n, stream("turn_tokens"))
    out = draw_lengths(mix["output_tokens"], n, stream("output_tokens"))
    pick = stream("session").random(n)
    weight = np.arange(1, sessions + 1, dtype=np.float64) \
        ** -float(mix["popularity"]["zipf"])
    gap, cap = float(mix["min_turn_gap_s"]), int(mix["max_prompt_tokens"])
    first = _histories(mix)
    history = first.copy()          # tokens of the conversation so far
    cached = first.copy()           # ... of them, what was a prompt before
    last_due = np.full(sessions, -gap)      # set-up: answered long ago
    cols = {k: np.zeros(n, np.int64) for k in
            ("session", "prompt_tokens", "shared_tokens", "restart")}
    for i in range(n):
        ready = np.flatnonzero(due[i] - last_due >= gap)
        if len(ready):
            w = np.cumsum(weight[ready])
            s = int(ready[min(np.searchsorted(w, pick[i] * w[-1],
                                              side="right"), len(ready) - 1)])
        else:
            s = int(np.argmin(last_due))
        restart = history[s] + turn[i] > cap
        if restart:
            history[s] = cached[s] = first[s]
            # a set-up history near the cap leaves room for a short turn only
            turn[i] = min(turn[i], cap - first[s])
        prompt = history[s] + turn[i]
        cols["session"][i], cols["restart"][i] = s, restart
        cols["prompt_tokens"][i], cols["shared_tokens"][i] = prompt, cached[s]
        cached[s], history[s] = prompt, prompt + out[i]
        last_due[s] = due[i]
    return {"due_s": due, "turn_tokens": turn, "output_tokens": out, **cols}


def _session_tokens(seed: int, session: int, vocab: int):
    """Conversation ``session``'s token values, drawn as they are needed."""
    rng = rng_for(seed, _TOKEN_STREAMS + session)
    return lambda n: rng.integers(0, vocab, int(n)).tolist()


def warm_prompts(mix: Dict[str, Any], seed: int,
                 vocab: int) -> List[List[int]]:
    """Each conversation's history at set-up: the first tokens of its
    stream, as ``generate`` draws them."""
    return [_session_tokens(seed, s, vocab)(h)
            for s, h in enumerate(_histories(mix))]


def generate(mix: Dict[str, Any], rate_rps: float, seconds: float,
             seed: int, vocab: int) -> List[Request]:
    s = schedule(mix, rate_rps, seconds)
    draws = [_session_tokens(seed, k, vocab)
             for k in range(int(mix["sessions"]))]
    first = [draw(h) for draw, h in zip(draws, _histories(mix))]
    history = [list(h) for h in first]
    out = []
    for i, due in enumerate(s["due_s"]):
        k = int(s["session"][i])
        if s["restart"][i]:
            history[k] = list(first[k])
        prompt = history[k] + draws[k](s["turn_tokens"][i])
        out.append(Request(i, float(due), k, prompt,
                           int(s["shared_tokens"][i]),
                           int(s["output_tokens"][i])))
        history[k] = prompt + draws[k](s["output_tokens"][i])
    return out


def expected_tokens(mix: Dict[str, Any], rate_rps: float,
                    seconds: float) -> Dict[str, float]:
    """The mix's mean UNSHARED prompt tokens and output tokens a request at
    this rate over this span, from 32 fixed draws of the schedule (clipping,
    the gap rule and the restarts included)."""
    unshared = output = n = 0
    for seed in range(100_001, 100_033):
        s = schedule({**mix, "traffic_seed": seed}, rate_rps, seconds)
        unshared += int((s["prompt_tokens"] - s["shared_tokens"]).sum())
        output += int(s["output_tokens"].sum())
        n += len(s["due_s"])
    return {"unshared": unshared / n, "output": output / n}


def seed_by_rule(mix: Dict[str, Any], rate_rps: float, seconds: float,
                 limit: int = 10_000) -> int:
    """The first of 1, 2, 3, ... whose window of ``seconds`` at ``rate_rps``
    holds ``rate x seconds`` requests to within 2.5 and the mix's expected
    UNSHARED prompt tokens and output tokens a request to within 5 %: so the
    cell offers the load its rate says (the rule of the other mixes, with
    what a request does not share counted in its prompt's place)."""
    want = expected_tokens(mix, rate_rps, seconds)
    for seed in range(1, limit):
        s = schedule({**mix, "traffic_seed": seed}, rate_rps, seconds)
        n = len(s["due_s"])
        if abs(n - rate_rps * seconds) > 2.5:
            continue
        got = {"unshared": (s["prompt_tokens"] - s["shared_tokens"]).sum() / n,
               "output": s["output_tokens"].sum() / n}
        if all(abs(got[k] / want[k] - 1.0) <= 0.05 for k in want):
            return seed
    raise ValueError("no seed under the limit fits the rule")
