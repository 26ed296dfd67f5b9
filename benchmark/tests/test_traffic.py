import numpy as np
import pytest

from benchmark import manifest, traffic
from benchmark.generators import sessions

CHAT = manifest.load_json(manifest.HERE + "/traffic/chat-steady.json")
AGENT = manifest.load_json(manifest.HERE + "/traffic/agent-prefix.json")
BIG_SEED = 2**31 + 12345        # the driver's seeds pass 32 signed bits


def _shape(r):
    return (r.due_s, len(r.prompt), r.shared_tokens, r.max_new, r.tenant)


@pytest.mark.parametrize("mix,vocab", [(CHAT, 32000), (AGENT, 152064)])
def test_same_seed_same_requests(mix, vocab):
    a = traffic.generate(mix, 2.0, 20, BIG_SEED, vocab)
    b = traffic.generate(mix, 2.0, 20, BIG_SEED, vocab)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("mix,vocab", [(CHAT, 32000), (AGENT, 152064)])
def test_every_seed_offers_the_same_schedule_with_its_own_tokens(mix, vocab):
    a = traffic.generate(mix, 2.0, 30, 1, vocab)
    b = traffic.generate(mix, 2.0, 30, BIG_SEED, vocab)
    assert list(map(_shape, a)) == list(map(_shape, b))
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    other = traffic.generate({**mix, "traffic_seed": mix["traffic_seed"] + 1},
                             2.0, 30, 1, vocab)
    assert list(map(_shape, other)) != list(map(_shape, a))


def _cells():
    man = manifest.load_manifest()
    return [manifest.load_cell(man, w["name"]) for w in man["workloads"]]


@pytest.mark.parametrize("cell", _cells(), ids=lambda c: c["name"])
def test_the_committed_schedule_offers_the_load_its_rate_says(cell):
    """The rule in the mix file that chose ``traffic_seed``, at the rate
    the cell runs at: it is chosen again whenever that rate moves. Prompt
    tokens are those a request does not share: what is prefilled."""
    mix, rate = cell["traffic_file"], cell["rate_rps"]
    rng = np.random.default_rng(0)
    prompt = (traffic.draw_lengths(mix["history_tokens"], 100000, rng)
              + traffic.draw_lengths(mix["turn_tokens"], 100000, rng)).mean()
    out = traffic.draw_lengths(mix["output_tokens"], 100000, rng).mean()

    def offers(seed):
        s = sessions.schedule({**mix, "traffic_seed": seed}, rate, 51)
        want = rate * 51
        got = s["history_tokens"].sum() + s["turn_tokens"].sum()
        return (abs(len(s["due_s"]) - want) <= 2.5
                and abs(got / (want * prompt) - 1) <= 0.05
                and abs(s["output_tokens"].sum() / (want * out) - 1) <= 0.05)

    first = next(s for s in range(1, 1000) if offers(s))
    assert first == mix["traffic_seed"]
    # and the generator hands out that schedule
    reqs = traffic.generate(mix, rate, 51, 3,
                            cell["config_file"]["vocab_size"])
    assert abs(len(reqs) - rate * 51) <= 2.5


def test_arrivals_are_a_poisson_draw_with_its_clusters():
    due = traffic.arrival_times({"process": "poisson"}, 5.0, 4000,
                                traffic.rng_for(9, 0))
    gaps = np.diff(due)
    assert len(due) == pytest.approx(20000, rel=0.03)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.03)
    # exponential gaps: a tenth of them are under mean * -ln(0.9)
    assert (gaps < 0.2 * 0.10536).mean() == pytest.approx(0.1, abs=0.01)
    # the committed windows keep their clusters too
    for cell in _cells():
        mix, rate = cell["traffic_file"], cell["rate_rps"]
        g = np.diff([r.due_s for r in
                     traffic.generate(mix, rate, 51, 1, 1000)])
        assert g.min() < 0.1 / rate and g.max() > 2.5 / rate


def test_a_sweep_offers_one_pattern_faster():
    slow = traffic.generate(CHAT, 1.0, 40, 1, 32000)
    fast = traffic.generate(CHAT, 2.0, 40, 1, 32000)
    assert len(fast) > len(slow)
    for a, b in zip(slow, fast):
        assert a.due_s == pytest.approx(2 * b.due_s)
        assert (len(a.prompt), a.max_new) == (len(b.prompt), b.max_new)


def test_chat_lengths_follow_the_mix_and_fit_the_engine():
    lens = traffic.draw_lengths(CHAT["turn_tokens"], 20000,
                                traffic.rng_for(1, 1))
    assert lens.min() == 64 and lens.max() == 1536
    assert 385 <= np.median(lens) <= 415
    reqs = traffic.generate(CHAT, 1.0, 51, 3, 32000)
    assert all(64 <= len(r.prompt) <= 1536 for r in reqs)
    assert all(32 <= r.max_new <= 256 for r in reqs)
    assert max(len(r.prompt) + r.max_new for r in reqs) <= 2048
    assert all(r.tenant == -1 and r.shared_tokens == 0 for r in reqs)
    assert 0 < reqs[0].due_s and reqs[-1].due_s < 51
    assert traffic.warm_prompts(CHAT, 3, 32000) == []


def test_agent_prompts_share_their_tenants_prefix():
    reqs = traffic.generate(AGENT, 3.0, 40, 5, 152064)
    prefixes = [p[:-1] for p in traffic.warm_prompts(AGENT, 5, 152064)]
    assert len(prefixes) == 8 and all(len(p) == 2048 for p in prefixes)
    for r in reqs:
        assert r.prompt[:2048] == prefixes[r.tenant]
        assert 32 <= len(r.prompt) - 2048 <= 512 + 128
        assert len(r.prompt) + r.max_new <= 4096
    counts = np.bincount(traffic.draw_zipf(8, 1.0, 50000,
                                           traffic.rng_for(2, 0)),
                         minlength=8)
    w = 1 / np.arange(1, 9)
    assert counts / 50000 == pytest.approx(w / w.sum(), abs=0.01)   # Zipf(1.0)


def test_onoff_keeps_the_mean_rate_and_its_pauses():
    spec = {"process": "onoff", "burst_requests": 10, "gap_s": 1.0}
    due = traffic.arrival_times(spec, 5.0, 2000, traffic.rng_for(4, 0))
    assert len(due) == pytest.approx(10000, rel=0.03)
    gaps = np.diff(due)
    assert (gaps >= 1.0).sum() >= 990           # one pause after each burst
    with pytest.raises(ValueError):
        traffic.arrival_times({**spec, "gap_s": 2.0}, 5.0, 10,
                              traffic.rng_for(4, 0))
