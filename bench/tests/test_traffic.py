"""The one generator: every seed offers the same sizes in the same order,
with token ids of its own."""

import numpy as np
import pytest

from bench import traffic

MIXES = sorted(p.stem for p in traffic.TRAFFIC_DIR.glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_offer_one_set_of_sizes(mix):
    m = traffic.load(mix)
    block = m["block"]
    gen = traffic.generator(mix)
    a = gen(m, 1, 49155, 4 * block)
    b = gen(m, 2 ** 33 + 5, 49155, 4 * block)

    def sizes(ss):
        return [(t.new_tokens.size, t.output, t.think_s) for s in ss
                for t in s.turns] + [s.arrival_s for s in ss]
    assert sizes(a) == sizes(b)
    assert not np.array_equal(a[0].turns[0].new_tokens[:8],
                              b[0].turns[0].new_tokens[:8])


def test_sessions_keep_to_their_limits():
    m = traffic.load("sessions")
    ss = traffic.sessions(m, 3, 49155, 256)
    turns = [len(s.turns) for s in ss]
    assert max(turns) <= m["turns"]["max"] and min(turns) >= 1
    for s in ss:
        n = s.prefix.size + sum(t.new_tokens.size + t.output
                                for t in s.turns)
        assert n <= m["max_context"]
        assert s.prefix.size == m["system_prompt"]["tokens"]
    arrivals = [s.arrival_s for s in ss]
    assert arrivals == sorted(arrivals)
    rate = (len(ss) - 1) / arrivals[-1]
    assert rate == pytest.approx(m["arrival"]["rate_per_s"], rel=0.1)
    # Zipf(1) over 4 prompts: the first is the most shared
    firsts = [int(s.prefix[0]) for s in ss]
    counts = sorted((firsts.count(v) for v in set(firsts)), reverse=True)
    assert len(counts) == 4 and counts[0] > 1.5 * counts[-1]


def test_lognormal_quantiles_hold_the_median():
    q = traffic.lognormal_quantiles(
        dict(median=1024, sigma=0.7, min=128, max=3072), 64)
    assert q.min() >= 128 and q.max() <= 3072
    assert 900 <= np.median(q) <= 1150


def test_steady_start_moves_output_into_the_prompt():
    m = dict(traffic.load("sessions"), arrival=dict(kind="closed", clients=4),
             system_prompt=None)
    s = traffic.sessions(m, 5, 1000, 1)[0]
    t = traffic.steady_start(s, 0.5, np.random.default_rng(0), 1000)
    moved = t.turns[0].new_tokens.size - s.turns[0].new_tokens.size
    assert moved + t.turns[0].output == s.turns[0].output
    assert t.turns[0].output >= 1
