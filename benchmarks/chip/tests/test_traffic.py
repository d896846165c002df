"""The one traffic generator: deterministic for a seed, and the same set of
sizes and gaps for every seed."""
import json
import os
from collections import Counter

import numpy as np
import pytest
from conftest import CHIP

from traffic import gen


def mix(name):
    with open(os.path.join(CHIP, "traffic", f"{name}.json")) as f:
        return json.load(f)


def open_sessions(seed):
    return gen.open_loop(mix("chat"), 0.5, 60.0, 151936,
                         np.random.default_rng(seed))


def shape(sessions):
    return [(s.sid, s.tier, s.arrival_s, s.prompt.tolist(),
             list(s.turns)) for s in sessions]


def test_open_loop_is_deterministic_for_a_seed():
    assert shape(open_sessions(2**31 + 7)) == shape(open_sessions(2**31 + 7))
    assert shape(open_sessions(1)) != shape(open_sessions(2))


def test_open_loop_offers_the_same_work_to_every_seed():
    def work(seed):
        ss = gen.open_loop(mix("chat"), 0.5, 64.0, 151936,
                           np.random.default_rng(seed))
        assert max(s.arrival_s for s in ss) < 64.0
        return ss, (len(ss), Counter(len(s.prompt) for s in ss),
                    Counter(t for s in ss for t in s.turns),
                    Counter(s.tier for s in ss))

    (sa, a), (sb, b) = work(3), work(2**31 + 4)
    # one block of stratified quantiles per quantity
    assert a == b
    assert a[0] == 32 and a[2] == Counter(gen.strata(
        mix("chat")["output"], sum(a[2].values()), np.random.default_rng(0),
        block=sum(a[2].values())).tolist())
    assert a[3]["paid"] == round(0.2 * 32)
    # the same sessions at the same times for every seed; the seed draws
    # only the token ids
    assert [(s.tier, s.arrival_s, len(s.prompt), s.turns) for s in sa] == \
        [(s.tier, s.arrival_s, len(s.prompt), s.turns) for s in sb]
    assert any((x.prompt != y.prompt).any() for x, y in zip(sa, sb))


@pytest.mark.parametrize("name", ["chat", "complete", "overcommit"])
def test_stratified_sizes_stay_in_the_mix_bounds(name):
    m = mix(name)
    vals = gen.strata(m["prompt"], 5 * gen.BLOCK, np.random.default_rng(0))
    assert set(vals.tolist()) <= set(gen.support(m["prompt"]))
    for b in range(5):
        blk = sorted(vals[b * gen.BLOCK:(b + 1) * gen.BLOCK].tolist())
        assert blk == sorted(gen.strata(m["prompt"], gen.BLOCK,
                                        np.random.default_rng(b)).tolist())


def test_chat_prompts_are_seven_warmable_shapes():
    assert gen.support(mix("chat")["prompt"]) == list(range(64, 257, 32))
    assert gen.support(mix("complete")["prompt"]) == [256, 384, 512, 640,
                                                      768]


def test_closed_loop_is_deterministic_and_keeps_sessions_in_bounds():
    m = mix("overcommit")

    def seq(seed):
        loop = gen.ClosedLoop(m, 151936, np.random.default_rng(seed), 512,
                              n_sessions=24)
        out, busy = [], set()
        for _ in range(400):
            sid, tier, prompt, mx, fresh = loop.next(busy)
            out.append((sid, tier, len(prompt), mx, fresh))
            busy = {sid}
        return out, loop

    a, loop = seq(11)
    b, _ = seq(11)
    assert a == b
    ctx = {}
    for sid, _, p, mx, fresh in a:
        ctx[sid] = (p if fresh else ctx[sid]) + mx
        assert ctx[sid] - 1 <= 512        # fits the block table
    assert loop.retired                   # sessions do fill and start over


def test_closed_loop_without_sessions_makes_one_turn_requests():
    loop = gen.ClosedLoop(mix("complete"), 32256, np.random.default_rng(5),
                          1024)
    reqs = [loop.next(set()) for _ in range(2 * gen.BLOCK)]
    assert all(fresh for *_, fresh in reqs)
    assert len({sid for sid, *_ in reqs}) == len(reqs)
    outs = sorted(m for _, _, _, m, _ in reqs[:gen.BLOCK])
    assert 64 <= outs[0] and outs[-1] <= 192


def test_quantiles_of_each_distribution():
    ln = {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 32,
          "max": 256, "round_up": 32}
    assert gen.quantile(ln, 0.5) == 128
    assert gen.quantile(ln, 0.001) == 32 and gen.quantile(ln, 0.999) == 256
    un = {"dist": "uniform", "min": 64, "max": 192}
    assert gen.quantile(un, 0.0) == 64 and gen.quantile(un, 0.9999) == 192
    ch = {"dist": "choice", "values": [1, 2, 3]}
    assert [gen.quantile(ch, q) for q in (0.1, 0.5, 0.9)] == [1, 2, 3]


def test_mean_context_lies_between_prompt_and_table():
    c = gen.mean_context(mix("overcommit"), 512)
    assert 128 < c < 512
