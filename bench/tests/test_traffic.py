"""The traffic generator: seeded, stratified, clipped, and for sessions
driven through their history. Besides the cell's mix, the mixes kept as
test data for later cells (short unshared requests, shared system prompts,
multi-turn chat, bursty chat, long and short prompts in one queue) show
that the shapes a later cell needs are parameters, not code."""
import dataclasses
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as T

V = 49155
DATA = Path(__file__).resolve().parent / "data"
CELL_MIXES = ["offline-decode"]
DATA_MIXES = ["short-unshared", "shared-prefix", "chat-sessions", "chat-bursty",
              "mixed-long-short"]


def load(name):
    return T.load_mix(name, DATA) if name in DATA_MIXES else T.load_mix(name)


def with_rate(mix, rate):
    return dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))


def lengths(tr):
    """The sorted user-text lengths and output lengths of every turn."""
    turns = [t for s in tr.sessions for t in s.turns]
    return sorted(len(t.user) for t in turns), sorted(t.out_len for t in turns)


@pytest.mark.parametrize("name", CELL_MIXES + DATA_MIXES)
def test_same_seed_same_schedule(name):
    a = T.build(load(name), 2**33 + 7, V, 60.0)
    b = T.build(load(name), 2**33 + 7, V, 60.0)
    assert dataclasses.asdict(a) == dataclasses.asdict(b)
    c = T.build(load(name), 2**33 + 8, V, 60.0)
    assert dataclasses.asdict(a) != dataclasses.asdict(c)


@pytest.mark.parametrize("name", CELL_MIXES + DATA_MIXES)
def test_seeds_offer_the_same_sizes_in_another_order(name):
    a = T.build(load(name), 1, V, 200.0)
    b = T.build(load(name), 2, V, 200.0)
    assert lengths(a) == lengths(b)
    if a.process == "poisson":
        assert sorted(s.start_s for s in a.sessions)[-1] == pytest.approx(
            sorted(s.start_s for s in b.sessions)[-1])


@pytest.mark.parametrize("name", CELL_MIXES + DATA_MIXES)
def test_lengths_stay_in_range_and_context(name):
    m = load(name)
    tr = T.build(m, 3, V, 120.0)
    for s in tr.sessions:
        c = m["classes"][s.cls]
        prompt = s.prompt(tr.system_prompts)
        t = s.turns[0]
        assert c["user_len"]["lo"] <= len(t.user) <= c["user_len"]["hi"]
        assert c["output_len"]["lo"] <= t.out_len <= c["output_len"]["hi"]
        assert len(prompt) + t.out_len <= m["context_cap"]
        assert all(0 <= x < V for x in prompt)


def test_a_balanced_queue_offers_every_seed_the_same_work_in_every_stretch():
    m = T.load_mix("offline-decode")
    assert m["order"] == "balanced"

    def sizes(tr):
        return np.array([(len(s.turns[0].user), s.turns[0].out_len)
                         for s in tr.sessions])

    a, b = T.build(m, 1, V, 60.0), T.build(m, 2**31 + 11, V, 60.0)
    assert np.array_equal(sizes(a), sizes(b))
    assert dataclasses.asdict(a) != dataclasses.asdict(b)     # token ids
    x = sizes(a)
    for start in range(0, len(x) - 40, 7):
        stretch = x[start:start + 40].mean(axis=0)
        assert np.all(np.abs(stretch / x.mean(axis=0) - 1) < 0.25)


@pytest.mark.parametrize("n", [1, 40, 600])
def test_balanced_orders_are_permutations(n):
    for kind in range(len(T._STEPS)):
        assert sorted(T.balanced_order(n, kind)) == list(range(n))


def test_clipping_to_the_context_cap():
    m = dict(load("short-unshared"), context_cap=100)
    for s in T.build(m, 4, V, 120.0).sessions:
        t = s.turns[0]
        assert len(t.user) + t.out_len <= 100 and t.out_len >= 1


def test_stratified_lengths_follow_the_lognormal():
    rng = np.random.default_rng(0)
    spec = {"median": 64, "sigma": 0.8, "lo": 8, "hi": 256}
    x = T.lognormal_lengths(spec, 1001, rng)
    assert np.median(x) == 64 and x.min() >= 8 and x.max() <= 256


@pytest.mark.parametrize("name", ["short-unshared", "shared-prefix"])
def test_open_arrivals_keep_their_rate(name):
    m = with_rate(load(name), 2.0)
    tr = T.build(m, 5, V, 100.0)
    assert len(tr.sessions) == int(np.ceil(2.0 * 100.0))
    span = max(s.start_s for s in tr.sessions)
    assert 0.9 * 100.0 < span < 1.1 * 100.0


def test_bursts_keep_the_mean_rate_and_fall_in_the_on_part():
    m = with_rate(load("chat-bursty"), 1.0)
    b = m["arrivals"]["bursts"]
    tr = T.build(m, 6, V, 300.0)
    starts = np.array([s.start_s for s in tr.sessions])
    assert len(starts) == 300
    assert np.all(np.mod(starts, b["period_s"]) < b["on_s"])
    assert 0.9 * 300.0 < starts.max() < 1.1 * 300.0


def test_classes_keep_their_shares():
    m = load("mixed-long-short")
    tr = T.build(m, 7, V, 400.0)
    n = len(tr.sessions)
    use = Counter(s.cls for s in tr.sessions)
    for ci, c in enumerate(m["classes"]):
        assert abs(use[ci] - c["share"] * n) <= 1
    long = [len(s.turns[0].user) for s in tr.sessions if s.cls == 0]
    assert min(long) >= m["classes"][0]["user_len"]["lo"]


@pytest.mark.parametrize("name", ["chat-sessions", "chat-bursty", "shared-prefix"])
def test_system_prompts_and_turns(name):
    m = load(name)
    c = m["classes"][0]
    tr = T.build(m, 6, V, 200.0)
    sp = c["system_prompts"]
    assert len(tr.system_prompts) == sp["count"]
    assert all(sp["lo"] <= len(p) <= sp["hi"] for p in tr.system_prompts)
    turns = c.get("turns", {"lo": 1, "hi": 1})
    assert all(turns["lo"] <= len(s.turns) <= turns["hi"] for s in tr.sessions)
    # Zipf popularity: the first system prompt is the most used
    use = Counter(s.system for s in tr.sessions)
    assert use[0] == max(use.values())
    assert all(t.think_s >= 0 for s in tr.sessions for t in s.turns)
    for s in tr.sessions:
        assert s.prompt(tr.system_prompts)[:len(tr.system_prompts[s.system])] \
            == tr.system_prompts[s.system]


def test_the_control_shares_no_prefix():
    tr = T.build(load("short-unshared"), 7, V, 200.0)
    assert not tr.system_prompts
    heads = [tuple(s.prompt([])[:4]) for s in tr.sessions]
    assert len(set(heads)) == len(heads)


def test_topic_tokens_stay_in_their_slice():
    m = T.load_mix("offline-decode")
    src = T.TokenSource(m, V, np.random.default_rng(0))
    width = V // m["tokens"]["topics"]
    for topic in range(m["tokens"]["topics"]):
        toks = src.draw(500, topic)
        assert all(topic * width <= t < (topic + 1) * width for t in toks)
    # Zipf: the slice's first id is the commonest
    toks = src.draw(5000, 0)
    assert Counter(toks).most_common(1)[0][0] == 0


def test_a_mix_without_a_known_process_is_refused(tmp_path):
    (tmp_path / "bad.json").write_text('{"arrivals": {"process": "closed"}, "classes": [{}]}')
    with pytest.raises(ValueError):
        T.load_mix("bad", tmp_path)


def test_a_mix_with_an_unknown_order_is_refused(tmp_path):
    (tmp_path / "bad.json").write_text(
        '{"arrivals": {"process": "backlog"}, "order": "sorted", "classes": [{}]}')
    with pytest.raises(ValueError):
        T.load_mix("bad", tmp_path)
