"""The traffic generator: deterministic per seed, and true to its mix file."""
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import (Stream, exponential_quantiles, load_mix,
                           lognormal_quantiles, zipf_counts)

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2 ** 31 + 12345
# mixes of the kinds no cell uses yet, at the sizes a cell would: an open
# loop of multi-turn chat sessions, and one of long single requests
CHAT = {
    "arrival": {"process": "poisson", "rate_per_s": 0.3},
    "sessions": {"system_prompts": 8, "system_prompt_tokens": 1024,
                 "zipf_s": 1.1, "turns_min": 1, "turns_max": 6,
                 "think_s_mean": 3.0},
    "prompt_tokens": {"median": 160, "sigma": 0.6, "min": 64, "max": 512},
    "output_tokens": {"median": 200, "sigma": 0.6, "min": 64, "max": 512},
    "max_context": 4096,
    "warmup": {"seconds": 20},
}
DOCS = {
    "arrival": {"process": "poisson", "rate_per_s": 2.0},
    "prompt_tokens": {"median": 4096, "sigma": 0.45, "min": 2048, "max": 8192},
    "output_tokens": {"median": 96, "sigma": 0.6, "min": 32, "max": 256},
    "max_context": 32768,
    "warmup": {"seconds": 20},
}
MIXES = {"chat_shared": CHAT, "long_docs": DOCS,
         **{p.stem: load_mix(p) for p in sorted(TRAFFIC.glob("*.json"))}}


def stream(name, seed, horizon=60.0, vocab=1000):
    return Stream(MIXES[name], seed, horizon, vocab)


def flat(st):
    return [(s.start_s, s.system, [(t.rid, t.user, t.max_new_tokens)
                                   for t in s.turns], s.think_s)
            for s in st.sessions]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream(name):
    assert flat(stream(name, BIG_SEED)) == flat(stream(name, BIG_SEED))
    assert flat(stream(name, BIG_SEED)) != flat(stream(name, 7))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_one_multiset_of_sizes(name):
    """Two seeds draw the same lengths and gaps, in another order."""
    def sizes(st):
        users = sorted(len(t.user) for s in st.sessions for t in s.turns)
        outs = sorted(t.max_new_tokens for s in st.sessions for t in s.turns)
        return users, outs
    a, b = stream(name, 1), stream(name, BIG_SEED)
    mix = MIXES[name]
    if not mix.get("sessions"):
        assert sizes(a) == sizes(b)
    if mix["arrival"]["process"] == "poisson":
        # the gaps are one permutation of the same quantiles; each stream
        # leaves out a different last one
        def gaps(st):
            return Counter(np.round(np.diff([s.start_s for s in st.sessions]),
                                    6).tolist())
        diff = (gaps(a) - gaps(b)) + (gaps(b) - gaps(a))
        assert sum(diff.values()) <= 2


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_context_match_the_file(name):
    mix = MIXES[name]
    st = stream(name, BIG_SEED)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for s in st.sessions:
        ctx = len(s.system)
        for t in s.turns:
            assert p["min"] <= len(t.user) <= p["max"]
            assert o["min"] <= t.max_new_tokens <= o["max"]
            ctx += len(t.user) + t.max_new_tokens
            assert ctx <= mix["max_context"]
    users = [len(t.user) for s in st.sessions for t in s.turns]
    assert abs(np.median(users) / p["median"] - 1) < 0.15


def test_poisson_rate_matches_the_file():
    mix = DOCS
    st = Stream(mix, BIG_SEED, 200.0, 1000)
    starts = np.array([s.start_s for s in st.sessions])
    rate = mix["arrival"]["rate_per_s"]
    # the gaps are the exponential's quantiles: their mean is 1 / rate
    assert abs(np.mean(np.diff(starts)) * rate - 1) < 0.05
    assert starts[-1] >= 200.0
    # the shuffled gaps cluster as a Poisson process's do: as many short
    # gaps follow short ones as chance gives, not the alternation of an
    # evened-out order
    gaps = np.diff(starts)
    short = gaps < np.median(gaps)
    runs = 1 + int(np.sum(short[1:] != short[:-1]))
    assert abs(runs / len(gaps) - 0.5) < 0.1


def test_backlog_is_due_at_once():
    st = stream("reasoning_batch", 3)
    mix = load_mix(TRAFFIC / "reasoning_batch.json")
    assert len(st.sessions) == mix["backlog_requests"]
    assert {s.start_s for s in st.sessions} == {0.0}


def test_chat_sessions_share_system_prompts():
    mix = CHAT
    st = Stream(mix, BIG_SEED, 60.0, 1000)
    sp = mix["sessions"]
    systems = {tuple(s.system) for s in st.sessions}
    assert len(systems) == sp["system_prompts"]
    assert {len(x) for x in systems} == {sp["system_prompt_tokens"]}
    counts = sorted((sum(tuple(s.system) == x for s in st.sessions)
                     for x in systems), reverse=True)
    assert counts == zipf_counts(sp["system_prompts"], sp["zipf_s"],
                                 len(st.sessions))
    turns = [len(s.turns) for s in st.sessions]
    assert min(turns) >= sp["turns_min"] and max(turns) <= sp["turns_max"]


def test_turn_prompts_carry_the_history():
    st = Stream(CHAT, 5, 60.0, 1000)
    s = next(s for s in st.sessions if len(s.turns) >= 2)
    p0 = s.prompt()
    assert p0 == s.system + s.turns[0].user
    reply = list(range(s.turns[0].max_new_tokens))
    due = Stream.after_reply(s, p0, reply, 10.0)
    assert due == 10.0 + s.think_s[0]
    assert s.prompt() == p0 + reply + s.turns[1].user


def test_quantile_helpers():
    q = lognormal_quantiles({"median": 100, "sigma": 0.5, "min": 10,
                             "max": 1000}, 101)
    assert q[50] == 100 and list(q) == sorted(q)
    e = exponential_quantiles(2.0, 10000)
    assert abs(e.mean() / 2.0 - 1) < 0.01
    assert sum(zipf_counts(8, 1.1, 37)) == 37
