"""The one traffic generator: turns a mix's data file and a seed into requests.

A mix is a JSON file ``bench/traffic/<name>.json``. Its keys:

* ``arrival``: ``{"process": "poisson", "rate_per_s": r}`` (open loop: first
  turns are due at a fixed rate, whatever the server does) or
  ``{"process": "backlog"}`` (every request is due at once, an offline batch).
* ``prompt_tokens`` / ``output_tokens``: length distributions
  ``{"median": m, "sigma": s, "min": a, "max": b}`` (lognormal, clipped).
  In a session mix ``prompt_tokens`` is the new user message of each turn.
* ``sessions`` (optional): multi-turn chat. ``system_prompts`` distinct
  system prompts of ``system_prompt_tokens`` tokens, picked with Zipf
  popularity ``zipf_s``; ``turns_min``..``turns_max`` turns; think time
  between a reply and the next turn exponential with mean ``think_s_mean``.
  A turn's prompt is the system prompt, the whole history (earlier user
  messages and the replies served to them), then the new message.
* ``max_context``: no request's prompt plus output exceeds it (a session
  ends before a turn that would).
* ``warmup``: ``{"seconds": s}`` runs the stream for ``s`` seconds before
  the window.

Every seed draws the same lengths, gaps and turn counts: the lognormal and
exponential draws are the quantiles ``(i + 0.5) / n`` of their
distributions, and the seed only shuffles each of them, independently (and
picks the token ids). So two seeds do the same work in another order, and
shuffled exponential gaps still cluster as Poisson arrivals do.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

_STD = NormalDist()


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    arrival = mix["arrival"]["process"]
    if arrival not in ("poisson", "backlog"):
        raise ValueError(f"{path}: unknown arrival process {arrival!r}")
    return mix


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator per (seed, purpose): any whole number seeds it."""
    return np.random.default_rng([seed % (1 << 64), *stream.encode()])


def lognormal_quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified lognormal lengths, ascending, clipped to [min, max]."""
    mu = math.log(dist["median"])
    out = [mu + dist["sigma"] * _STD.inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = np.rint(np.exp(out)).astype(np.int64)
    return np.clip(vals, dist["min"], dist["max"])


def exponential_quantiles(mean: float, n: int) -> np.ndarray:
    return np.array([-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)])


def zipf_counts(k: int, s: float, n: int) -> List[int]:
    """How many of n draws pick each of k items under Zipf(s), rounded so
    that they sum to n (largest remainders)."""
    w = np.array([1.0 / (i + 1) ** s for i in range(k)])
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


@dataclasses.dataclass
class Turn:
    """One request of the stream. ``prefix`` is the session's system prompt
    and history known when the stream is drawn; the history grows with the
    replies actually served (``Session.history``)."""
    rid: str
    user: List[int]
    max_new_tokens: int


@dataclasses.dataclass
class Session:
    sid: int
    start_s: float                  # due time of the first turn
    system: List[int]
    turns: List[Turn]
    think_s: List[float]            # wait after each reply (last unused)
    history: List[int] = dataclasses.field(default_factory=list)
    next_turn: int = 0

    def prompt(self) -> List[int]:
        return self.system + self.history + self.turns[self.next_turn].user


class Stream:
    """The requests of one run, drawn from (mix, seed, horizon, vocab).
    ``token_stream`` names the draw of the token ids alone: another name
    gives the same lengths, sharing and arrivals with other tokens.

    ``first_due()`` lists (due_s, session) for every session's first turn;
    after a turn is served, ``after_reply`` appends the reply to the
    session's history and says when its next turn is due (reply time plus
    think time), or None when the session is over. Single-turn mixes are
    sessions of one turn with no system prompt.
    """

    def __init__(self, mix: dict, seed: int, horizon_s: float, vocab: int,
                 token_stream: str = "tokens"):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.token_stream = token_stream
        self.sessions = self._draw(horizon_s)

    # ------------------------------------------------------------ drawing
    def _count(self, horizon_s: float) -> int:
        arr = self.mix["arrival"]
        if arr["process"] == "backlog":
            return int(self.mix["backlog_requests"])
        # enough first turns for the horizon, plus slack for the quantile
        # gaps' order (a seed may put the long gaps first)
        return int(math.ceil(arr["rate_per_s"] * horizon_s * 1.25)) + 8

    def _draw(self, horizon_s: float) -> List[Session]:
        mix, vocab = self.mix, self.vocab
        n = self._count(horizon_s)
        shuffler = rng_for(self.seed, "order")

        def order(values: np.ndarray) -> List:
            return [v.item() for v in shuffler.permutation(values)]

        toks = rng_for(self.seed, self.token_stream)
        arr = mix["arrival"]
        if arr["process"] == "poisson":
            gaps = order(exponential_quantiles(1.0 / arr["rate_per_s"], n))
            starts = np.cumsum([0.0] + gaps[:-1]).tolist()
        else:
            starts = [0.0] * n
        sess = mix.get("sessions")
        max_ctx = int(mix["max_context"])
        if sess:
            k = sess["system_prompts"]
            systems = [toks.integers(0, vocab, sess["system_prompt_tokens"]
                                     ).tolist() for _ in range(k)]
            picks = order(np.repeat(
                np.arange(k), zipf_counts(k, sess["zipf_s"], n)))
            span = sess["turns_max"] - sess["turns_min"] + 1
            n_turns = order(sess["turns_min"] + np.arange(n) % span)
        else:
            systems, picks, n_turns = [[]], [0] * n, [1] * n
        total = sum(n_turns)
        users = order(lognormal_quantiles(mix["prompt_tokens"], total))
        outs = order(lognormal_quantiles(mix["output_tokens"], total))
        think_mean = sess["think_s_mean"] if sess else 1.0
        thinks = order(exponential_quantiles(think_mean, total))
        sessions, j = [], 0
        for sid in range(n):
            system = systems[picks[sid]]
            turns, think, ctx = [], [], len(system)
            for t in range(n_turns[sid]):
                u, o = users[j], outs[j]
                j += 1
                if ctx + u + o > max_ctx:
                    break                     # the session ends here
                turns.append(Turn(rid=f"s{sid}t{t}",
                                  user=toks.integers(0, vocab, u).tolist(),
                                  max_new_tokens=o))
                think.append(thinks[j - 1])
                ctx += u + o
            if not turns:                     # a lone turn too long: clip it
                u = min(users[j - 1], max_ctx - len(system) - 1)
                o = max(1, min(outs[j - 1], max_ctx - len(system) - u))
                turns.append(Turn(rid=f"s{sid}t0",
                                  user=toks.integers(0, vocab, u).tolist(),
                                  max_new_tokens=o))
                think.append(0.0)
            sessions.append(Session(sid=sid, start_s=starts[sid],
                                    system=system, turns=turns, think_s=think))
        return sessions

    # ------------------------------------------------------------ serving
    def first_due(self) -> List[tuple]:
        return [(s.start_s, s) for s in self.sessions]

    @staticmethod
    def after_reply(session: Session, prompt: List[int], reply: List[int],
                    t_reply: float) -> Optional[float]:
        """Record a served turn; the next turn's due time, or None."""
        k = session.next_turn
        session.history = prompt[len(session.system):] + list(reply)
        session.next_turn += 1
        if session.next_turn >= len(session.turns):
            return None
        return t_reply + session.think_s[k]


def describe(mix: dict) -> Dict[str, object]:
    """The mix's parameters in one flat line, for the run's log."""
    out = {"arrival": mix["arrival"]["process"]}
    if "rate_per_s" in mix["arrival"]:
        out["rate_per_s"] = mix["arrival"]["rate_per_s"]
    out["prompt_median"] = mix["prompt_tokens"]["median"]
    out["output_median"] = mix["output_tokens"]["median"]
    if mix.get("sessions"):
        out["system_prompts"] = mix["sessions"]["system_prompts"]
    return out
