"""Drives the system under test: ``Engine.submit`` and ``Engine.step`` in one
process, open loop, with every output token stamped on the host clock.

A request is timed from when it was *due*, not from when the loop got round
to submitting it, so a long step counts against every request that fell due
during it. Tokens are stamped when ``Request.output`` grows after a
``step()`` returns. The loop records, per step, its wall interval and the
``StepMetrics`` the engine returned, and per request its due, submit, first
dispatch and token times.

The loop never holds back a request that is due and never waits while the
engine has work: it submits every due request, then steps, and sleeps only
when the engine is idle, until the next request falls due.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from .traffic import Session, Stream


@dataclasses.dataclass
class ReqRecord:
    rid: str
    due: float
    submitted: float = 0.0
    first_dispatch: Optional[float] = None   # start of the first step with it
    token_times: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    prompt_len: int = 0
    max_new: int = 0
    prefix_hit: int = 0                      # tokens served from the cache


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    metrics: object                          # repro StepMetrics
    prefill: bool                            # carried prompt tokens
    flops: float = 0.0                       # model FLOPs (flop_model)


class Feeder:
    """One engine fed by one stream. ``clock`` is the host clock in seconds
    since the stream began; tests pass a virtual one."""

    def __init__(self, engine, stream: Stream, flop_model,
                 clock: Callable[[], float],
                 sleep: Callable[[float], None] = time.sleep):
        self.eng = engine
        self.flop_model = flop_model
        self.clock = clock
        self.sleep = sleep
        self.due: List[tuple] = []           # heap of (due, seq, session)
        self._n = 0
        for t, s in stream.first_due():
            self._push(t, s)
        self.records: Dict[str, ReqRecord] = {}
        self.live: Dict[str, tuple] = {}     # rid -> (request, session)
        self.steps: List[StepRecord] = []
        self.finished: List[tuple] = []      # (record, request)
        self.preemptions = 0

    def _push(self, t: float, s: Session) -> None:
        heapq.heappush(self.due, (t, self._n, s))
        self._n += 1

    def next_due(self) -> Optional[float]:
        return self.due[0][0] if self.due else None

    # ---------------------------------------------------------------- loop
    def submit_due(self, now: float, make_request) -> None:
        """Submit every request that is due."""
        while self.due and self.due[0][0] <= now:
            t, _, s = heapq.heappop(self.due)
            turn = s.turns[s.next_turn]
            req = make_request(turn.rid, s.prompt(), turn.max_new_tokens)
            with TraceAnnotation("bench.submit"):
                self.eng.submit(req)
            self.records[req.rid] = ReqRecord(
                rid=req.rid, due=t, submitted=self.clock(),
                prompt_len=len(req.prompt), max_new=turn.max_new_tokens)
            self.live[req.rid] = (req, s)

    def run(self, t_end: float, make_request,
            until: Optional[Callable[[], bool]] = None) -> None:
        """Serve until the clock passes ``t_end`` (or ``until()`` holds)."""
        while True:
            now = self.clock()
            if now >= t_end or (until is not None and until()):
                return
            self.submit_due(now, make_request)
            if not self.eng.scheduler.has_work():
                nxt = self.next_due()
                wait = (t_end if nxt is None else min(nxt, t_end)) - now
                if wait > 0:
                    with TraceAnnotation("bench.wait"):
                        self.sleep(wait)
                continue
            self.one_step()

    def one_step(self) -> None:
        eng = self.eng
        before = {rid: (len(r.output), _computed(r))
                  for rid, (r, _) in self.live.items()}
        pre0 = eng.scheduler.preemption_count
        t0 = self.clock()
        with TraceAnnotation("bench.step"):
            m = eng.step()
        t1 = self.clock()
        self.preemptions += eng.scheduler.preemption_count - pre0
        flops = 0.0
        done = []
        for rid, (req, sess) in self.live.items():
            rec = self.records[rid]
            n_out0, c0 = before[rid]
            c1 = _computed(req)
            if rec.first_dispatch is None and req.started:
                rec.first_dispatch = t0
                rec.prefix_hit = req.seq.prefix_hit_tokens
            if c1 != c0:
                # positions the device computed this step: after a fresh
                # admission (or a preemption) it starts past the cache hit
                lo = c0 if 0 < c0 < c1 else req.seq.prefix_hit_tokens
                if c1 > lo:
                    flops += self.flop_model.token_flops(lo, c1)
            n_new = len(req.output) - n_out0
            if n_new > 0:
                rec.token_times.extend([t1] * n_new)
                flops += self.flop_model.logits_flops() * n_new
            if req.status.name == "FINISHED":
                rec.finished = t1
                done.append(rid)
        for rid in done:
            req, sess = self.live.pop(rid)
            self.finished.append((self.records[rid], req))
            nxt = Stream.after_reply(sess, req.prompt, req.output, t1)
            if nxt is not None:
                self._push(nxt, sess)
        if m is not None:
            self.steps.append(StepRecord(t0=t0, t1=t1, metrics=m,
                                         prefill=m.prefill_tokens > 0,
                                         flops=flops))


def _computed(req) -> int:
    return req.seq.num_computed if req.seq is not None else 0
