"""The open-loop feeder, over a fake engine whose steps take as long as the
test says."""
import dataclasses
import enum

from bench.flops import FlopModel
from bench.serve import Feeder
from bench.tests.test_traffic import CHAT as MIX
from bench.traffic import Stream
FLOPS = FlopModel(layers=1, d_model=8, heads=2, kv_heads=1, head_dim=4,
                  d_ff=16, vocab=10, tied=True)


class Status(enum.Enum):
    RUNNING = 1
    FINISHED = 2


@dataclasses.dataclass
class Seq:
    num_computed: int = 0
    prefix_hit_tokens: int = 0


@dataclasses.dataclass
class Req:
    rid: str
    prompt: list
    max_new: int
    output: list = dataclasses.field(default_factory=list)
    status: Status = Status.RUNNING
    started: bool = False
    seq: Seq = dataclasses.field(default_factory=Seq)


@dataclasses.dataclass
class Metrics:
    prefill_tokens: int
    running: int            # requests still running after the step


class FakeEngine:
    """Prefills a prompt in one step, then one token per step; a step
    takes ``step_s(n_running)`` on the clock it is given."""

    def __init__(self, clock, step_s):
        self.clock, self.step_s = clock, step_s
        self.running = []
        self.batches = []
        self.scheduler = self
        self.preemption_count = 0

    def has_work(self):
        return bool(self.running)

    def submit(self, req):
        self.running.append(req)

    def step(self):
        self.batches.append(sorted(r.rid.split(".")[-1] for r in self.running))
        prefill = 0
        for r in self.running:
            r.started = True
            if r.seq.num_computed < len(r.prompt):
                prefill += len(r.prompt)
                r.seq.num_computed = len(r.prompt)
            else:
                r.seq.num_computed += 1
            r.output.append(7)
            if len(r.output) >= r.max_new:
                r.status = Status.FINISHED
        self.running = [r for r in self.running
                        if r.status is not Status.FINISHED]
        self.clock.t += self.step_s(len(self.running))
        return Metrics(prefill_tokens=prefill, running=len(self.running))


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 0.0)


def serve(step_s, seconds=40.0):
    clock = Clock()
    eng = FakeEngine(clock, step_s)
    stream = Stream(MIX, 9, seconds, 1000)
    d = Feeder(eng, stream, FLOPS, clock=clock, sleep=clock.sleep)
    d.run(seconds, lambda rid, p, n: Req(rid=rid, prompt=p, max_new=n))
    return d, eng


def test_open_loop_times_each_request_from_its_due_time():
    d, _ = serve(lambda n: 0.05)
    assert d.records
    for r in d.records.values():
        assert r.submitted >= r.due
        if r.token_times:
            assert r.token_times[0] > r.submitted


def test_a_due_request_waits_only_for_the_step_in_flight():
    """Under slower or faster steps no due request waits for more than the
    step in flight when it fell due, and the loop steps whenever the engine
    has work: it never holds a request back, nor sleeps while one runs."""
    for factor in (1.1, 0.9):
        step = 0.05 * factor
        d, eng = serve(lambda n: step)
        assert len(eng.batches) > 100
        for r in d.records.values():
            assert r.due <= r.submitted <= r.due + step + 1e-9
        # steps follow one another without a gap while requests run
        gaps = [b.t0 - a.t1 for a, b in zip(d.steps, d.steps[1:])
                if a.metrics.running]
        assert gaps and max(gaps) < 1e-9
