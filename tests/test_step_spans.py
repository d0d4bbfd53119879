"""The engine's named step phases and its recompute counter.

Every phase of ``Engine.step`` (``repro.serving.spans``) lands in the
profiler's trace as a host span and in ``StepMetrics.phase_ms``;
``StepMetrics.recomputed_tokens`` counts the positions a step computes
again after a recompute preemption."""
import random
import tempfile
from collections import Counter
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from conftest import make_engine
from repro.serving import Request, SamplingParams
from repro.serving.runner import ModelRunner

PHASES = {"engine.step", "engine.schedule", "engine.copies",
          "runner.prepare", "runner.dispatch", "runner.fetch",
          "engine.advance", "engine.record"}
CHILDREN = PHASES - {"engine.step"}


def _host_span_names(pd) -> set:
    return {ev.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events}


def _check_phase_ms(m):
    assert set(m.phase_ms) == PHASES, m.phase_ms
    assert all(v >= 0.0 for v in m.phase_ms.values()), m.phase_ms
    # the children run one after another inside engine.step
    assert sum(m.phase_ms[k] for k in CHILDREN) <= m.phase_ms["engine.step"]
    # the existing timers cover the same intervals as their spans
    assert m.dispatch_ms == pytest.approx(
        m.phase_ms["runner.dispatch"] + m.phase_ms["runner.fetch"])


def test_every_phase_lands_in_the_trace_and_in_step_metrics():
    eng, _ = make_engine()
    eng.submit(Request(rid="a", prompt=list(range(12)),
                       sampling=SamplingParams(max_new_tokens=3)))
    eng.step()                          # compile outside the trace
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            m = eng.step()
        pd = ProfileData.from_file(
            str(sorted(Path(tmp).rglob("*.xplane.pb"))[-1]))
    assert PHASES <= _host_span_names(pd)
    _check_phase_ms(m)
    # host_build_ms: schedule, the restores before the step (one of the
    # two engine.copies spans) and prepare
    build = m.phase_ms["engine.schedule"] + m.phase_ms["runner.prepare"]
    assert build <= m.host_build_ms <= build + m.phase_ms["engine.copies"]


def test_async_steps_record_the_same_phases():
    """The pipelined step names the same work: its fetch is the completed
    step's, and dispatch_ms is that blocked wait."""
    eng, _ = make_engine(async_scheduling=True)
    eng.submit(Request(rid="a", prompt=list(range(12)),
                       sampling=SamplingParams(max_new_tokens=4)))
    eng.run_until_done(max_steps=50)
    assert eng.async_scheduling
    full = [m for m in eng.metrics if "runner.fetch" in m.phase_ms
            and "runner.dispatch" in m.phase_ms]
    assert full
    for m in full:
        assert set(m.phase_ms) == PHASES, m.phase_ms
        assert m.dispatch_ms == pytest.approx(m.phase_ms["runner.fetch"])
        assert sum(m.phase_ms[k] for k in CHILDREN) \
            <= m.phase_ms["engine.step"]


def _dispatched_positions(monkeypatch):
    """Every (rid, position) handed to ModelRunner.prepare, in order."""
    seen = []
    orig = ModelRunner.prepare

    def prepare(self, items, *a, **kw):
        for req, n, start in items:
            seen.extend((req.rid, p) for p in range(start, start + n))
        return orig(self, items, *a, **kw)

    monkeypatch.setattr(ModelRunner, "prepare", prepare)
    return seen


@pytest.mark.parametrize("async_", [False, True])
def test_recomputed_tokens_count_the_positions_computed_again(
        monkeypatch, async_):
    """A pool below the working set forces recompute preemption. With the
    prefix cache off every position of a re-admitted request is computed
    again, so the tokens recomputed are the positions dispatched more than
    once: counted here from what reached the runner, apart from the
    scheduler's high-water marks."""
    seen = _dispatched_positions(monkeypatch)
    rng = random.Random(50)
    eng, _ = make_engine(kv_pool_bytes=60_000, max_running=4,
                         max_num_batched_tokens=256,
                         enable_prefix_caching=False,
                         async_scheduling=async_)
    for i in range(6):
        eng.submit(Request(
            rid=f"r{i}",
            prompt=[(11 * i + j) % 50 for j in range(rng.randint(18, 26))],
            sampling=SamplingParams(max_new_tokens=rng.randint(10, 16))))
    eng.run_until_done(max_steps=2000)
    assert len(eng.finished) == 6
    assert eng.scheduler.preemption_count > 0
    again = sum(c - 1 for c in Counter(seen).values())
    assert again > 0
    assert sum(m.recomputed_tokens for m in eng.metrics) == again
    assert all(0 <= m.recomputed_tokens <= m.batched_tokens
               for m in eng.metrics)


def test_no_preemption_recomputes_nothing():
    eng, _ = make_engine()
    for i in range(3):
        eng.submit(Request(rid=f"r{i}", prompt=list(range(10 + i)),
                           sampling=SamplingParams(max_new_tokens=4)))
    eng.run_until_done()
    assert eng.scheduler.preemption_count == 0
    assert all(m.recomputed_tokens == 0 for m in eng.metrics)
