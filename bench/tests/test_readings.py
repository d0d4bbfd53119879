"""The per-layer readings that need more than a count: the device share of
peak, which takes its seconds from the trace and nothing else."""
from types import SimpleNamespace

import pytest

from bench import readings


def step(t0, t1, flops, prefill=False):
    return SimpleNamespace(t0=t0, t1=t1, flops=flops, prefill=prefill)


def view(trace, traced_steps, steps=()):
    return SimpleNamespace(trace=trace, traced_steps=list(traced_steps),
                           steps=list(steps), peak_flops=100.0)


def test_mfu_is_the_traced_steps_flops_over_device_busy_seconds():
    # two traced steps of 1 s of wall each; the device was busy 0.5 s of
    # them: host time is not in the denominator
    traced = [step(0.0, 1.0, 20.0), step(1.0, 2.0, 30.0)]
    earlier = [step(-5.0, -4.0, 1e6)]      # before the profiler: not counted
    r = readings.mfu(view({"busy_s": 0.5, "window_s": 2.0}, traced,
                          earlier + traced))
    assert r == pytest.approx(100.0 * 50.0 / (0.5 * 100.0))


@pytest.mark.parametrize("trace", [None, {}, {"busy_s": 0.0, "window_s": 1.0}])
def test_mfu_reads_nothing_without_device_time(trace):
    assert readings.mfu(view(trace, [step(0.0, 1.0, 20.0)])) is None


def test_step_ms_decode_is_the_mean_wall_of_decode_only_steps():
    steps = [step(0.0, 0.2, 1.0), step(0.2, 0.6, 1.0),
             step(0.6, 1.6, 1.0, prefill=True)]
    assert readings.step_ms_decode(view(None, [], steps)) == pytest.approx(300.0)
