"""Per-layer quantities of one run, shared by the readers in ``bench/metrics``.

Each function takes the run's ``RunView`` (``bench/harness.py``) and returns
a number, or None where the run holds nothing to read. Shares are in %.
"""
from __future__ import annotations

from typing import Optional

from .stats import percentile


def _mean(xs) -> Optional[float]:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def prefix_hit_frac(run) -> Optional[float]:
    """Prompt tokens served from the prefix cache at admission, over the
    prompt tokens of every request first dispatched in the window."""
    adm = [r for r in run.records
           if r.first_dispatch is not None and run.w0 <= r.first_dispatch < run.w1]
    total = sum(r.prompt_len for r in adm)
    if not total:
        return None
    return 100.0 * sum(r.prefix_hit for r in adm) / total


def queue_wait_p95_ms(run) -> Optional[float]:
    """Due time to the start of the first step that computes the request,
    p95 over every request due in the window (censored at the close)."""
    waits = []
    for r in run.records:
        if run.w0 <= r.due < run.w1:
            t = r.first_dispatch
            t = run.w1 if t is None or t > run.w1 else t
            waits.append(1e3 * (t - r.due))
    return percentile(waits, 95)


def pool_used_frac(run) -> Optional[float]:
    return _mean(100.0 * s.metrics.used_units / run.total_units
                 for s in run.steps)


def decode_batch_mean(run) -> Optional[float]:
    return _mean(s.metrics.decode_batch for s in run.steps)


def host_ms_per_step(run) -> Optional[float]:
    return _mean(s.metrics.host_build_ms + s.metrics.host_sample_ms
                 for s in run.steps)


def step_ms_decode(run) -> Optional[float]:
    """Mean host-clock wall of the steps that carried no prompt token."""
    walls = [s.t1 - s.t0 for s in run.steps if not s.prefill]
    return 1e3 * sum(walls) / len(walls) if walls else None


def mfu(run) -> Optional[float]:
    """The whole step's share of the chip's bf16 peak: model FLOPs
    (``bench/flops.py``) of the steps the profiler traced, over the seconds
    in which the trace shows an operation on the device. The loop is
    synchronous, so each traced step's device work lies inside the traced
    window, and nothing else runs on the device there."""
    tr = run.trace
    if not tr or tr["busy_s"] <= 0:
        return None
    flops = sum(s.flops for s in run.traced_steps)
    if flops <= 0:
        return None
    return 100.0 * flops / (tr["busy_s"] * run.peak_flops)


def device_idle_frac(run) -> Optional[float]:
    tr = run.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
