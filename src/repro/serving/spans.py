"""Named host spans of one engine step.

``phase(name, acc)`` times one phase of ``Engine.step`` in two places at
once:

* a ``jax.profiler.TraceAnnotation``, which a running profiler writes into
  its trace on the same clock as the device's operations, so that an idle
  gap on the device can be put down to the host phase open across it (with
  no profiler running it costs about a microsecond);
* ``time.perf_counter``, whose elapsed milliseconds it adds to
  ``acc[name]``: the step's ``StepMetrics.phase_ms``, so that every step
  carries its own split and not only the steps a profiler happened to see.

The phases (the async step uses the same names for the same work):

  engine.step      the whole ``Engine.step`` call
  engine.schedule  ``Scheduler.schedule``: admission, allocation, defer
                   and preemption
  engine.copies    each ``ModelRunner.apply_copies``: state restores
                   before the step, checkpoint copies after it
  runner.prepare   ``ModelRunner.prepare``: the host batch build
  runner.dispatch  ``ModelRunner.dispatch``: upload, zeroing of fresh
                   pages, the jit issue and the sampling tail's issue
  runner.fetch     ``ModelRunner.fetch``/``fetch_tokens``: the block on
                   the device and the device-to-host copy
  engine.advance   the loop over a plan's segments that records computed
                   tokens, samples and finishes requests
  engine.record    ``Engine._record_metrics``

Spans are per phase, never per request or segment: a step of ~200
segments would pay for a span each.
"""
from __future__ import annotations

import time
from typing import Dict

from jax.profiler import TraceAnnotation


class phase:
    """``with phase(name, acc) as p:`` opens the profiler span ``name``
    and, on exit, adds the block's milliseconds to ``acc[name]`` and
    leaves them in ``p.ms`` for a caller whose own timer covers the same
    interval."""

    __slots__ = ("name", "acc", "ms", "_ann", "_t0")

    def __init__(self, name: str, acc: Dict[str, float]):
        self.name = name
        self.acc = acc
        self.ms = 0.0

    def __enter__(self) -> "phase":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1e3
        self._ann.__exit__(*exc)
        self.acc[self.name] = self.acc.get(self.name, 0.0) + self.ms
