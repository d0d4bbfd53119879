"""Readings of the engine's own step phases and recompute counter, and the
device's idle gaps named by the innermost host span open across them.

The engine records per step (``repro.serving.spans``):

* ``StepMetrics.phase_ms``: the milliseconds of each named phase of
  ``Engine.step`` (``PROGRAM_SPANS``), which are also host spans in a
  running profiler's trace;
* ``StepMetrics.recomputed_tokens``: the tokens the step computes at
  positions its request had computed before a recompute preemption.

A program without them gives the readers nothing to read: each returns
None. Phases are matched by name only; no code of the program is imported.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .trace import SPANS, _device_planes, _union

PROGRAM_SPANS = ("engine.step", "engine.schedule", "engine.copies",
                 "runner.prepare", "runner.dispatch", "runner.fetch",
                 "engine.advance", "engine.record")


def _mean_of(run, f) -> Optional[float]:
    """Mean of ``f(phase_ms)`` over the window's steps."""
    phases = [getattr(s.metrics, "phase_ms", None) for s in run.steps]
    if not phases or any(p is None for p in phases):
        return None
    return sum(f(p) for p in phases) / len(phases)


def engine_host_ms(run) -> Optional[float]:
    """Mean over the window's steps of the host's time in ``Engine.step``
    outside the block on the device: ``engine.step`` less ``runner.fetch``."""
    return _mean_of(run, lambda p: p["engine.step"]
                    - p.get("runner.fetch", 0.0))


def fetch_ms(run) -> Optional[float]:
    """Mean ``runner.fetch``: the device work left once the step is issued,
    and the copy of its results to the host."""
    return _mean_of(run, lambda p: p.get("runner.fetch", 0.0))


def schedule_ms(run) -> Optional[float]:
    """Mean ``engine.schedule``: admission, allocation, defer, preemption."""
    return _mean_of(run, lambda p: p.get("engine.schedule", 0.0))


def recompute_frac(run) -> Optional[float]:
    """Tokens computed again after recompute preemption, over every token
    the window's steps dispatched, in %."""
    steps = [s.metrics for s in run.steps]
    if not steps or any(not hasattr(m, "recomputed_tokens") for m in steps):
        return None
    total = sum(m.batched_tokens for m in steps)
    if not total:
        return None
    return 100.0 * sum(m.recomputed_tokens for m in steps) / total


# ------------------------------------------------------------ idle gaps
def _named_spans(pd) -> List[Tuple[int, int, str]]:
    names = set(SPANS) | set(PROGRAM_SPANS)
    return sorted((int(ev.start_ns), int(ev.end_ns), ev.name)
                  for p in pd.planes if p.name.startswith("/host:")
                  for line in p.lines for ev in line.events
                  if ev.name in names)


def innermost(spans: List[Tuple[int, int, str]], t: int) -> str:
    """The span open at ``t`` that began last (spans nest on the host
    thread that drives the engine), or ``none``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if t < e and (best is None or s >= best[0]):
            best = (s, e, name)
    return best[2] if best else "none"


def _window(pd):
    """Host spans, device planes and ``bench/trace.py``'s window (first to
    last benchmark span), or None where the trace has no device or no
    benchmark span."""
    spans = _named_spans(pd)
    bench = [sp for sp in spans if sp[2] in SPANS]
    planes = _device_planes(pd)
    if not planes or not bench:
        return None
    return spans, planes, bench[0][0], max(e for _, e, _ in bench)


def _busy(plane, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The plane's device-op intervals clipped to [lo, hi], merged."""
    ivs = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            s, e = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
            if e > s:
                ivs.append((s, e))
    return _union(ivs)


def idle_gaps(pd, top: int = 10) -> List[list]:
    """The ``top`` longest gaps between device operations, each named by
    the innermost benchmark or program span open at its midpoint."""
    w = _window(pd)
    if w is None:
        return []
    spans, planes, lo, hi = w
    gaps: List[Tuple[float, int]] = []
    for p in planes:
        edges = [(lo, lo)] + _busy(p, lo, hi) + [(hi, hi)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps.append(((b - a) * 1e-9, (a + b) // 2))
    gaps.sort(reverse=True)
    return [[innermost(spans, mid), s] for s, mid in gaps[:top]]


def idle_by_span(pd) -> Dict[str, float]:
    """The device's idle seconds in the window, split by the innermost
    span open on the host at each instant, averaged over the devices: all
    of the idle time, where ``idle_gaps`` names the longest gaps only."""
    w = _window(pd)
    if w is None:
        return {}
    spans, planes, lo, hi = w
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    names = [innermost(spans, (a + b) // 2) for a, b in zip(cuts, cuts[1:])]
    out: Dict[str, float] = {}
    for p in planes:
        merged = _busy(p, lo, hi)
        i = 0
        for (a, b), name in zip(zip(cuts, cuts[1:]), names):
            while i < len(merged) and merged[i][1] <= a:
                i += 1
            busy, j = 0, i
            while j < len(merged) and merged[j][0] < b:
                busy += min(merged[j][1], b) - max(merged[j][0], a)
                j += 1
            if b - a > busy:
                idle = (b - a - busy) * 1e-9 / len(planes)
                out[name] = out.get(name, 0.0) + idle
    return out
