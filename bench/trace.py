"""Reduction of a JAX profiler trace to the device's busy time and breakdown.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to the
  traced window and averaged over the devices;
* ``device_ops``: the operations with the most device self time (their
  time less that of the operations nested in them, as a loop's body is
  nested in the loop), by the name the HLO gives the operation;
* ``idle_gaps``: the longest gaps between device operations, each named by
  the benchmark span (``bench.step``, ``bench.wait``, ``bench.submit``) that
  was open on the host at the gap's midpoint, or ``none``.

The window runs from the first benchmark span traced to the end of the
last one.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Tuple

SPANS = ("bench.step", "bench.wait", "bench.submit")


def _device_planes(pd) -> list:
    return [p for p in pd.planes
            if p.name.startswith("/device:TPU:") and
            any(line.name == "XLA Ops" for line in p.lines)]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_times(events: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Self time of each op, by name: nested ops are taken out of the op
    that holds them."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []              # [end, name, self_ns]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            out[n] += own * 1e-9
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for end, n, own in stack:
        out[n] += own * 1e-9
    return out


def _host_spans(pd) -> List[Tuple[int, int, str]]:
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    spans.append((int(ev.start_ns), int(ev.end_ns), ev.name))
    return sorted(spans)


def reduce_trace(pd, top: int = 10) -> Dict:
    spans = _host_spans(pd)
    planes = _device_planes(pd)
    if not planes or not spans:
        return {}
    t_lo_ns = spans[0][0]
    t_hi_ns = max(e for _, e, _ in spans)
    window_s = (t_hi_ns - t_lo_ns) * 1e-9
    op_time: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    gaps: List[Tuple[float, int]] = []
    for p in planes:
        ivs, events = [], []
        for line in p.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                s, e = max(s, t_lo_ns), min(e, t_hi_ns)
                if e <= s:
                    continue
                ivs.append((s, e))
                events.append((s, e, op_name(ev.name)))
        for name, t in _self_times(events).items():
            op_time[name] += t
        merged = _union(ivs)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [(t_lo_ns, t_lo_ns)] + merged + [(t_hi_ns, t_hi_ns)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps.append(((b - a) * 1e-9, (a + b) // 2))
    starts = [s for s, _, _ in spans]

    def span_at(t: int) -> str:
        # the benchmark's spans run one after another on one thread
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < spans[i][1]:
            return spans[i][2]
        return "none"

    gaps.sort(reverse=True)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_total / len(planes),
        "window_s": window_s,
        "devices": len(planes),
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[span_at(mid), s] for s, mid in gaps[:top]],
    }


def load(path) -> object:
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))
