"""The readings of the engine's step phases and recompute counter, and the
idle gaps named by the innermost host span."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from bench import harness, phases
from bench.tests.test_harness import make_root, run
from bench.trace import reduce_trace

# device ops, in microseconds: [1, 2], [5, 6], [8, 10]. Host spans: one
# engine step inside bench.step [1, 10.9], then bench.wait [10.9, 11.5].
DEVICE = [(1.0, 2.0), (5.0, 6.0), (8.0, 10.0)]
BENCH_SPANS = [("bench.step", 1.0, 10.9), ("bench.wait", 10.9, 11.5)]
PROGRAM = [("engine.step", 1.2, 10.8), ("engine.schedule", 1.2, 1.5),
           ("engine.copies", 1.5, 1.6), ("runner.prepare", 1.6, 2.5),
           ("runner.dispatch", 2.5, 3.0), ("runner.fetch", 3.0, 6.2),
           ("engine.advance", 6.2, 8.5), ("engine.copies", 8.5, 8.6),
           ("engine.record", 8.6, 8.7)]


def _line(lid, name, events):
    out = [f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0']
    for key, s, e in events:
        out.append(f"events {{ metadata_id: {key} "
                   f"offset_ps: {round(s * 1e6)} "
                   f"duration_ps: {round((e - s) * 1e6)} }}")
    return " ".join(out) + " }"


def synthetic(host_spans):
    """A text-proto trace: one device plane, one host thread."""
    names = sorted({n for n, _, _ in host_spans})
    key = {n: i + 1 for i, n in enumerate(names)}
    dev = _line(1, "XLA Ops", [(i + 1, s, e) for i, (s, e)
                               in enumerate(DEVICE)])
    dev_meta = " ".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "%fusion.{i + 1} = f32[8] fusion()" }} }}'
        for i in range(len(DEVICE)))
    host = _line(1, "python", [(key[n], s, e) for n, s, e in host_spans])
    host_meta = " ".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
        for n, k in key.items())
    return ProfileData.from_text_proto(
        f'planes {{ id: 1 name: "/device:TPU:0" {dev} {dev_meta} }} '
        f'planes {{ id: 2 name: "/host:CPU" {host} {host_meta} }}')


def test_a_gap_is_named_by_the_innermost_span():
    pd = synthetic(BENCH_SPANS + PROGRAM)
    assert phases.idle_gaps(pd) == [
        ["runner.fetch", pytest.approx(3e-6)],      # [2, 5]
        ["engine.advance", pytest.approx(2e-6)],    # [6, 8]
        ["engine.step", pytest.approx(1.5e-6)],     # [10, 11.5]: mid 10.75
    ]


def test_program_spans_leave_the_trace_reduction_as_it_was():
    """busy, window and the device ops read the same with the program's
    spans in the trace as without them: the window is the benchmark's."""
    with_program = reduce_trace(synthetic(BENCH_SPANS + PROGRAM))
    without = reduce_trace(synthetic(BENCH_SPANS))
    for k in ("busy_s", "window_s", "devices", "device_ops", "idle_gaps"):
        assert with_program[k] == without[k], k
    assert with_program["window_s"] == pytest.approx(10.5e-6)
    assert with_program["busy_s"] == pytest.approx(4e-6)


def test_no_device_or_no_benchmark_span_names_nothing():
    assert phases.idle_gaps(synthetic(PROGRAM)) == []
    assert phases.innermost([], 5) == "none"


# ------------------------------------------------------------- readers
def step(phase_ms=None, batched=0, recomputed=None):
    m = SimpleNamespace(batched_tokens=batched)
    if phase_ms is not None:
        m.phase_ms = phase_ms
    if recomputed is not None:
        m.recomputed_tokens = recomputed
    return SimpleNamespace(metrics=m)


def view(steps):
    return SimpleNamespace(steps=list(steps))


def test_phase_readers_average_over_the_window_steps():
    v = view([step({"engine.step": 100.0, "runner.fetch": 60.0,
                    "engine.schedule": 4.0}),
              step({"engine.step": 50.0, "engine.schedule": 2.0})])
    assert phases.engine_host_ms(v) == pytest.approx((40.0 + 50.0) / 2)
    assert phases.fetch_ms(v) == pytest.approx(30.0)
    assert phases.schedule_ms(v) == pytest.approx(3.0)


def test_recompute_frac_is_recomputed_over_batched_tokens():
    v = view([step(batched=200, recomputed=50), step(batched=300,
                                                      recomputed=0)])
    assert phases.recompute_frac(v) == pytest.approx(10.0)
    assert phases.recompute_frac(view([step(batched=0, recomputed=0)])) \
        is None


@pytest.mark.parametrize("reader", [phases.engine_host_ms, phases.fetch_ms,
                                    phases.schedule_ms,
                                    phases.recompute_frac])
def test_readers_read_nothing_from_a_program_without_them(reader):
    """A program that records no phases or recompute count (as before they
    existed) gives None, not 0 and not an error."""
    assert reader(view([step(batched=10)])) is None
    assert reader(view([])) is None


NEW = ("engine_host_ms.batch", "fetch_ms.batch", "schedule_ms.batch",
       "recompute_frac.batch")


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "setup_compile_cache", lambda root: "off")
    root = make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    batch = "tiny-dense.tiny_backlog"
    metrics = Path(phases.__file__).parent / "metrics"
    for name in NEW:
        (root / "bench/metrics" / f"{name}.py").write_text(
            (metrics / f"{name}.py").read_text())
        spec["per_layer"].append({
            "name": name, "unit": "%" if "frac" in name else "ms",
            "better": "lower", "source": "program_span", "layer": "test",
            "moves": "output_tokens_per_s", "workloads": [batch]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_all_of_the_idle_time_is_split_by_the_innermost_span():
    """Idle [2, 5] lies in runner.prepare [2, 2.5], runner.dispatch
    [2.5, 3] and runner.fetch [3, 5]; [6, 8] in runner.fetch [6, 6.2] and
    engine.advance [6.2, 8]; [10, 11.5] in engine.step, bench.step and
    bench.wait."""
    split = phases.idle_by_span(synthetic(BENCH_SPANS + PROGRAM))
    want = {"runner.prepare": 0.5, "runner.dispatch": 0.5,
            "runner.fetch": 2.2, "engine.advance": 1.8, "engine.step": 0.8,
            "bench.step": 0.1, "bench.wait": 0.6}
    assert split == {k: pytest.approx(v * 1e-6) for k, v in want.items()}
    total = reduce_trace(synthetic(BENCH_SPANS + PROGRAM))
    assert sum(split.values()) == pytest.approx(
        total["window_s"] - total["busy_s"])
    assert phases.idle_by_span(synthetic(PROGRAM)) == {}


@pytest.mark.parametrize("python_tracer", ["0", "1"])
def test_phase_trace_names_the_gaps_of_a_traced_run(root, monkeypatch,
                                                    capsys, python_tracer):
    """bench/phase_trace.py makes the traced run, whose result line holds
    the four new metrics, and prints the named gaps and the idle split
    beside it; the CPU's trace holds no device, so both are empty here,
    and the reduction and the profiler's start are put back afterwards."""
    import jax

    from bench import phase_trace, trace
    from bench.tests.test_harness import VirtualClock
    vc = VirtualClock()
    run_cell = harness.run_cell
    monkeypatch.setattr(phase_trace, "ROOT", root)
    monkeypatch.setattr(harness, "run_cell", lambda *a, **kw: run_cell(
        *a, require_chip=False, clock=vc, sleep=vc.sleep, **kw))
    reduce, start = trace.reduce_trace, jax.profiler.start_trace
    assert phase_trace.main(["--workload", "tiny-dense.tiny_backlog",
                             "--seed", "5", "--seconds", "1",
                             "--python-tracer", python_tracer]) == 0
    out, err = capsys.readouterr()
    assert "phase idle gaps: []" in err and "phase idle split: {}" in err
    assert trace.reduce_trace is reduce
    assert jax.profiler.start_trace is start
    res = json.loads(out.splitlines()[-1])
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(got)
    assert got["engine_host_ms.batch"] > 0
    assert got["fetch_ms.batch"] >= 0 and got["schedule_ms.batch"] > 0
    assert 0 <= got["recompute_frac.batch"] < 100
