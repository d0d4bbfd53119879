"""One run of one cell: set up, warm up, measure, check, report.

The flow (``run_cell``):

1. set-up: the model from the cell's configuration file, weights made on
   the device from the seed by the module of the architecture the
   configuration names (``bench/archs/<arch>.py``; ``bench/spec.py`` says
   what it provides), the ``Engine`` with its KV pool, a rehearsal that
   serves the run's own stream once so that its serve-step shapes compile
   (see ``rehearse``), then a fresh engine and a warm-up that serves the
   stream until its caches are in steady state;
2. the window: ``--seconds`` of open-loop serving (``bench/serve.py``);
   with ``--trace 1`` the profiler records its last seconds;
3. for a rate cell, a drain after the window, so that every request due
   in the window is timed to its real first token (capped);
4. ``memory_peak_bytes``, then the program's state is freed and the plain
   reference (the architecture module's ``gaps``) is run over a sample,
   drawn from the seed, of the requests the window finished (and of those
   still running, with the tokens they were served), the longest among
   them;
5. the result line.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from .peaks import chip_peaks
from .serve import Feeder
from .spec import Bench
from .stats import itl_samples, percentile, tokens_in, ttft_samples
from .traffic import Stream, describe, rng_for

TRACE_S = 4.0             # profiler window at the end of the measured window
DRAIN_MAX_S = 60.0        # longest wait past the close for a first token
CHECK_TOKENS = 512        # served tokens the reference compares, at least
CHECK_MAX_REQUESTS = 12
REHEARSE_MARGIN = 1.2     # the rehearsal serves this much of the window


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class RunView:
    """What the per-layer readers see of one run."""
    w0: float
    w1: float
    steps: list               # StepRecords inside the window
    traced_steps: list        # StepRecords run under the profiler
    records: list             # every ReqRecord of the run
    total_units: int
    peak_flops: float
    trace: Optional[dict]


class CompileLog:
    """Counts compilations and compile-cache loads, by phase, and measures
    the host time spent tracing, lowering and compiling: the union of those
    events' intervals, since JAX nests them (a jit traced inside another
    reports its own trace, and a cache load is part of a compile)."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compile",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load"}
    WORK = ("/jax/core/compile/backend_compile_duration",
            "/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.phase = "setup"
        self.n: Dict[tuple, int] = {}
        self.s: Dict[tuple, float] = {}
        self.clock = clock
        self._closed = 0.0          # work in intervals no later event covers
        self._open: List[tuple] = []   # recent (start, end), disjoint

    @property
    def work_s(self) -> float:
        return self._closed + sum(e - s for s, e in self._open)

    def listener(self, event, secs, **_):
        if event in self.WORK:
            end = self.clock()
            start = end - secs
            # events end in order, so an earlier one either lies inside
            # this one (nested) or ended before it began
            keep = [(s, e) for s, e in self._open if e <= start]
            self._closed += sum(e - s for s, e in keep)
            self._open = [(start, end)]
        kind = self.EVENTS.get(event)
        if kind:
            k = (self.phase, kind)
            self.n[k] = self.n.get(k, 0) + 1
            self.s[k] = self.s.get(k, 0.0) + secs

    def line(self, phase: str) -> str:
        return ", ".join(f"{kind} {self.n.get((phase, kind), 0)} "
                         f"({self.s.get((phase, kind), 0.0):.3f} s)"
                         for kind in ("compile", "cache_load"))


class RehearsalClock:
    """The clock of a rehearsal: host time less the time spent compiling,
    and which jumps over every wait for an arrival instead of waiting. A
    step then takes as long as it will once everything is compiled."""

    def __init__(self, clock: Callable[[], float], clog: CompileLog):
        self.clock = clock
        # a virtual clock (the tests') holds no compile time to leave out
        self.clog = clog if clock is time.perf_counter else None
        self.jumped = 0.0

    def __call__(self) -> float:
        work = self.clog.work_s if self.clog is not None else 0.0
        return self.clock() - work + self.jumped

    def sleep(self, dt: float) -> None:
        self.jumped += max(dt, 0.0)


def _log(msg: str) -> None:
    print(msg, flush=True)


def setup_compile_cache(root: Path) -> str:
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(Path(root) / ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_model(cfg: dict):
    from repro.configs.base import ModelConfig
    from repro.models.registry import build_model as build
    from repro.models.tp import single_device_dist
    return build(ModelConfig(**cfg["model"]), single_device_dist())


def make_params(arch, cfg: dict, seed: int, model):
    """The weights as the model serves them, made on the device by the
    configuration's architecture module."""
    import jax

    from repro.serving.engine import serving_params
    want = jax.eval_shape(serving_params, model.struct())
    params = arch.program_tree(cfg["model"], seed, want)
    return jax.block_until_ready(params)


def new_engine(cfg: dict, model, params):
    from repro.serving import Engine, EngineConfig
    return Engine(model, EngineConfig(**cfg["engine"]), params=params)


def share_compiled(src, dst) -> None:
    """Hand the compiled helpers (page zeroing and copies, jitted per
    runner) of one runner to a fresh one over the same model, so that the
    measured pass does not compile what the rehearsal already did. Any
    attribute that is a dict of jitted functions is shared; a runner with
    none shares nothing."""
    for name, val in vars(src).items():
        mine = getattr(dst, name, None)
        if (isinstance(val, dict) and val and isinstance(mine, dict)
                and not mine
                and all(hasattr(f, "lower") for f in val.values())):
            mine.update(val)


def make_request_fn(prefix: str = ""):
    from repro.serving import Request, SamplingParams

    def make(rid, prompt, max_new):
        return Request(rid=prefix + rid, prompt=list(prompt),
                       sampling=SamplingParams(max_new_tokens=max_new))
    return make


def warm_up(feeder: Feeder, mix: dict, make, t_begin: float) -> float:
    """Serve the stream for the mix's warm-up seconds; return the window's
    start on the run's clock."""
    w0 = t_begin + float(mix["warmup"]["seconds"])
    feeder.run(w0, make)
    return w0


def rehearse(eng, mix: dict, seed: int, seconds: float, vocab: int,
             flop_model, clog: CompileLog, clock) -> list:
    """Serve the run's own stream once before the warm-up, on an engine of
    its own, with other token ids and on a clock that leaves out
    compilation and jumps over idle waits. The serve step compiles here (or
    loads from the persistent cache) for the shapes this cell's traffic
    makes. Returns the step times on the rehearsal's clock."""
    warm = float(mix["warmup"]["seconds"])
    stream = Stream(mix, seed, warm + seconds + DRAIN_MAX_S, vocab,
                    token_stream="rehearsal tokens")
    rclock = RehearsalClock(clock, clog)
    feeder = Feeder(eng, _offset_stream(stream, rclock()), flop_model,
                    clock=rclock, sleep=rclock.sleep)
    make = make_request_fn(prefix="rehearsal.")
    w0 = warm_up(feeder, mix, make, rclock())
    feeder.run(w0 + seconds * REHEARSE_MARGIN, make)
    return [s.t1 - s.t0 for s in feeder.steps]


def sample_for_check(served: list, seed: int) -> list:
    """The request with the most positions, then others in an order drawn
    from the seed, until CHECK_TOKENS served tokens (or CHECK_MAX_REQUESTS).
    ``served`` holds (record, request) of every request that produced a
    token by the close, finished or not: a backlog cell may finish none in
    its window."""
    if not served:
        return []
    items = sorted(served, key=lambda rr: rr[0].rid)
    longest = max(items, key=lambda rr: len(rr[1].prompt) + len(rr[1].output))
    rest = [rr for rr in items if rr is not longest]
    order = rng_for(seed, "check").permutation(len(rest))
    out, n = [longest], len(longest[1].output)
    for i in order:
        if n >= CHECK_TOKENS or len(out) >= CHECK_MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i][1].output)
    return out


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_chip: bool = True,
             clock: Callable[[], float] = time.perf_counter,
             sleep: Callable[[float], None] = time.sleep,
             control: bool = False, model=None,
             clog: Optional[CompileLog] = None, mix: Optional[dict] = None,
             check: bool = True) -> dict:
    """One run. ``control`` puts the fp8 control's tokens in the place of
    the served ones for the check (see ``_check``); the keywords
    after it serve ``bench/sweep.py``, which runs many rates in one
    process: a model and compile log to reuse, a mix to serve instead of
    the cell's, and the reference check to leave out."""
    import jax
    from jax import monitoring

    bench = Bench(root)
    cell = bench.cell(workload)
    cfg = bench.config(cell["config"])
    arch = bench.arch(cell["config"])
    mix = bench.mix(cell["traffic"]) if mix is None else mix
    devs = jax.devices()
    dev = devs[0]
    _log(f"device: platform={dev.platform} device_kind={dev.device_kind!r} "
         f"count={len(devs)} (jax {jax.__version__})")
    if require_chip and (dev.platform != "tpu" or len(devs) < cell["chips"]):
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"found {len(devs)} {dev.platform} device(s)")
    _log(f"compile cache: {setup_compile_cache(root)}")
    if clog is None:
        clog = CompileLog()
        monitoring.register_event_duration_secs_listener(clog.listener)
    clog.phase = "setup"

    kind = "rate" if mix["arrival"]["process"] == "poisson" else "batch"
    m = cfg["model"]
    flop_model = arch.flop_model(cfg)
    t_init0 = clock()
    model = build_model(cfg) if model is None else model
    params = make_params(arch, cfg, seed, model)
    eng = new_engine(cfg, model, params)
    t_init1 = clock()
    total_units = eng.mgr.geometry.total_units
    reh_steps = rehearse(eng, mix, seed, seconds, m["vocab_size"],
                               flop_model, clog, clock)
    runner = eng.runner
    del eng
    runner.buffer = None
    gc.collect()
    eng = new_engine(cfg, model, params)        # the same empty state
    share_compiled(runner, eng.runner)
    del runner, params
    t_reh1 = clock()
    horizon = float(mix["warmup"]["seconds"]) + seconds + DRAIN_MAX_S
    stream = Stream(mix, seed, horizon, m["vocab_size"])
    _log(f"cell {workload}: {cfg['model']['name']} x {cell['traffic']} "
         f"{json.dumps(describe(mix))}; {len(stream.sessions)} sessions "
         f"drawn; pool {cfg['engine']['kv_pool_bytes']} bytes = "
         f"{total_units} units")
    make = make_request_fn()
    t_stream = clock()
    feeder = Feeder(eng, _offset_stream(stream, t_stream), flop_model,
                    clock=clock, sleep=sleep)
    w0 = warm_up(feeder, mix, make, t_stream)
    t_setup = clock()
    setup_s = t_setup - t_start
    _log(f"setup: {setup_s:.3f} s = init {t_init1 - t_start:.3f} s "
         f"(model, weights, engine {t_init1 - t_init0:.3f} s) + rehearsal "
         f"{t_reh1 - t_init1:.3f} s + warm-up {t_setup - t_stream:.3f} s; "
         f"{clog.line('setup')}; "
         f"serve-step shapes {_shapes(eng)}")

    clog.phase = "window"
    w1 = w0 + seconds
    tr, traced_steps = None, []
    if trace:
        feeder.run(max(w0, w1 - TRACE_S), make)
        n0 = len(feeder.steps)
        tr = _traced(feeder, w1, make)
        traced_steps = feeder.steps[n0:]
        _log(f"trace: {len(traced_steps)} steps "
             f"({sum(s.prefill for s in traced_steps)} with prompt tokens), "
             f"{sum(s.flops for s in traced_steps):.6g} model FLOPs, "
             f"{sum(s.t1 - s.t0 for s in traced_steps):.3f} s of step walls, "
             f"device busy {tr.get('busy_s', 0.0):.3f} s of "
             f"{tr.get('window_s', 0.0):.3f} s traced")
    else:
        feeder.run(w1, make)
    clog.phase = "after"
    queue = (len(eng.scheduler.waiting), len(eng.scheduler.running))
    t_close = w1
    if kind == "rate":
        due = [r for r in feeder.records.values() if w0 <= r.due < w1]
        feeder.run(w1 + DRAIN_MAX_S, make,
                   until=lambda: all(r.token_times for r in due))
        t_close = clock()
    n = min(len(reh_steps), len(feeder.steps))
    if n:
        real = sum(st.t1 - st.t0 for st in feeder.steps[:n])
        _log(f"rehearsal: the first {n} steps took {real:.3f} s, "
             f"{sum(reh_steps[:n]):.3f} s on the rehearsal's clock")
    _log(f"window: {seconds} s; {clog.line('window')} inside it; "
         f"serve-step shapes now {_shapes(eng)}; "
         f"preemptions {feeder.preemptions}; at the close {queue[0]} "
         f"waiting, {queue[1]} running")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    records = list(feeder.records.values())
    steps = [s for s in feeder.steps if w0 <= s.t0 and s.t1 <= w1]
    lateness = [r.submitted - r.due for r in records if w0 <= r.due < w1]
    if lateness:
        offered = sum(r.max_new for r in records if w0 <= r.due < w1)
        _log(f"generator lateness over {len(lateness)} requests: mean "
             f"{1e3 * np.mean(lateness):.3f} ms, max "
             f"{1e3 * max(lateness):.3f} ms; output tokens offered by the "
             f"requests due in the window {offered} "
             f"({offered / seconds:.1f}/s)")

    metrics: Dict[str, dict] = {}
    if not trace:
        e2e = _end_to_end(bench, workload, records, w0, w1, t_close, seconds)
        metrics.update(e2e)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    view = RunView(w0=w0, w1=w1, steps=steps, traced_steps=traced_steps,
                   records=records,
                   total_units=total_units,
                   peak_flops=chip_peaks(dev.device_kind)["bf16_flops"]
                   if require_chip else 1.0, trace=tr)
    layers = {}
    for name, (unit, read) in bench.readers(workload).items():
        v = read(view)
        if v is not None:
            layers[name] = {"value": v, "unit": unit}
    _log(f"steps in window: {len(steps)}; per-layer readings: "
         f"{json.dumps({k: v['value'] for k, v in layers.items()})}")
    if trace:
        metrics.update(layers)
    if kind == "rate":
        attempted = sum(1 for r in records if w0 <= r.due < w1)
        failed = sum(1 for r in records
                     if w0 <= r.due < w1 and not r.token_times)
    else:
        attempted = sum(1 for r in records
                        if any(w0 <= t < w1 for t in r.token_times))
        failed = 0

    served = [(rec, req) for rec, req in feeder.finished
              if rec.finished is not None and w0 <= rec.finished <= t_close]
    served += [(feeder.records[rid], req)
               for rid, (req, _) in feeder.live.items() if req.output]
    sample = [(list(req.prompt), list(req.output))
              for _, req in sample_for_check(served, seed)]
    del feeder, eng, served, records, steps, traced_steps, view
    gc.collect()
    _log(f"device bytes in use after the engine is freed: "
         f"{(dev.memory_stats() or {}).get('bytes_in_use', 'not reported')}")
    verdict = (_check(arch, cfg, seed, sample, control) if check
               else {"correct": False, "numbers": {}})
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": peak}}
    if tr:
        result["device"]["busy_s"] = tr["busy_s"]
        result["device"]["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["check"] = verdict["numbers"]
    return result


def _shapes(eng) -> int:
    """Serve-step programs compiled so far (for the log)."""
    return len(getattr(eng.runner, "_steps", ()))


def _offset_stream(stream: Stream, t0: float) -> Stream:
    for s in stream.sessions:
        s.start_s += t0
    return stream


def _traced(feeder: Feeder, w1: float, make) -> dict:
    import jax

    from . import trace as trace_mod
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        feeder.run(w1, make)
        jax.profiler.stop_trace()
        files = sorted(Path(tmp).rglob("*.xplane.pb"))
        if not files:
            return {}
        return trace_mod.reduce_trace(trace_mod.load(files[-1]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _end_to_end(bench: Bench, cell: str, records, w0, w1, t_close,
                seconds) -> Dict[str, dict]:
    out = {}
    wanted = {m["name"]: m["unit"] for m in bench.end_to_end(cell)}
    ttft = ttft_samples(records, w0, w1, t_close)
    itl = itl_samples(records, w0, w1)
    n_tok = tokens_in(records, w0, w1)
    _log(f"samples: ttft {len(ttft)} requests, itl {len(itl)} gaps, "
         f"{n_tok} output tokens in the window")
    values = {
        "ttft_p90_ms": percentile([1e3 * x for x in ttft], 90),
        "itl_p95_ms": percentile([1e3 * x for x in itl], 95),
        "output_tokens_per_s": n_tok / seconds,
    }
    for name, unit in wanted.items():
        if name in values and values[name] is not None:
            out[name] = {"value": values[name], "unit": unit}
    return out


def _check(arch, cfg: dict, seed: int, sample: list, control: bool) -> dict:
    """The architecture's plain reference over the sampled requests: the
    widest gap by which a served token's logit lies below the reference's
    best. With ``control`` the token read at each position is the one the
    fp8 control puts first, in the place of the served one, and the same
    limit judges it: the control has to come out not correct."""
    limit = float(cfg["check"]["max_logit_gap"])
    m = cfg["model"]
    if not sample:
        _log("check: no request was served a token")
        return {"correct": False, "numbers": {
            "logit_gap": {"value": None, "limit": limit},
            "tokens": {"value": 0, "limit": 1}}}
    t0 = time.perf_counter()
    w = arch.make(m, seed)
    mode = "fp8" if control else "fp32"
    worst, n_tok = 0.0, 0
    for prompt, output in sample:
        g = arch.gaps(w, m, prompt, output, mode=mode)
        worst = max(worst, float(g.max()))
        n_tok += len(output)
    del w
    numbers = {"logit_gap": {"value": worst, "limit": limit},
               "tokens": {"value": n_tok, "limit": 1}}
    whose = "the fp8 control's tokens" if control else "the served tokens"
    _log(f"check: {len(sample)} requests, {n_tok} served tokens, longest "
         f"{max(len(p) + len(o) for p, o in sample)} positions; widest gap "
         f"of {whose} below the reference's best logit {worst:.6f} (limit "
         f"{limit}); reference {time.perf_counter() - t0:.3f} s")
    for name, v in numbers.items():
        print(f"{name}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    return {"correct": bool(worst <= limit and n_tok >= 1),
            "numbers": numbers}
