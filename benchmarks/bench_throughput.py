"""Fig. 13 / Fig. 14: end-to-end engine throughput & latency — real engine
runs on reduced heterogeneous models, Jenga vs the PagedAttention baseline
under an identical pool budget. CPU wall-clock is not the roofline story;
the apples-to-apples signals are steps-to-finish and tokens/step (batch
capacity), exactly what the paper's speedups come from.

``run_async_ab`` A/Bs the double-buffered engine against the synchronous
loop on the decode-heavy staggered workload: same dispatches, same tokens,
host batch-build time overlapped with the in-flight device step. Writes
``BENCH_async.json`` (repo root) so the perf trajectory is recorded
per-PR."""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.configs import ARCHS, reduced
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.serving import (ROUTE_CACHE_AWARE, ROUTE_ROUND_ROBIN, DPEngine,
                           Engine, EngineConfig, Request, SamplingParams)


ARCH_SET = ("h2o-danube-3-4b", "zamba2-1.2b", "granite-3-2b")


def run_engine(arch: str, mode: str, n_req=6, prompt=192, out=8,
               pool=None, batching: str = "packed"):
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    if pool is None:
        # size the pool to ~2.5 requests of IDEAL (jenga) need, so the
        # baseline's waste forces smaller batches / preemption (the paper's
        # regime: memory capacity is the binding constraint)
        per_tok = 0
        from repro.models.registry import build_model as _bm
        for sp in model.kv_specs():
            if sp.kind in ("mamba", "rwkv"):
                per_tok += sp.page_units // max(1, prompt)
            elif sp.kind == "swa":
                per_tok += sp.units_per_token * min(
                    1.0, (cfg.sliding_window + out) / (prompt + out))
            else:
                per_tok += sp.units_per_token
        pool = int(2.5 * (prompt + out) * per_tok * 2)
        from repro.core.spec import lcm as _lcm
        big = _lcm([sp.page_units for sp in model.kv_specs()])
        pool = max(pool, 8 * big * 2)   # >= 8 LCM large pages
    eng = Engine(model, EngineConfig(kv_pool_bytes=pool, max_running=8,
                                     chunk_size=32, memory_mode=mode,
                                     batching_mode=batching,
                                     max_num_batched_tokens=256,
                                     enable_prefix_caching=False))
    for i in range(n_req):
        eng.submit(Request(rid=f"r{i}", prompt=[(7 * i + j) % 101
                                                for j in range(prompt)],
                           sampling=SamplingParams(max_new_tokens=out)))
    t0 = time.perf_counter()
    done = eng.run_until_done(max_steps=4000)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in done)
    return dict(steps=eng.step_count, finished=len(done),
                tokens=total_tokens, wall_s=dt,
                tok_per_step=total_tokens / max(1, eng.step_count),
                preemptions=eng.scheduler.preemption_count)


def run_waste_ab(arch: str, batching: str, n_req=16, prompt=96, out=24,
                 budget=128):
    """Decode-heavy mixed workload for the padding-waste A/B: requests
    arrive staggered so most steps co-schedule one prefill chunk with a
    growing decode batch — exactly the regime where the padded layout's
    decode rows pay the prefill chunk's (B, T) padding."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    eng = Engine(model, EngineConfig(
        kv_pool_bytes=96 << 20, max_running=n_req, chunk_size=32,
        memory_mode="jenga", batching_mode=batching,
        max_num_batched_tokens=budget, enable_prefix_caching=False))
    for i in range(n_req):
        eng.submit(Request(rid=f"r{i}", prompt=[(7 * i + j) % 101
                                                for j in range(prompt)],
                           sampling=SamplingParams(max_new_tokens=out)))
        eng.step()          # staggered arrivals: prefills ride with decodes
    eng.run_until_done(max_steps=4000)
    r = eng.runner
    waste = 1.0 - r.tokens_dispatched / max(1, r.slots_dispatched)
    return dict(waste=waste,
                tok_per_dispatch=r.tokens_dispatched / max(1, r.dispatch_count),
                slots=r.slots_dispatched, tokens=r.tokens_dispatched,
                finished=len(eng.finished))


def run_async_ab(arch: str, n_req=16, prompt=96, out=24, budget=128):
    """Async-vs-sync A/B on the decode-heavy staggered workload (the
    ``run_waste_ab`` regime). The semantic invariants come first — greedy
    outputs and dispatch counts identical — then the overlap accounting:
    per-step host batch-build ms (what double buffering hides behind the
    in-flight dispatch), device-wait ms, and wall-clock per step."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    params = model.init(0)
    rows = {}
    # warmup pass populates the model-shared serve-step jit cache so both
    # timed runs are compile-free (sync would otherwise pay every trace)
    for tag, async_ in (("warmup", False), ("sync", False),
                        ("async", True)):
        eng = Engine(model, EngineConfig(
            kv_pool_bytes=96 << 20, max_running=n_req, chunk_size=32,
            batching_mode="packed", async_scheduling=async_,
            max_num_batched_tokens=budget, enable_prefix_caching=False),
            params=params)
        for i in range(n_req):
            eng.submit(Request(rid=f"r{i}", prompt=[(7 * i + j) % 101
                                                    for j in range(prompt)],
                               sampling=SamplingParams(max_new_tokens=out)))
            eng.step()      # staggered arrivals: prefills ride with decodes
        t0 = time.perf_counter()
        eng.run_until_done(max_steps=4000)
        wall = time.perf_counter() - t0
        if tag == "warmup":
            continue
        ms = eng.metrics
        rows[tag] = dict(
            outputs={r.rid: list(r.output) for r in eng.finished},
            dispatches=eng.runner.dispatch_count,
            steps=eng.step_count,
            tokens=eng.runner.tokens_dispatched,
            wall_s=wall,
            host_build_ms_total=sum(m.host_build_ms for m in ms),
            dispatch_wait_ms_total=sum(m.dispatch_ms for m in ms),
            us_per_step=wall * 1e6 / max(1, eng.step_count),
        )
    assert rows["sync"]["outputs"] == rows["async"]["outputs"], \
        "async changed greedy outputs"
    assert rows["sync"]["dispatches"] == rows["async"]["dispatches"], \
        (rows["sync"]["dispatches"], rows["async"]["dispatches"])
    for r in rows.values():
        del r["outputs"]        # equality asserted; keep the JSON small
    # host build time the async loop issues while a dispatch is in flight —
    # the overlap claim is structural (phase order), measured here
    sync_b, async_b = (rows[t]["host_build_ms_total"]
                       for t in ("sync", "async"))
    return dict(arch=arch, n_req=n_req, prompt=prompt, out=out,
                budget=budget, sync=rows["sync"], async_=rows["async"],
                overlapped_host_build_ms=async_b,
                sync_host_build_ms=sync_b)


def run_pipeline_ab(arch: str = "granite-3-2b", n_req=16, prompt=96, out=24,
                    budget=128):
    """Pipeline-depth / device-sampling A/B on the decode-heavy staggered
    workload. Three timed legs: depth-2 host-sampled (the PR-3 double
    buffer), depth-2 device-sampled (same ring, completion blocks on 4
    bytes/segment instead of a vocab-wide fp32 row), and depth-4
    device-sampled (up to 3 steps queued on device). Semantic gates:
    greedy outputs bitwise identical across all legs (the device sampler
    shares the host tie-band rule), depth 4 finishes in no more engine
    steps than depth 2, and the drained pool leaks nothing. The recorded
    signals are the tentpole's: per-step fetched bytes, host sampling ms
    (0 on device legs), generated tokens/s, and the issue/queue/compute
    timing split."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    params = model.init(0)
    rows = {}
    legs = (("warmup", 2, False), ("depth2_host", 2, False),
            ("depth2_device", 2, True), ("depth4_device", 4, True))
    for tag, depth, device in legs:
        eng = Engine(model, EngineConfig(
            kv_pool_bytes=96 << 20, max_running=n_req, chunk_size=32,
            batching_mode="packed", async_scheduling=True,
            pipeline_depth=depth, device_sampling=device,
            max_num_batched_tokens=budget, enable_prefix_caching=False),
            params=params)
        for i in range(n_req):
            eng.submit(Request(rid=f"r{i}", prompt=[(7 * i + j) % 101
                                                    for j in range(prompt)],
                               sampling=SamplingParams(max_new_tokens=out)))
            eng.step()      # staggered arrivals: prefills ride with decodes
        t0 = time.perf_counter()
        eng.run_until_done(max_steps=4000)
        wall = time.perf_counter() - t0
        if tag == "warmup":
            continue
        ms = eng.metrics
        stats = eng.mgr.memory_stats()
        assert stats.used_units == 0 and \
            stats.free_units == stats.total_units, (tag, stats)
        gen = sum(len(r.output) for r in eng.finished)
        rows[tag] = dict(
            outputs={r.rid: list(r.output) for r in eng.finished},
            steps=eng.step_count,
            wall_s=wall,
            gen_tok_per_s=gen / max(1e-9, wall),
            fetched_bytes_total=eng.runner.bytes_fetched,
            fetched_bytes_per_step=eng.runner.bytes_fetched
            / max(1, eng.step_count),
            host_sample_ms_total=sum(m.host_sample_ms for m in ms),
            host_build_ms_total=sum(m.host_build_ms for m in ms),
            dispatch_wait_ms_total=sum(m.dispatch_ms for m in ms),
            spec_kills=eng.spec_kills,
        )
    base = rows["depth2_host"]
    for tag in ("depth2_device", "depth4_device"):
        assert rows[tag]["outputs"] == base["outputs"], \
            f"{tag} changed greedy outputs"
    assert rows["depth4_device"]["steps"] <= base["steps"], \
        (rows["depth4_device"]["steps"], base["steps"])
    # the round-trip kill: vocab-wide fp32 rows -> (segments,) int32
    assert rows["depth2_device"]["fetched_bytes_total"] * 10 \
        <= base["fetched_bytes_total"], rows["depth2_device"]
    assert rows["depth2_device"]["host_sample_ms_total"] == 0.0
    for r in rows.values():
        del r["outputs"]        # equality asserted; keep the JSON small
    return dict(arch=arch, n_req=n_req, prompt=prompt, out=out,
                budget=budget,
                fetch_bytes_ratio=base["fetched_bytes_total"]
                / max(1, rows["depth2_device"]["fetched_bytes_total"]),
                **rows)


def run_kernel_ab(arch: str = "granite-3-2b", n_req=32, prompt=96, out=24,
                  budget=128):
    """Kernel-vs-ref + autotune A/B on the decode-heavy staggered workload.

    Three timed legs over one workload: ref attention with the constant
    budgets above, the Pallas varlen kernel path, and ref attention with
    roofline-seeded autotuned budgets. Greedy outputs must match between
    ref and kernel; the block-sparse accounting (host-side mirror of the
    kernel's segment-interval skip test, identical for both impls since it
    depends only on the schedule) must show a majority of KV blocks
    skipped; autotuned budgets must finish in no more steps than the
    hand-picked constants. ``n_req`` is sized so the packed stream spans
    several query blocks — the skip fraction is bounded by 1 - 1/n_qblocks,
    so a decode batch of ~32 segments is what makes >50% reachable."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    params = model.init(0)
    from repro.serving.autotune import BudgetAutotuner, roofline_token_budget
    # the autotuned leg seeds its budgets from a v5e's roofline whatever
    # device runs this CPU benchmark
    v5e = "TPU v5 lite"
    rows = {}
    for tag, impl, autotune in (("warmup", "ref", False),
                                ("ref", "ref", False),
                                ("kernel", "kernel", False),
                                ("autotuned", "ref", True)):
        eng = Engine(model, EngineConfig(
            kv_pool_bytes=96 << 20, max_running=n_req, chunk_size=32,
            batching_mode="packed", attention_impl=impl,
            max_num_batched_tokens=budget,
            enable_prefix_caching=False), params=params)
        if autotune:
            eng.autotuner = BudgetAutotuner(cfg, v5e)
            eng.scheduler.set_budgets(eng.autotuner.budget,
                                      eng.autotuner.prefill_cap)
        for i in range(n_req):
            eng.submit(Request(rid=f"r{i}", prompt=[(7 * i + j) % 101
                                                    for j in range(prompt)],
                               sampling=SamplingParams(max_new_tokens=out)))
            eng.step()      # staggered arrivals: prefills ride with decodes
        t0 = time.perf_counter()
        eng.run_until_done(max_steps=4000)
        wall = time.perf_counter() - t0
        if tag == "warmup":
            continue
        r = eng.runner
        total = r.kv_blocks_scanned + r.kv_blocks_skipped
        rows[tag] = dict(
            outputs={q.rid: list(q.output) for q in eng.finished},
            steps=eng.step_count, wall_s=wall,
            kv_blocks_scanned=r.kv_blocks_scanned,
            kv_blocks_skipped=r.kv_blocks_skipped,
            kv_block_skip_frac=r.kv_blocks_skipped / max(1, total),
            attn_gflops_modeled=r.attn_flops_modeled / 1e9,
            attn_gbytes_modeled=r.attn_bytes_modeled / 1e9,
            budget_final=eng.scheduler.cfg.max_num_batched_tokens,
            prefill_cap_final=eng.scheduler.cfg.max_prefill_tokens_per_step,
        )
    assert rows["ref"]["outputs"] == rows["kernel"]["outputs"], \
        "kernel changed greedy outputs"
    assert rows["ref"]["kv_block_skip_frac"] > 0.5, rows["ref"]
    assert rows["autotuned"]["steps"] <= rows["ref"]["steps"], \
        (rows["autotuned"]["steps"], rows["ref"]["steps"])
    for r in rows.values():
        del r["outputs"]        # equality asserted; keep the JSON small
    return dict(arch=arch, n_req=n_req, prompt=prompt, out=out,
                budget_constant=budget,
                budget_roofline_seed=roofline_token_budget(cfg, v5e),
                ref=rows["ref"], kernel=rows["kernel"],
                autotuned=rows["autotuned"])


def _router_workload(groups=4, members=4, shared=56, unique=12, out=8):
    """Shared-prefix fleet workload: ``groups`` families of requests, each
    sharing a ``shared``-token prompt prefix (same system prompt / few-shot
    header) plus a short unique tail. Group LEADERS arrive first; followers
    arrive staggered a few ticks later, after the leaders' prefix pages
    have been computed and registered (cache-while-running) — so a
    cache-aware router can see where each family's prefix lives. Returns
    (arrival_tick, request-factory) pairs; factories, because every leg
    needs fresh Request objects."""
    out_specs = []
    for g in range(groups):
        pre = [(31 * g + j) % 101 for j in range(shared)]
        for m in range(members):
            tail = [(17 * g + 7 * m + j + 3) % 101 for j in range(unique)]
            arrival = 0 if m == 0 else 6 + 2 * m
            rid, prompt = f"g{g}m{m}", pre + tail
            out_specs.append((arrival, rid, prompt))
    def mk(rid, prompt):
        return lambda: Request(rid=rid, prompt=list(prompt),
                               sampling=SamplingParams(max_new_tokens=out))
    return sorted(((a, mk(r, p)) for a, r, p in out_specs),
                  key=lambda t: t[0])


def run_router_ab(arch: str = "granite-3-2b", shards: int = 3):
    """Data-parallel router A/B on the shared-prefix workload.

    Four legs, identical requests and arrival ticks: a solo engine (the
    1-device reference), a 1-shard fleet (must match the solo run BITWISE
    — the router layer adds no compute), and an N-shard fleet under
    round-robin vs cache-aware placement. The signal is the fleet-wide
    prefix-cache hit rate: round-robin scatters a prefix family across
    shards (each shard recomputes the shared prefix), cache-aware follows
    the boundary-hash chains to the shard that already holds it. Steps
    and per-request latency (submit->finish ticks) ride along."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    params = model.init(0)
    ecfg = EngineConfig(kv_pool_bytes=24 << 20, max_running=8,
                        chunk_size=32, batching_mode="packed",
                        max_num_batched_tokens=128,
                        enable_prefix_caching=True)
    wl = _router_workload()
    rows = {}
    legs = (("warmup", None, None), ("solo", None, None),
            ("router1", 1, ROUTE_CACHE_AWARE),
            (f"rr{shards}", shards, ROUTE_ROUND_ROBIN),
            (f"aware{shards}", shards, ROUTE_CACHE_AWARE))
    for tag, n, policy in legs:
        if n is None:
            eng = Engine(model, ecfg, params=params)
            submit, clock, stepf = eng.submit, lambda: eng.step_count, \
                eng.step
            busy = lambda: eng.scheduler.has_work() or eng.has_inflight
        else:
            eng = DPEngine(model, ecfg, params=params, num_shards=n,
                           policy=policy)
            submit, clock, stepf = eng.submit, lambda: eng.tick, eng.step
            busy = lambda: eng.has_work
        pending = list(wl)
        t0 = time.perf_counter()
        guard = 0
        while pending or busy():
            while pending and pending[0][0] <= clock():
                submit(pending.pop(0)[1]())
            stepf()
            guard += 1
            assert guard < 4000, tag
        wall = time.perf_counter() - t0
        if tag == "warmup":
            continue
        if n is None:
            hit = eng.mgr.prefix_hit_tokens_total
            query = eng.mgr.prefix_query_tokens_total
            steps, lat = eng.step_count, None
        else:
            fs = eng.fleet_stats()
            hit, query = fs["prefix_hit_tokens"], fs["prefix_query_tokens"]
            steps = max(fs["steps_per_shard"])
            lat = sum(eng.finish_tick[r] - eng.submit_tick[r]
                      for r in eng.finish_tick) / max(1, len(eng.finish_tick))
        rows[tag] = dict(
            outputs={r.rid: list(r.output) for r in eng.finished},
            finished=len(eng.finished), steps=steps, wall_s=wall,
            prefix_hit_tokens=hit, prefix_query_tokens=query,
            hit_rate=hit / max(1, query), mean_latency_ticks=lat,
            requests_per_shard=None if n is None
            else eng.fleet_stats()["requests_per_shard"])
    # the router in front of ONE engine is a pass-through: bitwise equal
    assert rows["router1"]["outputs"] == rows["solo"]["outputs"], \
        "1-shard fleet changed greedy outputs vs solo engine"
    aware, rr = rows[f"aware{shards}"], rows[f"rr{shards}"]
    assert sorted(aware["outputs"]) == sorted(rr["outputs"])
    assert aware["hit_rate"] > rr["hit_rate"], \
        (aware["hit_rate"], rr["hit_rate"])
    for r in rows.values():
        del r["outputs"]        # equality asserted; keep the JSON small
    return dict(arch=arch, shards=shards, **rows)


def run_disagg_ab(arch: str = "granite-3-2b", n_req=12, prompt=96, out=24,
                  budget=128):
    """Prefill/decode disaggregation A/B on a long-prompt + decode-heavy
    mix (the regime the split exists for: huge prompts competing with
    decode latency). Two timed legs over identical requests and arrival
    ticks on a 2-shard fleet: COLOCATED (both shards prefill+decode,
    the PR-8 default) and DISAGG (shard 0 prefill-only, shard 1
    decode-only, typed-page handoff at the prompt boundary). Gates:
    every request finishes exactly once in both legs, the split leg
    hands off every request, and its decode shard computes ZERO prefill
    tokens — the handoff replaced recompute entirely. Recorded per leg:
    handoff count and pages moved, per-shard prefill/decode token mix
    (the phase isolation the A/B is about), mean request latency in
    fleet ticks, and the dispatch issue/queue/compute timing split per
    shard."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    params = model.init(0)
    ecfg = EngineConfig(kv_pool_bytes=48 << 20, max_running=8,
                        chunk_size=32, batching_mode="packed",
                        max_num_batched_tokens=budget,
                        enable_prefix_caching=True)
    rows = {}
    legs = (("warmup", None), ("colocated", None),
            ("disagg", ["prefill", "decode"]))
    for tag, roles in legs:
        dp = DPEngine(model, ecfg, params=params, num_shards=2,
                      roles=roles)
        t0 = time.perf_counter()
        for i in range(n_req):
            dp.submit(Request(rid=f"r{i}", prompt=[(7 * i + j) % 101
                                                   for j in range(prompt)],
                              sampling=SamplingParams(max_new_tokens=out)))
            dp.step()       # staggered arrivals: decodes run under prefills
        guard = 0
        while dp.has_work:
            dp.step()
            guard += 1
            assert guard < 4000, tag
        wall = time.perf_counter() - t0
        if tag == "warmup":
            continue
        rids = [r.rid for r in dp.finished]
        assert len(rids) == n_req and len(set(rids)) == n_req, (tag, rids)
        fs = dp.fleet_stats()
        shards = []
        for sh in dp.shards:
            ms = sh.engine.metrics
            pf = sum(m.prefill_tokens for m in ms)
            tot = sum(m.batched_tokens for m in ms)
            shards.append(dict(
                role=sh.engine.role,
                steps=sh.engine.step_count,
                prefill_tokens=pf,
                decode_tokens=tot - pf,
                host_build_ms=sum(m.host_build_ms for m in ms),
            ))
        lat = sum(dp.finish_tick[r] - dp.submit_tick[r]
                  for r in dp.finish_tick) / max(1, len(dp.finish_tick))
        rows[tag] = dict(
            shards=shards, wall_s=wall, mean_latency_ticks=lat,
            handoffs=fs.get("handoffs", 0),
            handoff_pages=fs.get("handoff_pages", 0),
            role_failovers=fs.get("role_failovers", 0))
    d = rows["disagg"]
    # the handoff contract: every request moved, none recomputed prefill
    assert d["handoffs"] == n_req, (d["handoffs"], n_req)
    assert d["role_failovers"] == 0, d
    assert d["shards"][1]["prefill_tokens"] == 0, d["shards"][1]
    assert d["shards"][0]["decode_tokens"] == 0, d["shards"][0]
    assert rows["colocated"]["handoffs"] == 0
    return dict(arch=arch, n_req=n_req, prompt=prompt, out=out,
                budget=budget, **rows)


def main(report=print, only: str = None):
    if only == "disagg":
        db = run_disagg_ab()
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_disagg.json")
        with open(path, "w") as f:
            json.dump(db, f, indent=2, sort_keys=True)
        d, c = db["disagg"], db["colocated"]
        report(f"disagg_ab,0,"
               f"handoffs={d['handoffs']} pages={d['handoff_pages']} "
               f"decode_shard_prefill_tok={d['shards'][1]['prefill_tokens']} "
               f"lat_disagg={d['mean_latency_ticks']:.1f} "
               f"lat_coloc={c['mean_latency_ticks']:.1f} "
               f"-> {path}")
        return
    if only == "router":
        rb = run_router_ab()
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_router.json")
        with open(path, "w") as f:
            json.dump(rb, f, indent=2, sort_keys=True)
        n = rb["shards"]
        report(f"router_ab,0,"
               f"hit_aware={100 * rb[f'aware{n}']['hit_rate']:.1f}% "
               f"hit_rr={100 * rb[f'rr{n}']['hit_rate']:.1f}% "
               f"steps_solo={rb['solo']['steps']} "
               f"steps_aware={rb[f'aware{n}']['steps']} "
               f"lat_aware={rb[f'aware{n}']['mean_latency_ticks']:.1f} "
               f"lat_rr={rb[f'rr{n}']['mean_latency_ticks']:.1f} "
               f"-> {path}")
        return
    for arch in ARCH_SET:
        rows = {}
        # memory-mode A/B (paper Fig. 13/14) + batching-mode A/B: the
        # token-packed engine vs the PR-1 padded layout vs the legacy
        # one-prefill-per-step schedule, identical pool budget.
        for tag, mode, batching in (
                ("jenga", "jenga", "packed"),
                ("jenga-padded", "jenga", "padded"),
                ("jenga-serial", "jenga", "serial"),
                ("paged-baseline", "paged-baseline", "packed")):
            r = run_engine(arch, mode, batching=batching)
            rows[tag] = r
            report(f"e2e_{arch}_{tag},{r['wall_s']*1e6/max(1,r['steps']):.0f},"
                   f"steps={r['steps']} tok/step={r['tok_per_step']:.2f} "
                   f"finished={r['finished']} preempt={r['preemptions']}")
        sp = rows["paged-baseline"]["steps"] / max(1, rows["jenga"]["steps"])
        report(f"e2e_{arch}_speedup,0,steps_ratio={sp:.2f}x")
        sb = rows["jenga-serial"]["steps"] / max(1, rows["jenga"]["steps"])
        report(f"e2e_{arch}_batching_speedup,0,steps_ratio={sb:.2f}x")
    # padding-waste A/B (the token-packed dispatch win): pad slots per
    # dispatched slot and tokens per dispatch, padded vs packed layout on
    # a decode-heavy mixed workload.
    for batching in ("padded", "packed"):
        r = run_waste_ab("granite-3-2b", batching)
        report(f"dispatch_waste_{batching},0,"
               f"waste={100 * r['waste']:.1f}% "
               f"tok/dispatch={r['tok_per_dispatch']:.1f} "
               f"slots={r['slots']} tokens={r['tokens']} "
               f"finished={r['finished']}")
    # async double-buffering A/B: identical dispatches/outputs, host batch
    # build overlapped with the in-flight device step; JSON'd per-PR.
    ab = run_async_ab("granite-3-2b")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_async.json")
    with open(path, "w") as f:
        json.dump(ab, f, indent=2, sort_keys=True)
    report(f"async_ab,{ab['async_']['us_per_step']:.0f},"
           f"sync_us/step={ab['sync']['us_per_step']:.0f} "
           f"dispatches={ab['async_']['dispatches']} "
           f"overlapped_build_ms={ab['overlapped_host_build_ms']:.1f} "
           f"-> {path}")
    # pipeline-depth / device-sampling A/B: fetched-bytes collapse, depth-4
    # ring vs the depth-2 double buffer, identical greedy outputs; JSON'd.
    pb = run_pipeline_ab("granite-3-2b")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_pipeline.json")
    with open(path, "w") as f:
        json.dump(pb, f, indent=2, sort_keys=True)
    report(f"pipeline_ab,0,"
           f"fetch_bytes_ratio={pb['fetch_bytes_ratio']:.0f}x "
           f"steps_d2={pb['depth2_host']['steps']} "
           f"steps_d4={pb['depth4_device']['steps']} "
           f"bytes/step_d2host={pb['depth2_host']['fetched_bytes_per_step']:.0f} "
           f"bytes/step_d4dev={pb['depth4_device']['fetched_bytes_per_step']:.0f} "
           f"-> {path}")
    # kernel + autotune A/B: block-sparse skip accounting, kernel==ref
    # greedy outputs, autotuned-vs-constant step counts; JSON'd per-PR.
    kb = run_kernel_ab()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_kernel.json")
    with open(path, "w") as f:
        json.dump(kb, f, indent=2, sort_keys=True)
    report(f"kernel_ab,0,"
           f"skip={100 * kb['ref']['kv_block_skip_frac']:.1f}% "
           f"steps_const={kb['ref']['steps']} "
           f"steps_autotuned={kb['autotuned']['steps']} "
           f"roofline_seed={kb['budget_roofline_seed']} -> {path}")
    # data-parallel router A/B: cache-aware vs round-robin placement over
    # an N-shard fleet, 1-shard fleet bitwise == solo engine; JSON'd.
    main(report, only="router")
    # prefill/decode disaggregation A/B: typed-page handoff vs colocated,
    # zero prefill recompute on the decode shard; JSON'd.
    main(report, only="disagg")


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(only=sys.argv[1] if len(sys.argv) > 1 else None)
