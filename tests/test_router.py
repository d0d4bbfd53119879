"""Router + data-parallel fleet tests.

Placement is a pure function of (config, arrival order, shard state), so
the unit tests drive ``Router.place`` against real engines with warmed /
loaded caches and assert the exact shard ids. The fleet tests then pin
the semantic contracts: a 1-shard fleet is BITWISE the solo engine, an
N-shard fleet is output-equivalent per request across all seven
archetypes (fork-aware — shard batch mixes differ from the solo batch
mix, so bf16 reduction orders legitimately differ), and any one shard's
execution replays BITWISE on a standalone engine given the same requests
at the same shard-local arrival steps. Drain/re-admission paths must
leak nothing and must never poison the prefix cache (the PR-3 rule)."""
import random

import pytest

from conftest import assert_greedy_equiv, get_model, make_engine
from repro.serving import (ROUTE_CACHE_AWARE, ROUTE_ROUND_ROBIN, DPEngine,
                           Engine, EngineConfig, Request, Router,
                           RouterConfig, SamplingParams, ShardHealth,
                           prefix_match_tokens)
from repro.serving.autotune import BudgetAutotuner, shard_pool_bytes

ARCHS7 = ["granite-3-2b", "h2o-danube-3-4b", "qwen2-vl-2b", "zamba2-1.2b",
          "rwkv6-3b", "whisper-tiny", "dbrx-132b"]


def _req(rid, prompt, out=4, eos=None):
    return Request(rid=rid, prompt=list(prompt),
                   sampling=SamplingParams(max_new_tokens=out,
                                           eos_token=eos))


def _dp(arch="granite-3-2b", n=2, policy=ROUTE_CACHE_AWARE, roles=None,
        **cfg_kw):
    model, cfg, params = get_model(arch)
    kw = dict(kv_pool_bytes=8 << 20, max_running=4, chunk_size=8,
              max_num_batched_tokens=64, record_sample_logits=True)
    kw.update(cfg_kw)
    return DPEngine(model, EngineConfig(**kw), params=params,
                    num_shards=n, policy=policy, split_pool=False,
                    roles=roles)


# ------------------------------------------------------------- placement
def test_place_longest_prefix_match_wins():
    """Warm shard 1's prefix cache with a long prompt; a request sharing
    that prefix must route to shard 1 even when shard 0 is emptier."""
    dp = _dp(n=3)
    warm = [(3 * j + 1) % 50 for j in range(24)]
    dp.shards[1].engine.submit(_req("warm", warm, out=2))
    dp.shards[1].engine.run_until_done()
    probe = _req("probe", warm + [7, 8, 9])
    hits = [prefix_match_tokens(probe, sh.engine.mgr) for sh in dp.shards]
    assert hits[1] > 0 and hits[0] == 0 and hits[2] == 0, hits
    assert dp.submit(probe) == 1
    # and a LONGER match elsewhere outbids a shorter one: extend shard 2's
    # cache past shard 1's
    dp.shards[2].engine.submit(_req("warm2", warm + [7, 8, 9, 10], out=2))
    dp.shards[2].engine.run_until_done()
    probe2 = _req("probe2", warm + [7, 8, 9, 10, 11])
    h1 = prefix_match_tokens(probe2, dp.shards[1].engine.mgr)
    h2 = prefix_match_tokens(probe2, dp.shards[2].engine.mgr)
    assert h2 > h1 > 0, (h1, h2)
    assert dp.submit(probe2) == 2


def test_place_least_loaded_tiebreak():
    """With no cache hits anywhere, placement falls to the shard with the
    fewest outstanding tokens, then to the lowest shard id."""
    dp = _dp(n=3)
    assert dp.submit(_req("a", [1, 2, 3], out=8)) == 0      # all empty
    assert dp.submit(_req("b", [4, 5, 6], out=8)) == 1      # 0 now loaded
    assert dp.submit(_req("c", [7, 8, 9], out=8)) == 2
    # loads now equal-ish; lowest id wins the residual tie only if loads
    # match exactly — just assert determinism of the recorded placements
    sids = [p.shard for p in dp.router.placements]
    assert sids == [0, 1, 2], sids


def test_place_deterministic_replay():
    """Same workload, same config => identical placement sequence."""
    def run():
        rng = random.Random(11)
        dp = _dp(n=3)
        for i in range(10):
            plen = rng.randint(3, 20)
            dp.submit(_req(f"r{i}", [rng.randint(0, 40)
                                     for _ in range(plen)], out=3))
            if rng.random() < 0.5:
                dp.step()
        dp.run_until_done()
        return [(p.rid, p.shard, p.hit_tokens) for p in dp.router.placements]
    assert run() == run()


def test_health_cost_steers_placement():
    """Defer/preempt deltas in a health poll bump a shard's routing cost
    and push traffic away; quiet polls decay it back."""
    dp = _dp(n=2)
    base = dp.shards[0].engine.health_snapshot()
    import dataclasses as dc
    # shard 0 reports 2 new defer events: cost 2 * 16 tokens
    dp.router.observe(0, dc.replace(base, defer_count=2))
    assert dp.router.costs[0] == pytest.approx(32.0)
    assert dp.submit(_req("a", [1, 2, 3])) == 1     # cost outweighs the tie
    # quiet polls decay the cost to zero -> lowest-id tiebreak returns.
    # (loads must be equal again: let shard 1 finish its request first)
    dp.run_until_done()
    for _ in range(40):
        dp.router.observe(0, dc.replace(base, defer_count=2))
    assert dp.router.costs[0] == 0.0
    assert dp.submit(_req("b", [4, 5, 6])) == 0


def test_round_robin_ignores_caches():
    dp = _dp(n=3, policy=ROUTE_ROUND_ROBIN)
    sids = [dp.submit(_req(f"r{i}", [i, i + 1])) for i in range(6)]
    assert sids == [0, 1, 2, 0, 1, 2], sids


def test_router_rejects_bad_config():
    with pytest.raises(AssertionError):
        Router(RouterConfig(policy="nope"))
    dp = _dp(n=2)
    for sh in dp.shards:
        sh.accepting = False
    with pytest.raises(RuntimeError):
        dp.router.place(_req("x", [1]), dp.shards)


# ------------------------------------------------- started-flag semantics
def test_started_flag_not_num_computed():
    """A prefix-cache hit at admission sets seq.num_computed WITHOUT any
    device work — ``started`` must still be False until the request is
    part of a dispatched plan, so a graceful drain can safely move it."""
    eng, _ = make_engine(max_num_batched_tokens=64,
                         enable_prefix_caching=True)
    warm = [(5 * j + 2) % 50 for j in range(16)]
    eng.submit(_req("warm", warm, out=2))
    eng.run_until_done()
    hot = _req("hot", warm + [1, 2, 3])
    eng.submit(hot)
    eng.scheduler.schedule()            # admits: prefix hit, no dispatch
    assert hot.seq is not None and hot.seq.num_computed > 0
    assert not hot.started              # scheduled != dispatched
    # drain pulls it (never dispatched), pages released back to cache
    drained = eng.drain_requests(unstarted_only=True)
    assert drained == [hot] and hot.seq is None and not hot.output
    eng.mgr.check_invariants()
    # once dispatched, started flips and a graceful drain skips it
    eng.submit(_req("late", [9, 8, 7], out=3))
    eng.step()
    assert eng.scheduler.running and all(
        r.started for r in eng.scheduler.running)
    assert eng.drain_requests(unstarted_only=True) == []
    eng.run_until_done()


def test_drain_unstarted_zero_leak_and_unpoisoned():
    """Graceful drain of admitted-but-unstarted requests releases their
    prefix-hit pages back to the cache UNCHANGED: a third engine admitting
    the same prompt afterwards gets the same hit, and the re-admitted
    request's own prefix-restart on another shard produces bit-identical
    output (the PR-3 poisoning regression, at the fleet level)."""
    dp = _dp(n=2, enable_prefix_caching=True)
    warm = [(3 * j + 4) % 50 for j in range(20)]
    dp.shards[0].engine.submit(_req("warm", warm, out=2))
    dp.shards[0].engine.run_until_done()
    ref_out = {r.rid: list(r.output) for r in dp.shards[0].engine.finished}

    hot = _req("hot", warm + [5, 6], out=4)
    assert dp.submit(hot) == 0          # follows its prefix
    dp.shards[0].engine.scheduler.schedule()    # admit (hit), no dispatch
    assert hot.seq is not None and not hot.started
    used_before = dp.shards[0].engine.mgr.memory_stats().used_units
    moved = dp.inject_stall(0, resume_after=2)
    assert moved == [hot] and hot.shard_history == [0, 1]
    # nothing leaked on the drained shard beyond the warm request's cache
    stats = dp.shards[0].engine.mgr.memory_stats()
    assert stats.used_units == 0 and used_before > 0, (stats, used_before)
    dp.check_invariants()
    dp.run_until_done()
    assert {r.rid for r in dp.finished} == {"warm", "hot"}

    # the same request cold on a solo engine: identical tokens
    solo, _ = make_engine(max_num_batched_tokens=64,
                          enable_prefix_caching=True)
    solo.submit(_req("hot", warm + [5, 6], out=4))
    solo.run_until_done()
    dp_out = {r.rid: list(r.output) for r in dp.finished}
    assert dp_out["hot"] == list(solo.finished[0].output)
    assert dp_out["warm"] == ref_out["warm"]
    # and shard 0's cache still serves the warm prefix (not poisoned)
    assert prefix_match_tokens(_req("p", warm + [9]),
                               dp.shards[0].engine.mgr) > 0


# ------------------------------------------------------ fleet equivalence
@pytest.mark.parametrize("arch", ARCHS7)
def test_fleet_outputs_match_solo(arch):
    """Every archetype: a 3-shard fleet finishes the same requests with
    the same greedy tokens as one solo engine (fork-aware: shard batch
    mixes differ from the solo mix)."""
    rng = random.Random(hash(arch) & 0xffff)
    model, cfg, params = get_model(arch)
    reqs = []
    for i in range(5):
        kw = {}
        prompt = [rng.randint(0, 49) for _ in range(rng.randint(4, 16))]
        if cfg.family == "vlm" and i % 2 == 0:
            from repro.core.request import MMItem
            kw["mm_items"] = (MMItem(0, min(3, len(prompt)), mm_hash=i),)
        if cfg.family == "encdec":
            from repro.core.request import MMItem
            kw["encoder_items"] = (MMItem(0, cfg.encoder_seq, mm_hash=i),)
        reqs.append(dict(rid=f"r{i}", prompt=prompt,
                         out=rng.randint(2, 5), kw=kw))

    def build(r):
        return Request(rid=r["rid"], prompt=list(r["prompt"]),
                       sampling=SamplingParams(max_new_tokens=r["out"]),
                       **r["kw"])

    ecfg = dict(kv_pool_bytes=8 << 20, max_running=4, chunk_size=8,
                max_num_batched_tokens=64, record_sample_logits=True)
    solo = Engine(model, EngineConfig(**ecfg), params=params)
    for r in reqs:
        solo.submit(build(r))
    solo.run_until_done()

    dp = DPEngine(model, EngineConfig(**ecfg), params=params,
                  num_shards=3, split_pool=False)
    for r in reqs:
        dp.submit(build(r))
    dp.run_until_done()
    dp.check_invariants()
    for sh in dp.shards:
        assert sh.engine.mgr.memory_stats().used_units == 0
    assert_greedy_equiv(solo, dp, label=f"fleet-{arch}")


def test_router1_bitwise_equals_solo():
    """A 1-shard fleet IS the solo engine plus a pass-through router:
    outputs must match bit for bit, no fork tolerance."""
    rng = random.Random(3)
    solo, _ = make_engine(max_num_batched_tokens=64)
    dp = _dp(n=1)
    for i in range(6):
        prompt = [rng.randint(0, 49) for _ in range(rng.randint(3, 18))]
        solo.submit(_req(f"r{i}", prompt, out=4))
        dp.submit(_req(f"r{i}", prompt, out=4))
        solo.step()
        dp.step()
    solo.run_until_done()
    dp.run_until_done()
    assert {r.rid: list(r.output) for r in solo.finished} \
        == {r.rid: list(r.output) for r in dp.finished}


def test_shard_replay_bitwise():
    """Any one shard's run replays bitwise on a standalone engine: same
    requests, same shard-local arrival steps => same batches, same
    dispatches, same tokens. (This is the determinism contract that makes
    fleet failures debuggable shard by shard.)"""
    rng = random.Random(17)
    dp = _dp(n=3)
    reqs = {}
    for i in range(9):
        r = _req(f"r{i}", [rng.randint(0, 49)
                           for _ in range(rng.randint(3, 15))], out=3)
        reqs[r.rid] = r
        dp.submit(r)
        if rng.random() < 0.6:
            dp.step()
    dp.run_until_done()
    for sh in dp.shards:
        fin = sh.engine.finished
        if not fin:
            continue
        replay, _ = make_engine(max_num_batched_tokens=64,
                                record_sample_logits=True)
        pending = sorted(fin, key=lambda r: (r.arrival, r.rid))
        guard = 0
        while pending or replay.scheduler.has_work() or replay.has_inflight:
            while pending and pending[0].arrival <= replay.step_count:
                src = pending.pop(0)
                replay.submit(_req(src.rid, src.prompt,
                                   out=src.sampling.max_new_tokens))
            if not replay.scheduler.has_work() and not replay.has_inflight:
                src = pending.pop(0)    # idle gap: arrivals don't advance
                replay.submit(_req(src.rid, src.prompt,
                                   out=src.sampling.max_new_tokens))
            replay.step()
            guard += 1
            assert guard < 500
        assert {r.rid: list(r.output) for r in replay.finished} \
            == {r.rid: list(r.output) for r in fin}, sh.sid


# ------------------------------------------------------------- autotuner
def test_autotuner_shard_window_scaling():
    """Per-shard budgets: the roofline seed is per-device (unchanged by
    fleet size), but the observation window scales with N — a shard sees
    1/N of the traffic, so it needs N x the steps before moving budgets."""
    _, cfg, _ = get_model("granite-3-2b")
    one = BudgetAutotuner(cfg, "TPU v5 lite")
    four = BudgetAutotuner(cfg, "TPU v5 lite", num_shards=4)
    assert four.budget == one.budget
    assert four.prefill_cap == one.prefill_cap
    assert four.window == 4 * one.window
    assert shard_pool_bytes(100, 4) == 25
    assert shard_pool_bytes(3, 8) == 1      # floor, never zero


def test_fleet_autotuned_budgets_per_shard(monkeypatch):
    # the budget seed models a v5e; the fleet itself runs on the CPU
    from repro.serving import dp_engine, engine
    for mod in (engine, dp_engine):
        monkeypatch.setattr(mod, "device_kind", lambda model: "TPU v5 lite")
    dp = _dp(n=2, autotune_budgets=True)
    for sh in dp.shards:
        assert sh.engine.autotuner is not None
        assert sh.engine.autotuner.num_shards == 2
    dp.submit(_req("a", [1, 2, 3, 4], out=3))
    dp.run_until_done()
    assert len(dp.finished) == 1


# --------------------------------------- prefill/decode disaggregation
def _mk_mgr_pair(n_large_src=16, n_large_dst=16):
    """Two standalone managers sharing a spec set — a prefill shard's and a
    decode shard's pools, without the engines around them."""
    from repro.core import (BYTES_PER_UNIT, JengaKVCacheManager,
                            attention_spec, make_geometry, mamba_spec)
    specs = [attention_spec("full_attn", num_layers=2, kv_heads=1,
                            head_dim=64, tokens_per_page=4),
             mamba_spec("ssm", num_layers=2, conv_units=64, ssm_units=64,
                        checkpoint_interval=4)]
    g = make_geometry(specs, total_memory_bytes=10**9)

    def mk(n_large):
        return JengaKVCacheManager(
            specs, total_memory_bytes=g.large_page_units * n_large *
            BYTES_PER_UNIT)
    return mk(n_large_src), mk(n_large_dst)


def test_export_adopt_roundtrip():
    """Manager-level handoff: export a computed request's typed page set,
    adopt it on a second manager. The destination mirrors the tables,
    registers the same hashes, resumes at the same position with zero
    tokens left to recompute — and both sides drain clean."""
    from repro.core import SequenceState
    src, dst = _mk_mgr_pair()
    r = SequenceState(rid="h0", tokens=list(range(100, 112)))
    ok, _ = src.begin_request(r)
    assert ok
    assert src.allocate_for_tokens(r, 12)
    src.advance(r, 12)
    export = src.export_request(r)
    assert export.num_tokens == 12

    r2 = SequenceState(rid="h0", tokens=list(r.tokens))
    ok, pairs = dst.adopt_request(r2, export)
    assert ok and pairs
    # position restored: nothing to recompute, chains continue verbatim
    assert r2.num_computed == 12 and r2.prefix_hit_tokens == 12
    assert len(r2.page_tables["full_attn"]) == len(r.page_tables["full_attn"])
    assert r2.page_hashes == r.page_hashes
    # every copy pair reads a USED source page into a USED dest page
    for name, s_eid, d_eid in pairs:
        from repro.core import PageState
        assert src.pools[name].pages[s_eid].state == PageState.USED
        assert dst.pools[name].pages[d_eid].state == PageState.USED
    assert dst.handoff_adopted == 1
    assert dst.handoff_pages_adopted == len(pairs)

    # decode continues on the destination as if it computed the prefill
    assert dst.allocate_for_tokens(r2, 14)
    r2.tokens.extend([7, 8])
    dst.advance(r2, 14)
    dst.free_request(r2, cache=True)
    # source side: release retires its copy into the prefix cache
    src.release_export(r, export)
    assert src.memory_stats().used_units == 0
    assert dst.memory_stats().used_units == 0
    src.check_invariants()
    dst.check_invariants()
    # both caches now serve the prompt: a fresh same-prompt arrival hits
    for m in (src, dst):
        probe = SequenceState(rid="p", tokens=list(range(100, 112)))
        assert m.lookup_prefix(probe) > 0, "adopted hashes not registered"


def test_adopt_failure_rolls_back():
    """Destination pool pressure mid-adopt: every allocation is undone,
    the request is cleared, and the source cancels back to normal
    ownership — the §5.4 transaction across a shard boundary."""
    from repro.core import SequenceState
    src, dst = _mk_mgr_pair(n_large_dst=1)     # destination cannot fit it
    r = SequenceState(rid="h1", tokens=list(range(100, 124)))
    ok, _ = src.begin_request(r)
    assert ok
    assert src.allocate_for_tokens(r, 24)
    src.advance(r, 24)
    export = src.export_request(r)

    before = dst.memory_stats()
    r2 = SequenceState(rid="h1", tokens=list(r.tokens))
    ok, pairs = dst.adopt_request(r2, export)
    assert not ok and pairs == []
    after = dst.memory_stats()
    assert after.used_units == before.used_units == 0, (before, after)
    assert not r2.page_tables and not r2.state_pages and not r2.ckpt_pages
    assert r2.num_computed == 0
    dst.check_invariants()
    # failover: the source cancels the export and keeps running
    src.cancel_export(export)
    src.free_request(r, cache=False)
    assert src.memory_stats().used_units == 0
    src.check_invariants()


def test_place_role_filter_and_fallback():
    """``want`` restricts placement to role-compatible shards; when no
    accepting shard qualifies the filter is dropped, not fatal — a
    degraded fleet keeps serving colocated."""
    dp = _dp(n=3, roles=["prefill", "decode", "decode"])
    assert dp.router.place(_req("a", [1, 2, 3]), dp.shards,
                           want="prefill") == 0
    assert dp.router.place(_req("b", [1, 2, 3]), dp.shards,
                           want="decode") in (1, 2)
    dp.shards[1].accepting = False
    dp.shards[2].accepting = False
    # no decode-capable shard accepting: fall back to whoever is
    assert dp.router.place(_req("c", [1, 2, 3]), dp.shards,
                           want="decode") == 0


def test_disagg_zero_decode_prefill_and_matches_solo():
    """The tentpole contract: a prefill/decode split fleet finishes every
    request with the solo engine's greedy tokens, the decode shard
    computes ZERO prefill tokens (handoff admits whole-prompt hits), the
    handoff log is populated, and both shards drain leak-free."""
    rng = random.Random(7)
    reqs = [(f"r{i}",
             [rng.randint(0, 49) for _ in range(rng.randint(4, 20))],
             rng.randint(2, 5))
            for i in range(5)]
    solo, _ = make_engine(max_num_batched_tokens=64,
                          record_sample_logits=True)
    for rid, prompt, out in reqs:
        solo.submit(_req(rid, prompt, out=out))
    solo.run_until_done()

    dp = _dp(n=2, roles=["prefill", "decode"])
    for rid, prompt, out in reqs:
        dp.submit(_req(rid, prompt, out=out))
    dp.run_until_done()
    dp.check_invariants()
    assert len(dp.finished) == len(reqs)
    assert dp.handoffs, "no handoffs happened — disagg never engaged"
    fs = dp.fleet_stats()
    assert fs["handoffs"] == len(dp.handoffs)
    assert fs["handoff_pages"] > 0
    # decode shard never computed a prefill token
    dec = dp.shards[1].engine
    assert sum(m.prefill_tokens for m in dec.metrics) == 0
    # prefill shard never decoded: every handed-off request left at
    # exactly its prompt boundary (t0 sampled, zero decode steps)
    for h in dp.handoffs:
        prompt = next(p for rid, p, _ in reqs if rid == h["rid"])
        assert h["tokens"] == len(prompt), h
    for sh in dp.shards:
        assert sh.engine.mgr.memory_stats().used_units == 0, sh.sid
        assert not sh.engine.runner._mirrors
    assert_greedy_equiv(solo, dp, label="disagg")


def test_disagg_all_decode_dead_falls_back_colocated():
    """Failover: every decode-capable shard dies while prefill-complete
    requests await handoff — the fleet flips the surviving prefill shard
    to colocated ("both") and still finishes everything exactly once."""
    rng = random.Random(23)
    reqs = [(f"r{i}",
             [rng.randint(0, 49) for _ in range(rng.randint(6, 16))], 4)
            for i in range(4)]
    solo, _ = make_engine(max_num_batched_tokens=64,
                          record_sample_logits=True)
    for rid, prompt, out in reqs:
        solo.submit(_req(rid, prompt, out=out))
    solo.run_until_done()

    dp = _dp(n=2, roles=["prefill", "decode"])
    for rid, prompt, out in reqs:
        dp.submit(_req(rid, prompt, out=out))
    dp.step()
    dp.inject_crash(1)                  # the only decode shard dies
    dp.run_until_done()
    assert dp.fleet_stats()["role_failovers"] >= 1
    assert dp.shards[0].engine.role == "both"   # flipped to colocated
    rids = [r.rid for r in dp.finished]
    assert sorted(rids) == sorted(r[0] for r in reqs)
    assert len(rids) == len(set(rids))
    for sh in dp.shards:
        assert sh.engine.mgr.memory_stats().used_units == 0, sh.sid
    assert_greedy_equiv(solo, dp, label="disagg-failover")
