"""Roofline-seeded budget autotuning + attention-work accounting.

The autotuner is pure host logic, so its two adjustment rules are unit
tested on synthetic StepMetrics; the engine integration test checks the
seeding reaches the scheduler and a full run stays healthy. The
block-sparse attention-work counters (host mirror of the kernel's
segment-interval skip test) are asserted at both the runner and the
StepMetrics level.

The budget seed is the roofline of one v5e chip: engine-level tests run on
the CPU, so they make the engine see that chip (``as_v5e``).
"""
import pytest

from conftest import make_engine
from repro.configs import ARCHS, reduced
from repro.serving import Request, SamplingParams
from repro.serving import engine as engine_mod
from repro.serving.autotune import (MAX_BUDGET, MIN_BUDGET, QUANTUM,
                                    BudgetAutotuner, roofline_token_budget)
from repro.serving.engine import StepMetrics

V5E = "TPU v5 lite"


@pytest.fixture
def as_v5e(monkeypatch):
    monkeypatch.setattr(engine_mod, "device_kind", lambda model: V5E)


def mk_metrics(step, **kw):
    base = dict(decode_batch=1, prefill_tokens=0, used_units=0,
                evictable_units=0, empty_units=0, free_units=0)
    base.update(kw)
    return StepMetrics(step=step, **base)


# -------------------------------------------------------------- seeding
def test_roofline_seed_bounds_and_quantum():
    for arch in ("granite-3-2b", "dbrx-132b", "rwkv6-3b"):
        b = roofline_token_budget(reduced(ARCHS[arch]), V5E)
        assert MIN_BUDGET <= b <= MAX_BUDGET
        assert b % QUANTUM == 0


def test_moe_seed_exceeds_dense():
    """MoE total/active > 1 pushes the balance point right: a step must
    batch more tokens before the (all-expert) weight read is amortized."""
    dense = roofline_token_budget(reduced(ARCHS["granite-3-2b"]), V5E)
    moe = roofline_token_budget(reduced(ARCHS["dbrx-132b"]), V5E)
    assert moe > dense


def test_unknown_device_kind_raises():
    """Peaks come from the published table only: a chip missing from it
    (here the CPU the engine runs on) is an error, not a v5e default."""
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_token_budget(reduced(ARCHS["granite-3-2b"]), "cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        make_engine(autotune_budgets=True, batching_mode="packed")


# ----------------------------------------------------------- adjustments
def test_host_bound_grows_budget():
    tun = BudgetAutotuner(reduced(ARCHS["granite-3-2b"]), V5E, window=4)
    b0, p0 = tun.budget, tun.prefill_cap
    changed = []
    for i in range(4):
        changed.append(tun.observe(mk_metrics(
            i, host_build_ms=5.0, dispatch_ms=1.0)))
    assert changed == [False, False, False, True]
    assert tun.budget > b0 and tun.budget % QUANTUM == 0
    assert tun.prefill_cap >= p0
    assert tun.adjustments == 1
    assert len(tun._hist) == 0      # window restarts after an adjustment


def test_bytes_trend_shrinks_prefill_cap_to_floor():
    tun = BudgetAutotuner(reduced(ARCHS["granite-3-2b"]), V5E, window=4)
    floor = max(QUANTUM, QUANTUM * round(tun.budget / 2 / QUANTUM))
    for round_ in range(8):          # keep feeding growing-traffic windows
        for i in range(4):
            tun.observe(mk_metrics(
                4 * round_ + i, host_build_ms=0.1, dispatch_ms=1.0,
                attn_bytes_modeled=1e6 * (1 + 10 * (i // 2))))
    assert tun.prefill_cap == floor  # clamped, never collapses to QUANTUM
    assert tun.budget == roofline_token_budget(tun.model_cfg, V5E)  # untouched


def test_flat_traffic_no_adjustment():
    tun = BudgetAutotuner(reduced(ARCHS["granite-3-2b"]), V5E, window=4)
    for i in range(12):
        assert not tun.observe(mk_metrics(
            i, host_build_ms=0.1, dispatch_ms=1.0, attn_bytes_modeled=1e6))
    assert tun.adjustments == 0


# ------------------------------------------------------- work accounting
def test_attn_block_stats_flow_to_metrics():
    """Runner accumulates per-dispatch block-scan/skip counters and the
    engine slices them into per-step StepMetrics deltas that sum back to
    the runner totals."""
    eng, _ = make_engine(batching_mode="packed", max_num_batched_tokens=64)
    for i in range(4):
        eng.submit(Request(rid=f"r{i}",
                           prompt=[(3 * i + j) % 50 for j in range(12 + i)],
                           sampling=SamplingParams(max_new_tokens=4)))
    eng.run_until_done(max_steps=500)
    r = eng.runner
    assert r.kv_blocks_scanned > 0
    assert r.attn_flops_modeled > 0 and r.attn_bytes_modeled > 0
    ms = eng.metrics
    assert sum(m.kv_blocks_scanned for m in ms) == r.kv_blocks_scanned
    assert sum(m.kv_blocks_skipped for m in ms) == r.kv_blocks_skipped
    assert abs(sum(m.attn_flops_modeled for m in ms)
               - r.attn_flops_modeled) < 1e-6 * max(1.0, r.attn_flops_modeled)


def test_rwkv_has_no_attention_work():
    """No token-page attention tables -> the counters stay zero (the
    modeled work is attention-only by construction)."""
    eng, _ = make_engine("rwkv6-3b", batching_mode="packed")
    eng.submit(Request(rid="r0", prompt=list(range(10)),
                       sampling=SamplingParams(max_new_tokens=3)))
    eng.run_until_done(max_steps=200)
    assert eng.runner.kv_blocks_scanned == 0
    assert eng.runner.attn_flops_modeled == 0.0


# ----------------------------------------------------- engine integration
def test_engine_seeds_scheduler_from_roofline(as_v5e):
    eng, cfg = make_engine(autotune_budgets=True, batching_mode="packed")
    seed = roofline_token_budget(cfg, V5E)
    assert eng.autotuner is not None
    assert eng.scheduler.cfg.max_num_batched_tokens == seed
    assert eng.scheduler.cfg.max_prefill_tokens_per_step \
        == eng.autotuner.prefill_cap


def test_autotuned_run_completes(as_v5e):
    eng, _ = make_engine(autotune_budgets=True, batching_mode="packed")
    for i in range(4):
        eng.submit(Request(rid=f"r{i}",
                           prompt=[(5 * i + j) % 50 for j in range(16)],
                           sampling=SamplingParams(max_new_tokens=4)))
    done = eng.run_until_done(max_steps=500)
    eng.mgr.check_invariants()
    assert len(done) == 4
    # budgets remain quantized and bounded whatever observe() did
    assert eng.scheduler.cfg.max_num_batched_tokens % QUANTUM == 0
    assert MIN_BUDGET <= eng.scheduler.cfg.max_num_batched_tokens \
        <= MAX_BUDGET
