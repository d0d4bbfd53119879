"""Token-budget mixed prefill/decode batching: packing, equivalence across
the three batching layouts, transactional batch allocation, and preemption.

The engine packs multiple concurrent prefill chunks plus all decodes into
ONE dispatch per step, as a token-packed stream (``"packed"``, default) or
as padded per-sequence rows (``"padded"``, the PR-1 layout; ``"mixed"`` is
a legacy alias); ``batching_mode="serial"`` reproduces the old
one-prefill-chunk-per-step engine. Greedy outputs must be identical token
for token across all three schedules for every model family.
"""
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.serving import Engine, EngineConfig, Request, SamplingParams


from conftest import assert_greedy_equiv, make_engine


def run_workload(eng, n_req=3, prompt=14, out=4):
    for i in range(n_req):
        eng.submit(Request(rid=f"r{i}", prompt=[(3 * i + j) % 50
                                                for j in range(prompt + i)],
                           sampling=SamplingParams(max_new_tokens=out)))
    eng.run_until_done(max_steps=2000)
    return {r.rid: list(r.output) for r in eng.finished}


# ------------------------------------------------------------------ packing
def test_multi_prefill_packing_respects_budget():
    budget = 20
    eng, _ = make_engine(chunk_size=8, max_num_batched_tokens=budget)
    for i in range(4):
        eng.submit(Request(rid=f"r{i}", prompt=list(range(16)),
                           sampling=SamplingParams(max_new_tokens=3)))
    eng.run_until_done(max_steps=500)
    assert len(eng.finished) == 4
    assert all(m.batched_tokens <= budget for m in eng.metrics), \
        [(m.step, m.batched_tokens) for m in eng.metrics]
    # the budget admits more than one prefill chunk per step
    assert max(m.num_prefills for m in eng.metrics) >= 2
    # and prefill chunks ride together with decodes in one plan
    assert any(m.num_prefills >= 1 and m.decode_batch >= 1
               for m in eng.metrics)


def test_serial_mode_schedules_one_prefill():
    eng, _ = make_engine(batching_mode="serial")
    for i in range(3):
        eng.submit(Request(rid=f"r{i}", prompt=list(range(16)),
                           sampling=SamplingParams(max_new_tokens=2)))
    eng.run_until_done(max_steps=500)
    assert len(eng.finished) == 3
    assert all(m.num_prefills <= 1 for m in eng.metrics)


# -------------------------------------------------------------- determinism
@pytest.mark.parametrize("arch", ["granite-3-2b", "h2o-danube-3-4b",
                                  "qwen2-vl-2b", "zamba2-1.2b", "rwkv6-3b",
                                  "whisper-tiny", "dbrx-132b",
                                  "granite-3-2b+wide"])
def test_packed_padded_serial_greedy_equal(arch):
    """Greedy outputs are token-identical across all three batching
    layouts — packed stream, padded rows, and the legacy
    one-prefill-per-step schedule (ample memory: no preemption) — for
    every model family (attention, swa, vlm, hybrid-mamba2, rwkv6,
    encdec, moe), up to fork-checked ambiguous near-ties: the layouts
    reduce in different orders, so genuinely tied top-2 decisions may
    flip (conftest.assert_greedy_equiv bounds any divergence)."""
    engs = {}
    for mode in ("packed", "padded", "serial"):
        eng, _ = make_engine(arch, batching_mode=mode,
                             max_num_batched_tokens=64,
                             record_sample_logits=True)
        run_workload(eng)
        engs[mode] = eng
    assert_greedy_equiv(engs["packed"], engs["padded"],
                        label=f"{arch}/padded")
    assert_greedy_equiv(engs["packed"], engs["serial"],
                        label=f"{arch}/serial")


@pytest.mark.parametrize("arch", ["qwen2-vl-2b", "whisper-tiny"])
def test_batching_modes_match_multimodal(arch):
    """Determinism with actual mm/encoder items: packed/padded batches must
    route mm embeddings / encoder KV writes to the right tokens/rows."""
    from repro.core.request import MMItem
    outs = {}
    for mode in ("packed", "padded", "serial"):
        eng, cfg = make_engine(arch, batching_mode=mode,
                               max_num_batched_tokens=64)
        for i in range(2):
            kw = {}
            if arch == "whisper-tiny":
                kw["encoder_items"] = (MMItem(0, cfg.encoder_seq,
                                              mm_hash=7 + i),)
            else:
                kw["mm_items"] = (MMItem(2, 6, mm_hash=40 + i),)
            eng.submit(Request(rid=f"r{i}", prompt=list(range(12 + i)),
                               sampling=SamplingParams(max_new_tokens=3),
                               **kw))
        eng.run_until_done(max_steps=500)
        outs[mode] = {r.rid: list(r.output) for r in eng.finished}
    assert outs["packed"] == outs["padded"] == outs["serial"], (arch, outs)


def test_mixed_chunk_size_invariance():
    """Generations must not depend on how prefill is chunked/packed."""
    outs = []
    for chunk, budget in ((4, 16), (8, 64), (64, 256)):
        eng, _ = make_engine(chunk_size=chunk,
                             max_num_batched_tokens=budget)
        eng.submit(Request(rid="x", prompt=list(range(20)),
                           sampling=SamplingParams(max_new_tokens=6)))
        eng.run_until_done()
        outs.append(eng.finished[0].output)
    assert outs[0] == outs[1] == outs[2], outs


# ------------------------------------------------------- fewer engine steps
def test_mixed_needs_fewer_steps_than_serial():
    """The point of the refactor: identical workload + pool budget, fewer
    engine steps (more tokens per dispatch) than one-prefill-per-step."""
    steps = {}
    for mode in ("mixed", "serial"):
        eng, _ = make_engine(batching_mode=mode, max_running=8,
                             max_num_batched_tokens=256)
        for i in range(4):
            eng.submit(Request(rid=f"r{i}", prompt=list(range(64)),
                               sampling=SamplingParams(max_new_tokens=4)))
        eng.run_until_done(max_steps=2000)
        assert len(eng.finished) == 4
        steps[mode] = eng.step_count
    assert steps["mixed"] < steps["serial"], steps


# ------------------------------------------------------------- transactions
def test_allocate_for_batch_transactional():
    """A failing batch allocation must leave the manager untouched."""
    from repro.core.request import SequenceState
    eng, _ = make_engine(kv_pool_bytes=300_000)
    mgr = eng.mgr
    a = SequenceState(rid="a", tokens=list(range(8)))
    ok, _ = mgr.begin_request(a)
    assert ok
    assert mgr.allocate_for_tokens(a, 8)
    before = mgr.memory_stats().used_units
    b = SequenceState(rid="b", tokens=list(range(8)))
    ok, _ = mgr.begin_request(b)
    assert ok
    huge = SequenceState(rid="huge", tokens=[0] * 100_000)
    ok, _ = mgr.begin_request(huge)
    assert ok
    # second member's target is unsatisfiable -> the whole batch must roll
    # back, including b's pages allocated before the failure
    assert not mgr.allocate_for_batch([b, huge], [8, 100_000])
    assert mgr.memory_stats().used_units == before
    mgr.check_invariants()
    # and a feasible plan over the same sequences commits
    assert mgr.allocate_for_batch([b, a], [8, 8])
    assert mgr.memory_stats().used_units > before
    mgr.check_invariants()


# --------------------------------------------------------------- preemption
def test_oom_preemption_recovers_mixed():
    """Tiny pool forces preemption mid-plan; every request still completes
    and the batch-transactional allocator keeps invariants intact."""
    cfg = reduced(ARCHS["granite-3-2b"])
    model = build_model(cfg, single_device_dist())
    eng = Engine(model, EngineConfig(kv_pool_bytes=200_000, max_running=4,
                                     chunk_size=8,
                                     max_num_batched_tokens=64))
    for i in range(4):
        eng.submit(Request(rid=f"r{i}", prompt=list(range(16)),
                           sampling=SamplingParams(max_new_tokens=4)))
    done = eng.run_until_done(max_steps=500)
    assert len(done) == 4, (len(done), eng.scheduler.preemption_count)
    eng.mgr.check_invariants()
