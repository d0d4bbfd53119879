"""Token-packed varlen dispatch: stream layout, segment isolation, token
bucketing, the latency-aware prefill cap, and the padding-waste win over
the padded layout.

The packed engine flattens every step into one (total_tokens_bucket,)
token stream with per-token segment ids; these tests pin down the
properties that make that safe: the segment mask never lets a token see
another segment or its own future, per-segment recurrent states reset at
segment boundaries (covered by the cross-family equivalence tests in
test_mixed_batching), and dispatched slots track the scheduler's token
budget instead of B*T padding.
"""
import numpy as np
import pytest

from repro.serving import Request, SamplingParams
from repro.serving.runner import _seg_bucket, _tok_bucket


from conftest import make_engine


# ---------------------------------------------------------------- bucketing
def test_tok_bucket_shape():
    """pow2 below 16 (exact small decode steps), then four buckets per
    octave — multiples of 16 up to 64 tokens, of 32 up to 128, of 64 up to
    256, of 128 up to 512 — so pad slots stay under 16, or under half the
    step above 64 tokens, and a run meets few serve-step programs;
    segments bucket to powers of two up to 16, then powers of four."""
    assert [_tok_bucket(n) for n in (1, 2, 3, 7, 8, 9, 16)] == \
        [1, 2, 4, 8, 8, 16, 16]
    assert _tok_bucket(17) == 32
    assert _tok_bucket(71) == 96
    assert _tok_bucket(255) == 256
    assert _tok_bucket(257) == 384
    assert len({_tok_bucket(n) for n in range(257, 513)}) == 2
    for n in range(17, 1100):
        b = _tok_bucket(n)
        q = max(16, 1 << (n - 1).bit_length() >> 2)
        assert b >= n and b - n < q and b % q == 0
        assert b - n < max(16, n / 2)
    assert [_seg_bucket(n) for n in (1, 3, 16, 17, 64, 65, 150, 256, 257)] \
        == [1, 4, 16, 64, 64, 256, 256, 256, 1024]


def test_packed_single_decode_is_one_slot():
    """A lone decode step dispatches a 1-token stream, not a padded row."""
    eng, _ = make_engine(batching_mode="packed")
    eng.submit(Request(rid="x", prompt=list(range(8)),
                       sampling=SamplingParams(max_new_tokens=3)))
    eng.run_until_done()
    decode_steps = [m for m in eng.metrics
                    if m.decode_batch == 1 and m.num_prefills == 0]
    assert decode_steps and all(m.dispatched_slots == 1
                                for m in decode_steps)


# ------------------------------------------------------------ padding waste
def test_packed_waste_below_padded():
    """The tentpole claim: on a decode-heavy mixed workload the packed
    stream's padding waste (pad slots / dispatched slots) collapses versus
    the padded (B, T) layout, whose decode rows pay the co-scheduled
    prefill chunk's length."""
    waste = {}
    for mode in ("padded", "packed"):
        eng, _ = make_engine(batching_mode=mode, max_running=8,
                             max_num_batched_tokens=128)
        for i in range(8):
            eng.submit(Request(rid=f"r{i}", prompt=list(range(48)),
                               sampling=SamplingParams(max_new_tokens=16)))
        eng.run_until_done(max_steps=2000)
        assert len(eng.finished) == 8
        r = eng.runner
        waste[mode] = 1.0 - r.tokens_dispatched / r.slots_dispatched
    assert waste["packed"] < waste["padded"], waste
    assert waste["packed"] < 0.25, waste   # stream tracks the budget


# ------------------------------------------------------- latency-aware cap
def test_max_prefill_tokens_per_step_caps_prefill():
    """A huge prompt must not monopolize the step budget: with the cap set,
    prefill tokens per step stay at the cap while decodes of other requests
    keep running every step."""
    eng, _ = make_engine(batching_mode="packed", max_running=4,
                         max_num_batched_tokens=64,
                         max_prefill_tokens_per_step=16)
    eng.submit(Request(rid="short", prompt=list(range(8)),
                       sampling=SamplingParams(max_new_tokens=24)))
    eng.run_until_done(max_steps=6)        # short request reaches decode
    eng.submit(Request(rid="huge", prompt=list(range(120)),
                       sampling=SamplingParams(max_new_tokens=2)))
    eng.run_until_done(max_steps=2000)
    assert len(eng.finished) == 2
    assert all(m.prefill_tokens <= 16 for m in eng.metrics), \
        [(m.step, m.prefill_tokens) for m in eng.metrics]
    # decode latency protected: every step that prefilled the huge prompt
    # after the short request reached decode also decoded it
    mixed = [m for m in eng.metrics if m.prefill_tokens > 0
             and m.decode_batch > 0]
    assert mixed, "prefill chunks should ride along with running decodes"


def test_max_prefill_cap_same_outputs():
    """The cap changes step packing, never outputs."""
    outs = []
    for cap in (None, 8):
        eng, _ = make_engine(batching_mode="packed",
                             max_num_batched_tokens=64,
                             max_prefill_tokens_per_step=cap)
        eng.submit(Request(rid="x", prompt=list(range(20)),
                           sampling=SamplingParams(max_new_tokens=6)))
        eng.run_until_done()
        outs.append(eng.finished[0].output)
    assert outs[0] == outs[1], outs


# ------------------------------------------------------- budget invariance
def test_packed_budget_invariance():
    """Generations must not depend on how the stream is packed/bucketed."""
    outs = []
    for chunk, budget in ((4, 16), (8, 64), (64, 256)):
        eng, _ = make_engine(batching_mode="packed", chunk_size=chunk,
                             max_num_batched_tokens=budget)
        eng.submit(Request(rid="x", prompt=list(range(20)),
                           sampling=SamplingParams(max_new_tokens=6)))
        eng.run_until_done()
        outs.append(eng.finished[0].output)
    assert outs[0] == outs[1] == outs[2], outs


def test_packed_oom_preemption_recovers():
    """Tiny pool forces preemption mid-plan under the packed layout too."""
    eng, _ = make_engine(batching_mode="packed", kv_pool_bytes=200_000,
                         max_num_batched_tokens=64)
    for i in range(4):
        eng.submit(Request(rid=f"r{i}", prompt=list(range(16)),
                           sampling=SamplingParams(max_new_tokens=4)))
    done = eng.run_until_done(max_steps=500)
    assert len(done) == 4, (len(done), eng.scheduler.preemption_count)
    eng.mgr.check_invariants()


# ------------------------------------------------------------ segment mask
def test_segment_mask_basics():
    import jax.numpy as jnp
    from repro.models.attention import segment_mask
    seg = jnp.asarray([[0, 0, 1, 1, 1, -1]])
    pos = jnp.asarray([[5, 6, 0, 1, 2, 1 << 29]])
    m = np.asarray(segment_mask(seg, pos, seg, pos))
    # own past+self visible, futures and other segments invisible; when q
    # and kv are the SAME stream (the fresh-KV path) pads see only each
    # other — their rows are garbage and dropped by the caller, while real
    # tokens never see a pad (kv-side pads in the old-page stream carry -2
    # and match nothing at all)
    expect = np.zeros((6, 6), bool)
    expect[0, 0] = expect[1, 0] = expect[1, 1] = True
    expect[2, 2] = True
    expect[3, 2] = expect[3, 3] = True
    expect[4, 2] = expect[4, 3] = expect[4, 4] = True
    expect[5, 5] = True
    assert (m[0] == expect).all(), m[0].astype(int)


def test_segment_mask_property():
    """Hypothesis: for random packed layouts, token i never attends a slot
    of a different segment, a future position of its own segment, nor (with
    chunk_start) any slot at/after its chunk start; pads match nothing."""
    pytest.importorskip("hypothesis")
    import hypothesis.strategies as st
    from hypothesis import given, settings
    import jax.numpy as jnp
    from repro.models.attention import segment_mask

    @settings(max_examples=60, deadline=None)
    @given(st.data())
    def check(data):
        n_seg = data.draw(st.integers(1, 4))
        lens = [data.draw(st.integers(1, 6)) for _ in range(n_seg)]
        starts = [data.draw(st.integers(0, 9)) for _ in range(n_seg)]
        window = data.draw(st.sampled_from([0, 3]))
        use_chunk = data.draw(st.booleans())
        q_seg, q_pos, q_cs = [], [], []
        for i, (ln, s0) in enumerate(zip(lens, starts)):
            q_seg += [i] * ln
            q_pos += list(range(s0, s0 + ln))
            q_cs += [s0] * ln
        pad = data.draw(st.integers(0, 3))
        q_seg += [-1] * pad
        q_pos += [1 << 29] * pad
        q_cs += [1 << 29] * pad
        # kv slot stream: random segments/positions (old pages)
        n_kv = data.draw(st.integers(1, 12))
        kv_seg = [data.draw(st.integers(-2, n_seg - 1)) for _ in range(n_kv)]
        kv_pos = [data.draw(st.integers(0, 12)) for _ in range(n_kv)]
        m = np.asarray(segment_mask(
            jnp.asarray([q_seg]), jnp.asarray([q_pos]),
            jnp.asarray([kv_seg]), jnp.asarray([kv_pos]), window=window,
            chunk_start=jnp.asarray([q_cs]) if use_chunk else None))[0]
        for i in range(len(q_seg)):
            for j in range(n_kv):
                if not m[i, j]:
                    continue
                assert q_seg[i] >= 0, "pad token attended something"
                assert kv_seg[j] == q_seg[i], "cross-segment attention"
                if use_chunk:
                    assert kv_pos[j] < q_cs[i], "slot at/after chunk start"
                else:
                    assert kv_pos[j] <= q_pos[i], "future position"
                if window:
                    assert kv_pos[j] > q_pos[i] - window, "outside window"

    check()


# ---------------------------------------------------------- async engine
# EngineConfig.async_scheduling double-buffers the step loop: plan N+1 is
# scheduled (speculative +1 decode per running request) and host-built
# while plan N's dispatch is in flight; sampling/advancing N happens when
# its logits are fetched, and the already-built batch N+1 is reconciled
# (dead segments killed, speculative pages rolled back, decode token ids
# patched) before its own dispatch. Everything observable must be
# BIT-IDENTICAL to the synchronous loop.

@pytest.mark.parametrize("arch", ["granite-3-2b", "h2o-danube-3-4b",
                                  "qwen2-vl-2b", "zamba2-1.2b", "rwkv6-3b",
                                  "whisper-tiny", "dbrx-132b"])
def test_async_matches_sync_greedy(arch):
    """Async greedy outputs equal the synchronous packed engine's, token
    for token, for every model family (attention, swa, vlm, hybrid-mamba2,
    rwkv6, encdec, moe) — including mm/encoder item routing."""
    from repro.core.request import MMItem
    outs = {}
    for async_ in (False, True):
        eng, cfg = make_engine(arch, batching_mode="packed",
                               max_num_batched_tokens=64,
                               async_scheduling=async_)
        for i in range(3):
            kw = {}
            if arch == "whisper-tiny":
                kw["encoder_items"] = (MMItem(0, cfg.encoder_seq,
                                              mm_hash=7 + i),)
            elif arch == "qwen2-vl-2b":
                kw["mm_items"] = (MMItem(2, 6, mm_hash=40 + i),)
            eng.submit(Request(rid=f"r{i}",
                               prompt=[(3 * i + j) % 50
                                       for j in range(12 + i)],
                               sampling=SamplingParams(max_new_tokens=5),
                               **kw))
        eng.run_until_done(max_steps=1000)
        eng.mgr.check_invariants()
        assert len(eng.finished) == 3
        # one-step-delayed sampling must still stamp first/finished steps
        # with the step that SAMPLED, matching the synchronous loop
        outs[async_] = {r.rid: (list(r.output), r.first_token_step,
                                r.finished_step) for r in eng.finished}
    assert outs[False] == outs[True], (arch, outs)


def test_async_matches_sync_padded_layout():
    """Async scheduling composes with the padded (B, T) layout too — the
    layout only changes how the runner flattens the plan."""
    outs = {}
    for async_ in (False, True):
        eng, _ = make_engine(batching_mode="padded",
                             max_num_batched_tokens=64,
                             async_scheduling=async_)
        for i in range(3):
            eng.submit(Request(rid=f"r{i}", prompt=list(range(10 + i)),
                               sampling=SamplingParams(max_new_tokens=4)))
        eng.run_until_done(max_steps=500)
        outs[async_] = {r.rid: list(r.output) for r in eng.finished}
    assert outs[False] == outs[True], outs


def test_async_serial_falls_back_to_sync():
    """serial mode issues two dispatch groups per step; async_scheduling is
    documented to fall back to the synchronous loop there."""
    eng, _ = make_engine(batching_mode="serial", async_scheduling=True)
    assert eng.async_scheduling is False
    eng.submit(Request(rid="x", prompt=list(range(10)),
                       sampling=SamplingParams(max_new_tokens=3)))
    eng.run_until_done(max_steps=200)
    assert len(eng.finished[0].output) == 3


def test_async_eos_spec_rollback():
    """A request that EOSes while its speculative +1 decode page is already
    committed: the dead segment is neutralized in the prepared batch and
    the page popped back (manager rollback), with outputs unchanged.
    tokens_per_page=4 on reduced configs; prompt length 12 puts the EOS'd
    request's speculative +1 exactly across a page boundary."""
    probe, _ = make_engine(batching_mode="packed", async_scheduling=False)
    probe.submit(Request(rid="p", prompt=[j % 50 for j in range(12)],
                         sampling=SamplingParams(max_new_tokens=4)))
    probe.run_until_done(max_steps=200)
    eos = probe.finished[0].output[0]

    outs = {}
    for async_ in (False, True):
        eng, _ = make_engine(batching_mode="packed",
                             async_scheduling=async_)
        eng.submit(Request(rid="x", prompt=[j % 50 for j in range(12)],
                           sampling=SamplingParams(max_new_tokens=8,
                                                   eos_token=eos)))
        eng.run_until_done(max_steps=200)
        eng.mgr.check_invariants()
        outs[async_] = list(eng.finished[0].output)
        if async_:
            assert eng.spec_kills >= 1, "EOS kill path never exercised"
            assert eng.spec_rollback_pages >= 1, \
                "speculative +1 page was never committed/rolled back"
        # dispatch accounting stays truthful through kills: killed slots
        # count as padding waste, never as dispatched tokens
        assert sum(m.batched_tokens for m in eng.metrics) == \
            eng.runner.tokens_dispatched
        assert sum(m.dispatched_slots for m in eng.metrics) == \
            eng.runner.slots_dispatched
    assert outs[False] == outs[True] and outs[True][-1] == eos, outs


def test_async_prefix_cache_hit_restart():
    """Prefix-cache-hit restart mid-run: a finished request's prompt is
    resubmitted while other requests are mid-decode; the hit restores
    state under async double-buffering exactly as under sync."""
    outs = {}
    for async_ in (False, True):
        eng, _ = make_engine(batching_mode="packed",
                             async_scheduling=async_)
        eng.submit(Request(rid="a", prompt=list(range(16)),
                           sampling=SamplingParams(max_new_tokens=3)))
        eng.run_until_done(max_steps=200)           # a finishes, gets cached
        eng.submit(Request(rid="bg", prompt=[7] * 10,
                           sampling=SamplingParams(max_new_tokens=8)))
        for _ in range(3):
            eng.step()                              # bg mid-decode...
        eng.submit(Request(rid="a2", prompt=list(range(16)),
                           sampling=SamplingParams(max_new_tokens=3)))
        eng.run_until_done(max_steps=400)
        assert len(eng.finished) == 3
        assert eng.mgr.prefix_hit_tokens_total > 0
        a, a2 = [next(r for r in eng.finished if r.rid == rid)
                 for rid in ("a", "a2")]
        assert a.output == a2.output, (a.output, a2.output)
        assert a2.seq.prefix_hit_tokens > 0          # the restart really hit
        outs[async_] = {r.rid: list(r.output) for r in eng.finished}
    assert outs[False] == outs[True], outs


# ----------------------------------------------------------- runner layout
def test_packed_plan_layout():
    """The packed plan is one contiguous stream: segments back to back,
    positions continuing each sequence, per-segment last-token indices."""
    eng, _ = make_engine(batching_mode="packed", max_num_batched_tokens=64)
    reqs = []
    for i in range(3):
        r = Request(rid=f"r{i}", prompt=list(range(6 + i)),
                    sampling=SamplingParams(max_new_tokens=2))
        eng.submit(r)
        reqs.append(r)
    plan = eng.scheduler.schedule()
    items = [(s.req, s.num_tokens) for s in plan.scheduled]
    batch, info = eng.runner.build_plan(items, packed=True)
    total = sum(nt for _, nt in items)
    assert info["tokens"] == total and info["slots"] == _tok_bucket(total)
    seg = np.asarray(batch.seg_ids[0])
    pos = np.asarray(batch.positions[0])
    start = np.asarray(batch.seg_start_tok[0])
    last = np.asarray(batch.seg_last_tok)
    off = 0
    for si, (req, nt) in enumerate(items):
        nc = req.seq.num_computed        # schedule() does not advance
        assert (seg[off:off + nt] == si).all()
        assert (pos[off:off + nt] == np.arange(nc, nc + nt)).all()
        assert (start[off:off + nt] == off).all()
        assert last[si] == off + nt - 1
        off += nt
    assert (seg[off:] == -1).all()
