"""Segment-block-sparse kernel attention on the packed serve path.

Function-level: the block-sparse q-blocked ref (``_prefill_flash`` with
segment metadata) is BITWISE-equal to the dense masked scan — a skipped
KV block's online-softmax update is the identity, so skipping is a pure
compute save. The one-call Pallas path (``packed_kernel_attention``,
interpret mode on CPU) matches the ref two-part merge to bf16 tolerance:
it sums attention in a different order, so equality is numeric, not
bitwise. Cross-segment isolation and fully-masked rows (cross-attn
``enc_lens == 0``) are asserted directly.

Engine-level: greedy outputs under ``attention_impl="kernel"`` equal the
"ref" path token for token across every model archetype. The two impls
differ by bf16 reduction order, so token equality rides on the engine's
tie-banded greedy argmax (TIE_EPS in serving.engine).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import assert_greedy_equiv, make_engine
from repro.core.request import MMItem
from repro.models import attention as A
from repro.models import blocks_attn as BA
from repro.serving import Request, SamplingParams

ARCHS7 = ["granite-3-2b", "h2o-danube-3-4b", "qwen2-vl-2b", "zamba2-1.2b",
          "rwkv6-3b", "whisper-tiny", "dbrx-132b"]


# ---------------------------------------------------------- packed fixture
def packed_case(seed=0, t=20, s=96, kvl=2, g=2, d=16):
    """One hand-built packed step: seg0 = 8 prefill tokens from scratch,
    seg1 = 1 decode token over 12 old slots, seg2 = a 10-token chunk over
    4 old slots, 1 pad token; old pages segment-contiguous, pad slots
    seg -2."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((1, t, kvl, g, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, s, kvl, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, s, kvl, d)), jnp.bfloat16)
    kf = jnp.asarray(rng.standard_normal((1, t, kvl, d)), jnp.bfloat16)
    vf = jnp.asarray(rng.standard_normal((1, t, kvl, d)), jnp.bfloat16)
    seg = np.full((1, t), -1, np.int32)
    pos = np.full((1, t), 1 << 29, np.int32)
    cs = np.full((1, t), 1 << 29, np.int32)
    seg[0, :8] = 0; pos[0, :8] = np.arange(8); cs[0, :8] = 0
    seg[0, 8] = 1; pos[0, 8] = 12; cs[0, 8] = 12
    seg[0, 9:19] = 2; pos[0, 9:19] = np.arange(4, 14); cs[0, 9:19] = 4
    sseg = np.full((1, s), -2, np.int32)
    spos = np.full((1, s), np.iinfo(np.int32).max // 2, np.int32)
    sseg[0, :12] = 1; spos[0, :12] = np.arange(12)
    sseg[0, 12:16] = 2; spos[0, 12:16] = np.arange(4)
    return (q, k, v, kf, vf, *map(jnp.asarray, (seg, pos, cs, sseg, spos)))


def ref_packed(q, k, v, kf, vf, seg, pos, cs, sseg, spos, window=0):
    """The ref path's math: dense masked old-slot scan merged with the
    fresh-part segment attention, finalized."""
    m_old = (np.asarray(spos)[:, None, :] < np.asarray(cs)[:, :, None]) \
        & (np.asarray(sseg)[:, None, :] == np.asarray(seg)[:, :, None])
    if window:
        m_old &= (np.asarray(spos)[:, None, :]
                  > np.asarray(pos)[:, :, None] - window)
    oo, mo, lo = A.attend_tokens(q, k, v, jnp.asarray(m_old))
    m_f = A.segment_mask(seg, pos, seg, pos)
    if window:
        m_f = m_f & (pos[:, None, :] > pos[:, :, None] - window)
    of, mf, lf = A.attend_tokens(q, kf, vf, m_f)
    om, mm, lm = A.merge_partials(oo, mo, lo, of, mf, lf)
    return A.finalize_softmax(om, lm), m_old


# --------------------------------------------------------- function level
def test_sparse_ref_bitwise_equals_dense():
    """Block-skipping in the q-blocked ref is bitwise-exact for every row
    with visible old KV: a skipped block's online-softmax update is the
    identity (corr = exp(0) = 1, pexp underflows to exact 0). Rows with NO
    visible old slots differ pre-merge by design — the dense scan
    degenerates to a uniform average (m pinned at NEG_INF makes every pexp
    exp(0) = 1) while the sparse path returns the identity partial
    (l = 0) — and both are erased exactly by the fresh-part merge
    (corr_old = exp(NEG_INF - m_fresh) = 0), so the served output is
    unchanged either way."""
    q, k, v, kf, vf, seg, pos, cs, sseg, spos = packed_case()
    mask = (spos[:, None, :] < cs[:, :, None]) \
        & (sseg[:, None, :] == seg[:, :, None])
    o_d, m_d, l_d = A.attend_tokens(q, k, v, mask)
    o_s, m_s, l_s = BA._prefill_flash(q, k, v, spos, pos, window=0,
                                      chunk_start=cs, q_seg=seg, kv_seg=sseg)
    dense = np.asarray(A.finalize_softmax(o_d, l_d), np.float32)
    sparse = np.asarray(A.finalize_softmax(o_s, l_s), np.float32)
    rows = np.asarray(seg[0] >= 0) & np.asarray(mask[0].any(-1))
    assert rows.any()
    assert (dense[0, rows] == sparse[0, rows]).all()
    # no-old-KV rows: the sparse partial is the merge identity
    nokv = np.asarray(seg[0] >= 0) & ~np.asarray(mask[0].any(-1))
    assert nokv.any()
    assert (np.asarray(l_s, np.float32)[..., nokv] == 0.0).all()
    # ...and after merging the fresh part, ALL real rows agree bitwise
    m_f = A.segment_mask(seg, pos, seg, pos)
    of, mf, lf = A.attend_tokens(q, kf, vf, m_f)
    md = np.asarray(A.finalize_softmax(*_merge_ol(o_d, m_d, l_d, of, mf, lf)),
                    np.float32)
    msp = np.asarray(A.finalize_softmax(*_merge_ol(o_s, m_s, l_s, of, mf, lf)),
                     np.float32)
    real = np.asarray(seg[0]) >= 0
    assert (md[0, real] == msp[0, real]).all()


def _merge_ol(o1, m1, l1, o2, m2, l2):
    o, m, l = A.merge_partials(o1, m1, l1, o2, m2, l2)
    return o, l


@pytest.mark.parametrize("window", [0, 6])
def test_packed_kernel_matches_ref_merge(window):
    """One-call kernel (old slots ++ fresh concat) vs the ref two-part
    merge, causal and sliding-window, to bf16 tolerance."""
    q, k, v, kf, vf, seg, pos, cs, sseg, spos = packed_case()
    ref, _ = ref_packed(q, k, v, kf, vf, seg, pos, cs, sseg, spos,
                        window=window)
    kern = BA.packed_kernel_attention(q, k, v, spos, sseg, kf, vf, pos,
                                      seg, cs, window=window)
    rows = np.asarray(seg[0]) >= 0
    diff = np.abs(np.asarray(ref, np.float32)[0, rows]
                  - np.asarray(kern, np.float32)[0, rows])
    assert diff.max() < 2e-2, diff.max()   # bf16 value scale ~1e-2 ulp


def test_kernel_no_cross_segment_leak():
    """Scrambling another segment's old KV and fresh tokens leaves a
    segment's rows bitwise-unchanged — the kernel's seg-equality mask
    isolates segments exactly."""
    q, k, v, kf, vf, seg, pos, cs, sseg, spos = packed_case()
    base = np.asarray(BA.packed_kernel_attention(
        q, k, v, spos, sseg, kf, vf, pos, seg, cs), np.float32)
    # scramble segment 1's old slots (slots 0:12) and its fresh token (8)
    k2 = k.at[:, :12].set(jnp.asarray(
        np.random.default_rng(9).standard_normal((1, 12, *k.shape[2:])),
        jnp.bfloat16))
    kf2 = kf.at[:, 8].set(100.0)
    vf2 = vf.at[:, 8].set(-100.0)
    pert = np.asarray(BA.packed_kernel_attention(
        q, k2, v, spos, sseg, kf2, vf2, pos, seg, cs), np.float32)
    rows02 = np.isin(np.asarray(seg[0]), [0, 2])
    assert (base[0, rows02] == pert[0, rows02]).all()
    assert not (base[0, np.asarray(seg[0]) == 1]
                == pert[0, np.asarray(seg[0]) == 1]).all()


def test_cross_attn_kernel_masked_rows_zero():
    """Cross-attn kernel: rows whose segment has enc_lens == 0 come back
    exactly zero (matching the ref path's explicit zero guard), other
    rows match the ref masked softmax."""
    rng = np.random.default_rng(3)
    t, s, kvl, g, d = 12, 32, 2, 2, 16
    q = jnp.asarray(rng.standard_normal((1, t, kvl, g, d)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((1, s, kvl, d)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((1, s, kvl, d)), jnp.bfloat16)
    seg = np.full((1, t), -1, np.int32)
    seg[0, :6] = 0; seg[0, 6:10] = 1
    enc = np.zeros((1, t), np.int32)
    enc[0, :6] = 24          # seg0 sees 24 encoder slots
    # seg1: enc_lens 0 -> fully masked rows
    sseg = np.full((1, s), -2, np.int32)
    spos = np.full((1, s), np.iinfo(np.int32).max // 2, np.int32)
    sseg[0, :24] = 0; spos[0, :24] = np.arange(24)
    seg, enc, sseg, spos = map(jnp.asarray, (seg, enc, sseg, spos))
    kern = np.asarray(BA.packed_cross_attn_kernel(
        q, kc, vc, spos, sseg, seg, enc), np.float32)
    mask = (sseg[:, None, :] == seg[:, :, None]) \
        & (spos[:, None, :] < enc[:, :, None])
    o, m, l = A.attend_tokens(q, kc, vc, mask)
    ref = np.asarray(A.finalize_softmax(o, l), np.float32)
    assert (kern[0, 6:10] == 0.0).all()
    diff = np.abs(kern[0, :6] - ref[0, :6])
    assert diff.max() < 2e-2, diff.max()


def test_sparse_blocks_sizing():
    """Block sizing stays within kernel-friendly pow2 bounds and shrinks
    with the problem so small packed steps still split into blocks; the
    minima are the TPU tile (8 query rows x 128 KV lanes)."""
    assert BA.sparse_blocks(16, 64) == (8, 128)
    qb, kb = BA.sparse_blocks(128, 2048)
    assert qb == 32 and kb == 128
    qb, kb = BA.sparse_blocks(10_000, 100_000)
    assert qb == BA.Q_BLOCK and kb == BA.KV_BLOCK


# ----------------------------------------------------------- engine level
@pytest.mark.parametrize("arch", ARCHS7 + ["granite-3-2b+wide"])
def test_kernel_matches_ref_greedy(arch):
    """attention_impl="kernel" reproduces the ref path's greedy outputs
    for every archetype, including mm/encoder item routing (vlm mrope +
    whisper cross-attn). The two impls differ by bf16 reduction order, so
    the comparison is fork-aware (conftest.assert_greedy_equiv); when no
    request forks, first-token and finish step stamps must match too."""
    engs = {}
    for impl in ("ref", "kernel"):
        eng, cfg = make_engine(arch, batching_mode="packed",
                               max_num_batched_tokens=64,
                               attention_impl=impl,
                               record_sample_logits=True)
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
        engs[impl] = eng
    forked = assert_greedy_equiv(engs["ref"], engs["kernel"], label=arch)
    if not forked:
        stamps = {impl: {r.rid: (r.first_token_step, r.finished_step)
                         for r in engs[impl].finished} for impl in engs}
        assert stamps["ref"] == stamps["kernel"], (arch, stamps)


def test_kernel_async_composes():
    """Kernel impl under the async double-buffered loop still equals the
    synchronous kernel run bit for bit (async reorders host work only)."""
    outs = {}
    for async_ in (False, True):
        eng, _ = make_engine(batching_mode="packed",
                             max_num_batched_tokens=64,
                             attention_impl="kernel",
                             async_scheduling=async_)
        for i in range(3):
            eng.submit(Request(rid=f"r{i}",
                               prompt=[(3 * i + j) % 50
                                       for j in range(12 + i)],
                               sampling=SamplingParams(max_new_tokens=4)))
        eng.run_until_done(max_steps=1000)
        outs[async_] = {r.rid: list(r.output) for r in eng.finished}
    assert outs[False] == outs[True]
