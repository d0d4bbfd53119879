"""Kernel attention on the packed serve path.

Function-level: the block-sparse q-blocked ref (``_prefill_flash`` with
segment metadata) is BITWISE-equal to the dense masked scan — a skipped
KV block's online-softmax update is the identity, so skipping is a pure
compute save. The in-place path (``blocks_attn.attn_paged``: write the
step's K/V, then the Pallas paged kernel in interpret mode on CPU)
matches the gather path's old + fresh merge to bf16 tolerance: it sums
attention in a different order, so equality is numeric, not bitwise.
Cross-segment isolation and fully-masked rows (cross-attn
``enc_lens == 0``) are asserted directly, and so is which shapes take the
in-place path.

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


# --------------------------------------------- in-place paged attention
TPP, LAYERS, LAYER = 4, 2, 1
# segments as (first position, tokens, pages of the segment's table); the
# pages are indices into a shuffled pool, so ids come out of order
CASES = {
    "decode": [(9, 1, [0, 1, 2]), (3, 1, [3]), (14, 1, [4, 5, 6, 7])],
    "mixed": [(0, 6, [0, 1]), (9, 1, [2, 3, 4]), (5, 7, [5, 6, 7])],
    "no_old_pages": [(0, 9, [0, 1, 2]), (6, 1, [3, 4])],
    "prefix_shared": [(9, 3, [0, 1, 2]), (4, 5, [0, 3, 4])],
}


def inplace_case(segs, kvl, d, seed=0, g=2, dm=64):
    """One packed step of one attention layer: params, activations, a
    buffer of random pages, and the step's metadata in both layouts (the
    gather path's page stream with per-page segments and positions, and
    the in-place path's per-segment page runs). The page stream is padded
    to a power of two with -1 ids; token stream pads carry segment -1."""
    from repro.core.layout import buffer_shape
    from repro.core.spec import attention_spec

    rng = np.random.default_rng(seed)
    n_pages = sum(len(p) for _, _, p in segs) + 4
    vp = n_pages + 3
    perm = rng.permutation(vp)
    spec = attention_spec("full_attn", num_layers=LAYERS, kv_heads=kvl,
                          head_dim=d, tokens_per_page=TPP)
    view = (vp, LAYERS, 2, TPP, kvl, d)
    shape = buffer_shape([spec], int(np.prod(view)))[-2:]
    buf = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    t = 32
    tokens = sum(n for _, n, _ in segs)
    seg = np.full((1, t), -1, np.int32)
    pos = np.full((1, t), 1 << 29, np.int32)
    cs = np.full((1, t), 1 << 29, np.int32)
    wr = np.full((1, t), -1, np.int32)
    p_len = 1 << (sum(len(p) for _, _, p in segs) + 2).bit_length()
    table = np.full((1, p_len), -1, np.int32)
    ppos = np.full((1, p_len), 1 << 29, np.int32)
    pseg = np.full((1, p_len), -2, np.int32)
    start = np.zeros(4, np.int32)
    count = np.zeros(4, np.int32)
    off = cur = 0
    for si, (st, n, pages) in enumerate(segs):
        ids = perm[pages]
        seg[0, off:off + n] = si
        pos[0, off:off + n] = np.arange(st, st + n)
        cs[0, off:off + n] = st
        wr[0, off:off + n] = ids[np.arange(st, st + n) // TPP]
        table[0, cur:cur + len(ids)] = ids
        ppos[0, cur:cur + len(ids)] = np.arange(len(ids)) * TPP
        pseg[0, cur:cur + len(ids)] = si
        start[si], count[si] = cur, len(ids)
        off += n
        cur += len(ids)
    assert off == tokens <= t
    w = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.bfloat16)
    params = {"attn_norm": jnp.ones((dm,), jnp.float32),
              "q": w(dm, kvl * g * d), "k": w(dm, kvl * d),
              "v": w(dm, kvl * d), "o": w(kvl * g * d, dm)}
    x = w(1, t, dm)
    meta = {k: jnp.asarray(v) for k, v in dict(
        seg=seg, pos=pos, cs=cs, wr=wr, table=table, ppos=ppos, pseg=pseg,
        start=start, count=count).items()}
    return params, x, buf, view, meta


def run_both(params, x, buf, view, meta, *, kvl, d, g=2, window=0):
    """The gather path (read old pages, attend old + fresh, then write)
    and the in-place path (write, then the paged kernel), each as one
    layer of a packed step under shard_map. Returns both (x, buffer)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.models.tp import single_device_dist

    dist = single_device_dist()
    kw = dict(kv_local=kvl, head_dim=d, positions=meta["pos"],
              rope_theta=1e4, window=window)

    def ref(params, x, buf, meta):
        gathered = BA.attn_gather(buf, view, meta["table"], meta["ppos"],
                                  LAYER, meta["pseg"])
        out, k, v = BA.attn_compute(
            params, x, gathered, dist, seq_lens=None, seg_ids=meta["seg"],
            chunk_start=meta["cs"], **kw)
        return out, BA.attn_write(buf, view, LAYER, meta["wr"],
                                  meta["pos"], k, v)

    def in_place(params, x, buf, meta):
        work = BA.paged_work(view, g, meta["seg"], meta["pos"],
                             meta["start"], meta["count"], window=window)
        return BA.attn_paged(params, x, buf, view, dist, layer=LAYER,
                             write_eids=meta["wr"],
                             page_ids=meta["table"][0], work=work, **kw)

    outs = []
    for fn in (ref, in_place):
        sm = jax.shard_map(fn, mesh=dist.mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)
        outs.append(jax.jit(sm)(params, x, buf, meta))
    return outs


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("case", sorted(CASES) + ["window"])
def test_inplace_matches_gather_path(case, d):
    """Write-first in-place attention equals the gather path's old + fresh
    merge: the same buffer after the layer, bit for bit, and the same
    layer output to bf16 rounding, for every real token. Covers decode
    only, mixed prefill and decode, a segment with no old pages, page ids
    shared across segments (a prefix hit), pad pages past the live
    stream, out-of-order page ids, and a sliding window (6 slots over
    4-slot pages), at head_dim 64 (two heads a row) and 128."""
    kvl = 2
    window = 6 if case == "window" else 0
    segs = CASES["mixed" if case == "window" else case]
    params, x, buf, view, meta = inplace_case(segs, kvl, d)
    (x_ref, buf_ref), (x_new, buf_new) = run_both(
        params, x, buf, view, meta, kvl=kvl, d=d, window=window)
    np.testing.assert_array_equal(np.asarray(buf_ref, np.float32),
                                  np.asarray(buf_new, np.float32))
    real = np.asarray(meta["seg"][0]) >= 0
    a = np.asarray(x_ref, np.float32)[0, real]
    b = np.asarray(x_new, np.float32)[0, real]
    assert np.abs(a - b).max() <= 2 ** -7 * np.abs(a).max(), \
        np.abs(a - b).max()


def test_inplace_no_cross_segment_leak():
    """Scrambling one segment's pages leaves every other segment's rows
    bitwise-unchanged: the kernel reads only a segment's own page run."""
    params, x, buf, view, meta = inplace_case(CASES["mixed"], 2, 64)
    base = run_both(params, x, buf, view, meta, kvl=2, d=64)[1][0]
    # segment 1's pages: its run in the flat page stream
    s, n = int(meta["start"][1]), int(meta["count"][1])
    ids = np.asarray(meta["table"][0, s:s + n])
    rows = np.asarray(buf, np.float32).reshape(view[0] * LAYERS, -1, 128)
    rows[ids * LAYERS + LAYER] = 100.0
    buf2 = jnp.asarray(rows.reshape(buf.shape), jnp.bfloat16)
    pert = run_both(params, x, buf2, view, meta, kvl=2, d=64)[1][0]
    seg = np.asarray(meta["seg"][0])
    keep = np.isin(seg, [0, 2])
    assert (np.asarray(base)[0, keep] == np.asarray(pert)[0, keep]).all()
    assert not (np.asarray(base)[0, seg == 1]
                == np.asarray(pert)[0, seg == 1]).all()


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
WIDE = ["granite-3-2b+wide", "h2o-danube-3-4b+wide", "qwen2-vl-2b+wide"]


@pytest.mark.parametrize("arch", ARCHS7 + WIDE)
def test_kernel_matches_ref_greedy(arch):
    """attention_impl="kernel" reproduces the ref path's greedy outputs
    for every archetype, including mm/encoder item routing (vlm mrope +
    whisper cross-attn). The "+wide" dense archs (head_dim 64) attend in
    place, sliding-window layers included (h2o-danube); the reduced()
    widths (head_dim 16) keep the ref path. The two impls differ by bf16
    reduction order, so the comparison is fork-aware
    (conftest.assert_greedy_equiv); when no request forks, first-token and
    finish step stamps must match too."""
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
        assert eng.runner.in_place == (impl == "kernel" and arch in WIDE)
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


# ------------------------------------------------------ path selection
def _attends_in_place(arch=None, cfg=None, tp=1, sp=False, impl="kernel",
                      lanes=128):
    from jax.sharding import AbstractMesh

    from repro.configs import ARCHS
    from repro.models.registry import build_model
    from repro.models.tp import Dist

    mesh = AbstractMesh((1, tp), ("data", "model"))
    model = build_model(cfg or ARCHS[arch], Dist(mesh=mesh, sp=sp))
    return model.attends_in_place(impl, lanes)


def test_inplace_path_selection():
    """The in-place path is taken from what the model can observe: the
    kernel impl, one shard of each KV head, no sequence parallelism, whole
    128-lane K/V rows, head_dim 64 or 128, and a dense decoder. Every
    other shape keeps the gather + ref path."""
    import dataclasses

    from repro.configs import ARCHS, reduced

    assert _attends_in_place("internlm2-1.8b")             # head_dim 128
    assert _attends_in_place("granite-3-2b")               # head_dim 64
    assert not _attends_in_place("internlm2-1.8b", impl="ref")
    assert not _attends_in_place("h2o-danube-3-4b")        # head_dim 120
    assert not _attends_in_place(cfg=reduced(ARCHS["granite-3-2b"]))  # 16
    # kv_groups: 1 KV head over a model axis of 2 -> replicated KV pages
    one_kv = dataclasses.replace(ARCHS["granite-3-2b"], num_kv_heads=1)
    assert not _attends_in_place(cfg=one_kv, tp=2)
    assert not _attends_in_place("granite-3-2b", sp=True)
    assert not _attends_in_place("granite-3-2b", lanes=64)
    for arch in ("zamba2-1.2b", "whisper-tiny", "rwkv6-3b"):
        assert not _attends_in_place(arch)   # state / cross-attn / none


def test_default_impl_follows_backend(monkeypatch):
    """``EngineConfig.attention_impl=None`` resolves to "kernel" on a TPU
    and "ref" elsewhere: the CPU suite keeps the gather path unless a test
    asks for the kernel."""
    import jax

    from repro.serving import Engine, EngineConfig
    from conftest import get_model

    model, _, params = get_model("granite-3-2b+wide")
    eng = Engine(model, EngineConfig(kv_pool_bytes=8 << 20), params=params)
    assert eng.cfg.attention_impl == "ref" and not eng.runner.in_place
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = Engine(model, EngineConfig(kv_pool_bytes=8 << 20), params=params)
    assert eng.cfg.attention_impl == "kernel" and eng.runner.in_place


@pytest.mark.parametrize("arch", ["granite-3-2b+wide", "h2o-danube-3-4b+wide"])
def test_kv_pages_read(arch):
    """``StepMetrics.kv_pages_read`` is the host's count of the page-layers
    the in-place kernel reads: per step it equals the device work plan's
    pages (``plan_work``) times the layers of each KV type, sliding-window
    layers starting at the first page their window reaches; the per-step
    values sum to the runner's total, and the gather path counts 0."""
    import jax

    from repro.models import blocks_attn as BA2

    eng, cfg = make_engine(arch, batching_mode="packed",
                           max_num_batched_tokens=64,
                           attention_impl="kernel")
    for i in range(3):
        eng.submit(Request(rid=f"r{i}", prompt=[(5 * i + j) % 50
                                                for j in range(20 + 7 * i)],
                           sampling=SamplingParams(max_new_tokens=6)))
    runner, model = eng.runner, eng.model
    checked = 0
    prepare = runner.prepare

    def checking_prepare(items, **kw):
        nonlocal checked
        prep = prepare(items, **kw)
        a = prep.arrs
        want = 0
        for spec in model.kv_specs():
            view = model._layer_views(runner.buffer)[spec.name]
            work = BA2.paged_work(
                view, model.ri["q_local"] // model.ri["kv_local"],
                jnp.asarray(a["seg_ids"]), jnp.asarray(a["positions"]),
                jnp.asarray(a["page_start"][spec.name][0, 0, 0]),
                jnp.asarray(a["page_count"][spec.name][0, 0, 0]),
                window=spec.sliding_window if spec.kind == "swa" else 0)
            want += spec.num_layers * int(jax.device_get(
                jnp.sum(work["p_count"])))
        assert int(prep.info["kv_pages_read"].sum()) == want > 0
        checked += 1
        return prep

    runner.prepare = checking_prepare
    eng.run_until_done(max_steps=500)
    assert checked == len(eng.metrics) > 3
    assert all(m.kv_pages_read > 0 and m.kv_blocks_scanned == 0
               for m in eng.metrics)
    assert sum(m.kv_pages_read for m in eng.metrics) == runner.kv_pages_read
    ref, _ = make_engine(arch, batching_mode="packed", attention_impl="ref")
    ref.submit(Request(rid="r", prompt=list(range(10)),
                       sampling=SamplingParams(max_new_tokens=3)))
    ref.run_until_done(max_steps=200)
    assert ref.runner.kv_pages_read == 0
    assert all(m.kv_pages_read == 0 for m in ref.metrics)
