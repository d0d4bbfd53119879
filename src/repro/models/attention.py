"""Attention math (local, inside shard_map): flash-style chunked attention
for train/prefill, paged gather attention for decode, partial-softmax
combining for sequence-parallel long-context decode.

Local GQA convention: q is (B, T, KVL, G, D) — KVL local kv heads, G padded
q-heads-per-kv-head on this device; k/v are (B, S, KVL, D).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def group_q(q, kv_local: int):
    """(B, T, q_local, D) -> (B, T, KVL, G, D)."""
    b, t, ql, d = q.shape
    assert ql % kv_local == 0
    return q.reshape(b, t, kv_local, ql // kv_local, d)


def segment_mask(q_seg, q_pos, kv_seg, kv_pos, *, window=0, chunk_start=None):
    """Attention mask for a PACKED token stream: several independent
    segments (sequences) share one batch row, identified by per-token /
    per-slot segment ids. Token i may attend slot j iff both belong to the
    same segment and j is not in i's future.

    q_seg: (B, T); kv_seg: (B, S); q_pos: (B, T); kv_pos: (B, S) — absolute
    positions within each token's own sequence. Padded q tokens carry seg id
    -1 and padded kv slots -2, so pads never match anything (including each
    other). chunk_start: (B, T) per-token start position of the token's
    current chunk — when given, slots are valid iff kv_pos < chunk_start
    (strictly before the chunk: the chunk's own slots come via the fresh-KV
    path); when None the in-chunk causal rule kv_pos <= q_pos applies.
    window > 0 adds the sliding-window bound kv_pos > q_pos - window.
    Returns (B, T, S) bool."""
    mask = q_seg[:, :, None] == kv_seg[:, None, :]
    if chunk_start is not None:
        mask &= kv_pos[:, None, :] < chunk_start[:, :, None]
    else:
        mask &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if window:
        mask &= kv_pos[:, None, :] > q_pos[:, :, None] - window
    return mask


# --------------------------------------------------------------------- flash
def flash_attention_partials(
    q, k, v, *, causal: bool = True, window: int = 0,
    q_offset=0, kv_len: Optional[jax.Array] = None,
    block: int = 512,
):
    """Chunked online-softmax attention (pure jnp; Pallas kernel on TPU).
    Returns un-normalized partials (acc (B,KVL,G,T,D), m, l).

    q: (B, T, KVL, G, D); k, v: (B, S, KVL, D).
    q position of row i = q_offset + i; kv position of col j = j.
    window > 0 = sliding window (attend to positions > qpos - window).
    kv_len: (B,) valid kv length mask (padding beyond).
    """
    b, t, kvl, g, d = q.shape
    s = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    qf = (q * scale).astype(q.dtype)

    nblk = -(-s // block)
    pad = nblk * block - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, block, kvl, d)
    vb = v.reshape(b, nblk, block, kvl, d)

    q_pos = q_offset + jnp.arange(t)                      # (T,)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, j0 = blk                              # (B, blk, KVL, D)
        kv_pos = j0 + jnp.arange(block)                   # (blk,)
        logit = jnp.einsum("btkgd,bjkd->bkgtj", qf, kblk,
                           preferred_element_type=jnp.float32)
        mask = jnp.ones((t, block), dtype=bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        mask = jnp.broadcast_to(mask, (b, kvl, g, t, block))
        if kv_len is not None:
            mask &= (kv_pos[None, :] < kv_len[:, None])[:, None, None, None, :]
        logit = jnp.where(mask, logit, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logit, axis=-1))
        p = jnp.exp(logit - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgtj,bjkd->bkgtd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvl, g, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvl, g, t), jnp.float32)
    a0 = jnp.zeros((b, kvl, g, t, d), jnp.float32)
    blk_starts = jnp.arange(nblk) * block
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), blk_starts),
    )
    return acc, m, l


def flash_attention(q, k, v, **kw):
    """Normalized flash attention -> (B, T, KVL, G, D) in q.dtype."""
    acc, m, l = flash_attention_partials(q, k, v, **kw)
    return finalize_softmax(acc, l).astype(q.dtype)


def merge_partials(o1, m1, l1, o2, m2, l2):
    """Merge two partial-softmax results (local, no collective)."""
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    out = o1 * c1[..., None] + o2 * c2[..., None]
    return out, m, l1 * c1 + l2 * c2


# ------------------------------------------------------------------- decode
def attend_tokens(q, k, v, mask):
    """Materialized attention for short T (decode T=1).

    q: (B, T, KVL, G, D); k/v: (B, S, KVL, D); mask: (B, T, S) bool.
    Returns (out, m, l) for partial-softmax combining."""
    d = q.shape[-1]
    scale = 1.0 / (d ** 0.5)
    logit = jnp.einsum("btkgd,bskd->bkgts", q * scale, k,
                       preferred_element_type=jnp.float32)
    logit = jnp.where(mask[:, None, None], logit, NEG_INF)
    m = jnp.max(logit, axis=-1)
    p = jnp.exp(logit - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bkgts,bskd->bkgtd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out, m, l


def finalize_softmax(out, l):
    out = out / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 3, 1)                        # (B, T, KVL, G, D)


def combine_partials(out, m, l, axis_name: str, groups=None):
    """Flash-decoding combine across an axis (sequence-parallel decode /
    replica-group KV split). ``groups`` restricts the reduction to
    axis_index_groups (e.g. KV-replica subgroups of the tp axis).
    Returns (out, m, l) rescaled to the group max."""
    gmax = jax.lax.pmax(m, axis_name, axis_index_groups=groups)
    corr = jnp.exp(m - gmax)
    out = jax.lax.psum(out * corr[..., None], axis_name,
                       axis_index_groups=groups)
    l = jax.lax.psum(l * corr, axis_name, axis_index_groups=groups)
    return out, gmax, l


def replica_groups(kv_tp: int, repl: int):
    """tp-axis index groups [[kg*repl .. kg*repl+repl-1] ...] — the KV
    replica sets that jointly hold one kv-head group's pages."""
    return [[kg * repl + r for r in range(repl)] for kg in range(kv_tp)]


# -------------------------------------------------------------- paged cache
# Write strategy for the unified buffer:
#   "scatter"  — gather-scatter (.at[].set). In-place on TPU (donated buffer
#                scatter aliases); XLA:CPU inserts 2 pool copies.
#   "dus"      — flat dynamic_update_slice writes (loop over seqs / pages).
#                Proven 0-copy on CPU (see /tmp experiments + EXPERIMENTS.md);
#                used by the dry-run so memory_analysis reflects the TPU
#                in-place behaviour. Requires page-aligned prefill chunks.
_WRITE_MODE = ["scatter"]


def set_write_mode(mode: str):
    assert mode in ("scatter", "dus")
    _WRITE_MODE[0] = mode


def view_offset(view_shape, eid, layer, sel, slot):
    """Flat-buffer offset of (eid, layer, sel, slot, 0, 0) in an attention
    view (VP, L, 2, TPP, KVL, D). int64 math — pools exceed 2^31 units
    (requires jax_enable_x64 in the dry-run process)."""
    vp, nl, _, tpp, kvl, d = view_shape
    eid = eid.astype(jnp.int64) if hasattr(eid, "astype") else eid
    return ((((eid * nl + layer) * 2 + sel) * tpp) + slot) * kvl * d


def gather_pages(buf, view_shape, tables, layer):
    """buf: the unified buffer as ``(rows, lanes)`` (``layout.as_rows``);
    view_shape: (VP, L, 2, TPP, KVL, D); tables: (B, P) int32 (entries < 0
    are invalid pads/frees). Returns k, v: (B, P*TPP, KVL, D).

    One gather of whole (page, layer) blocks, indexed ``page * L + layer``
    on a row view: only this layer's bytes of the listed pages move, and
    the view is a free reshape of the rows (no relayout of the pool).

    Invalid entries are ZEROED, not merely masked downstream: the clamped
    gather would otherwise read arbitrary units of the unified buffer —
    including other types' pages, e.g. fp32 recurrent state bitcast into
    bf16 pairs, whose halves can decode as NaN. A NaN V poisons the
    partial-softmax merge even for fully-masked rows (exp(0)*NaN, and
    NaN*0 == NaN in the rescale). VALID pages are safe without an isnan
    scrub because the runner zero-initialises every freshly allocated page
    (ModelRunner.zero_pages) before its first dispatch."""
    vp, nl, _, tpp, kvl, d = view_shape
    lanes = buf.shape[-1]
    blk = 2 * tpp * kvl * d // lanes                  # rows per page-layer
    rows = buf.reshape(vp * nl, blk, lanes)
    pages = jnp.take(rows, jnp.maximum(tables, 0) * nl + layer, axis=0)
    pages = jnp.where((tables >= 0)[:, :, None, None], pages, 0)
    b, p = tables.shape
    pages = pages.reshape(b, p, 2, tpp, kvl, d)
    k = pages[:, :, 0]
    v = pages[:, :, 1]
    return k.reshape(b, p * tpp, kvl, d), v.reshape(b, p * tpp, kvl, d)


def write_token_kv(buf, view_shape, layer, eids, slots, k_new, v_new):
    """Write T new tokens per sequence into their pages.

    buf: the unified buffer as ``(rows, lanes)`` (the scan carry);
    view_shape: (VP, L, 2, TPP, KVL, D); eids: (B, T) exec page id per new
    token (<0 = drop, e.g. non-owner shard in the replica-split); slots:
    (B, T) slot within page; k_new/v_new: (B, T, KVL, D). Each token's K
    and V rows are scattered as whole buffer rows. Returns the updated
    buf."""
    if _WRITE_MODE[0] == "dus":
        return _write_token_kv_dus(buf, view_shape, layer, eids, slots,
                                   k_new, v_new)
    vp, nl, _, tpp, kvl, d = view_shape
    lanes = buf.shape[-1]
    tr = kvl * d // lanes                             # rows per token
    b, t = eids.shape
    k_row = ((jnp.maximum(eids, 0) * nl + layer) * 2 * tpp + slots) * tr
    k_row = jnp.where(eids >= 0, k_row, buf.shape[0])  # OOB -> dropped
    v_row = jnp.where(eids >= 0, k_row + tpp * tr, buf.shape[0])
    sub = jnp.arange(tr)
    idx = jnp.stack([k_row, v_row], axis=-1)[..., None] + sub  # (B,T,2,tr)
    upd = jnp.stack([k_new, v_new], axis=2).astype(buf.dtype)
    return buf.at[idx.reshape(-1)].set(
        upd.reshape(-1, lanes), mode="drop", unique_indices=False)


def _write_token_kv_dus(buf, view_shape, layer, eids, slots, k_new, v_new):
    """Flat dynamic_update_slice writes (0-copy on every backend).

    Drop semantics (<0 eids) redirect the write into the SCRATCH page — the
    final small page of the buffer, reserved by the runner/dry-run sizing —
    so no read-modify-write is needed (reads before in-place writes force
    pool copies in XLA buffer assignment).

    Decode (T==1): one dus row per sequence. Prefill (T>1): fori_loop over
    (seq, page) writing whole page-layer slices — requires the chunk to start
    page-aligned (guaranteed by the runner in dus mode)."""
    shape = buf.shape
    buf = buf.reshape(-1)
    vp, nl, _, tpp, kvl, d = view_shape
    b, t = eids.shape
    row = kvl * d
    total = buf.shape[0]
    kf = k_new.astype(buf.dtype)
    vf = v_new.astype(buf.dtype)
    if t == 1:
        for bi in range(b):
            eid = eids[bi, 0]
            slot = slots[bi, 0]
            for sel, data in ((0, kf), (1, vf)):
                off = view_offset(view_shape, jnp.maximum(eid, 0), layer,
                                  sel, slot)
                off = jnp.where(eid >= 0, off, total - row)   # -> scratch
                buf = jax.lax.dynamic_update_slice(
                    buf, data[bi, 0].reshape(row), (off,))
        return buf.reshape(shape)
    # prefill: page-granular writes
    npg = -(-t // tpp)
    pad = npg * tpp - t
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        eids = jnp.pad(eids, ((0, 0), (0, pad)), constant_values=-1)
    kp = kf.reshape(b, npg, tpp * row)
    vp_data = vf.reshape(b, npg, tpp * row)
    page_eids = eids[:, ::tpp]                                 # (B, npg)
    page_sz = tpp * row

    def body(j, buf):
        bi = j // npg
        pg = j % npg
        eid = page_eids[bi, pg]
        for sel, data in ((0, kp), (1, vp_data)):
            off = view_offset(view_shape, jnp.maximum(eid, 0), layer, sel, 0)
            off = jnp.where(eid >= 0, off, total - page_sz)
            buf = jax.lax.dynamic_update_slice(
                buf, jax.lax.dynamic_slice(data, (bi, pg, 0),
                                           (1, 1, page_sz)).reshape(page_sz),
                (off,))
        return buf

    return jax.lax.fori_loop(0, b * npg, body, buf).reshape(shape)


def bf16_pair_to_f32(x):
    """(..., 2U) bf16 -> (..., U) f32 bitcast (exact fp32 state storage
    inside the bf16 unified buffer; 1 fp32 state unit = 2 buffer units)."""
    assert x.dtype == jnp.bfloat16 and x.shape[-1] % 2 == 0
    return jax.lax.bitcast_convert_type(
        x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2), jnp.float32)


def f32_to_bf16_pair(x):
    assert x.dtype == jnp.float32
    y = jax.lax.bitcast_convert_type(x, jnp.bfloat16)  # (..., U, 2)
    return y.reshape(*x.shape[:-1], x.shape[-1] * 2)


def read_state(view, layer, eids):
    """State view: (VP, L, 2U) bf16. eids: (B,). Returns (B, U) f32.
    Invalid (< 0, padded-row) eids read as zero state — the clamped gather
    would otherwise hand NaN-decoding foreign bytes to the recurrent scan."""
    lview = jax.lax.dynamic_index_in_dim(view, layer, axis=1, keepdims=False)
    st = jnp.take(lview, jnp.maximum(eids, 0), axis=0)        # (B, 2U)
    st = jnp.where((eids >= 0)[:, None], st, 0)
    return bf16_pair_to_f32(st)


def write_state(buf, view_shape, layer, eids, state):
    """state: (B, U) f32, stored as bit-exact fp32 pairs.
    buf: flat unified buffer; view_shape: (VP, L, 2U)."""
    vp, nl, u2 = view_shape
    data = f32_to_bf16_pair(state.astype(jnp.float32)).astype(buf.dtype)
    if _WRITE_MODE[0] == "scatter":
        view = buf.reshape(view_shape)
        b = eids.shape[0]
        layer_f = jnp.full((b,), layer, jnp.int32)
        eids_s = jnp.where(eids < 0, vp, eids)
        view = view.at[eids_s, layer_f].set(
            data, mode="drop", unique_indices=False)
        return view.reshape(buf.shape)
    shape = buf.shape
    buf = buf.reshape(-1)
    total = buf.shape[0]
    for bi in range(eids.shape[0]):
        eid = eids[bi]
        off = (jnp.maximum(eid, 0).astype(jnp.int64) * nl + layer) * u2
        off = jnp.where(eid >= 0, off, total - u2)            # -> scratch
        buf = jax.lax.dynamic_update_slice(buf, data[bi], (off,))
    return buf.reshape(shape)
