"""Transformer blocks (attention / MLP / MoE) — local code inside shard_map.

All functions take *local* param slices (leading tp dim already consumed by
shard_map's in_specs and squeezed by the caller) and replicated activations
(B, T, d); tensor-parallel reductions are explicit ``psum`` over the tp axis.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.flash_attention.kernel import (block_intervals,
                                              flash_attention_varlen_tpu)
from ..kernels.paged_attention import kernel as PK
from . import attention as A
from .common import dense, rms_norm
from .rotary import apply_mrope, apply_rope
from .tp import Dist, psum_tp

# Block-size caps for the segment-block-sparse packed attention schedule
# (MXU-friendly at scale; sparse_blocks scales them down for small streams).
Q_BLOCK = 128
KV_BLOCK = 512


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


def sparse_blocks(t: int, s: int) -> tuple:
    """(q_block, kv_block) for the segment-block-sparse packed schedule.

    Page streams are segment-contiguous, so per-block segment-id intervals
    are tight and non-overlapping (q block, kv block) pairs can be
    skipped. Aim for ~4 query blocks and ~16 KV blocks so skipping has
    granularity to work with at serving sizes, clamped to the MXU-friendly
    maxima (128 x 512) at scale and to the TPU tile (8 x 128) below: the
    varlen kernel's per-block metadata tiles are (blk_q, 128) and
    (8, blk_k), which the TPU compiler accepts only at these multiples.
    ``ModelRunner._attn_block_stats`` mirrors this sizing on the host for
    the StepMetrics work counters — keep the two in sync."""
    return (max(8, min(Q_BLOCK, _pow2_floor(t // 4))),
            max(128, min(KV_BLOCK, _pow2_floor(s // 16))))


# ---------------------------------------------------------------- attention
def qkv_proj(p, xn, *, kv_local: int, head_dim: int, positions,
             rope_theta: float, mrope_positions=None, use_rope=True):
    """Project + rope. Returns q (B,T,KVL,G,D), k, v (B,T,KVL,D)."""
    b, t, _ = xn.shape
    q = dense(xn, p["q"], p.get("q_bias"))
    k = dense(xn, p["k"], p.get("k_bias"))
    v = dense(xn, p["v"], p.get("v_bias"))
    q = q.reshape(b, t, -1, head_dim)
    k = k.reshape(b, t, kv_local, head_dim)
    v = v.reshape(b, t, kv_local, head_dim)
    if use_rope:
        if mrope_positions is not None:
            q = apply_mrope(q, mrope_positions, rope_theta)
            k = apply_mrope(k, mrope_positions, rope_theta)
        else:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
    q = A.group_q(q, kv_local)
    return q, k, v


def attn_train(p, x, dist: Dist, *, kv_local, head_dim, window=0,
               rope_theta=1e6, positions=None, mrope_positions=None,
               causal=True, norm_eps=1e-5, q_block=1024):
    """Full/SWA self-attention for training (no cache)."""
    b, t, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    xn = rms_norm(x, p["attn_norm"], norm_eps)
    q, k, v = qkv_proj(p, xn, kv_local=kv_local, head_dim=head_dim,
                       positions=positions, rope_theta=rope_theta,
                       mrope_positions=mrope_positions)

    # outer scan over q chunks keeps the score tensor bounded
    nq = -(-t // q_block)
    pad = nq * q_block - t
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0))) if pad else q

    def qchunk(carry, inp):
        qc, off = inp
        out = A.flash_attention(qc, k, v, causal=causal, window=window,
                                q_offset=off)
        return carry, out

    qblocks = qp.reshape(b, nq, q_block, *q.shape[2:])
    offs = jnp.arange(nq) * q_block
    _, outs = jax.lax.scan(qchunk, None, (jnp.moveaxis(qblocks, 1, 0), offs))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * q_block, -1)
    out = out[:, :t]
    y = dense(out, p["o"])
    return x + psum_tp(y, dist)


def attn_gather(buf, view_shape, tables, page_pos, layer, page_seg=None):
    """Phase 1 (READ): gather this layer's old pages + absolute positions.
    Must run before any buffer write in the same scan iteration (in-place
    aliasing: see EXPERIMENTS.md 'buffer-copy' study).

    page_seg: (B, P) owning-segment id per page for PACKED layouts (all
    segments' pages share one flat table row); None for the padded
    row-per-sequence layout. Returns (k, v, slot_pos, slot_seg) with
    slot_seg None when page_seg is None."""
    k_all, v_all = A.gather_pages(buf, view_shape, tables, layer)
    b, p = tables.shape
    tpp = view_shape[3]
    s = k_all.shape[1]
    slot_pos = (page_pos[:, :, None] + jnp.arange(tpp)[None, None, :]
                ).reshape(b, s)
    slot_seg = None
    if page_seg is not None:
        slot_seg = jnp.broadcast_to(page_seg[:, :, None],
                                    (b, p, tpp)).reshape(b, s)
    return k_all, v_all, slot_pos, slot_seg


def attn_compute(p, x, gathered, dist: Dist, *, kv_local, head_dim,
                 positions, seq_lens, window=0, rope_theta=1e6,
                 mrope_positions=None, norm_eps=1e-5, prefill=False,
                 sp_axis: Optional[str] = None, kv_groups=None,
                 seg_ids=None, chunk_start=None):
    """Phase 2 (COMPUTE): attention over gathered old pages + this step's
    fresh K/V (still in registers — the buffer write happens in phase 3).

    Old-page masking uses ``slot_pos < chunk_start`` (strictly before the
    chunk start): the chunk's own slots are not yet written. The fresh part
    is intra-chunk causal attention merged via partial-softmax, after the
    old part was combined across KV-replica groups / SP shards (the fresh
    part is replicated on all shards, so it merges locally exactly once).

    PACKED layout: ``seg_ids`` (B, T) carries per-token segment ids and
    ``chunk_start`` (B, T) each token's chunk-start position (several
    sequences share one stream row); both masks then additionally require
    segment equality, using the slot_seg returned by ``attn_gather``.
    Returns (x_out, k_fresh, v_fresh)."""
    k_all, v_all, slot_pos, slot_seg = gathered
    b, t, _ = x.shape
    xn = rms_norm(x, p["attn_norm"], norm_eps)
    q, k, v = qkv_proj(p, xn, kv_local=kv_local, head_dim=head_dim,
                       positions=positions, rope_theta=rope_theta,
                       mrope_positions=mrope_positions)
    packed = seg_ids is not None
    if chunk_start is None:
        chunk_start = positions[:, :1]                         # (B, 1)
    if prefill or packed:
        o, m, l = _prefill_flash(q, k_all, v_all, slot_pos, positions,
                                 chunk_start=chunk_start, window=window,
                                 q_seg=seg_ids, kv_seg=slot_seg)
    else:
        mask = slot_pos[:, None, :] < chunk_start[:, :, None]  # strict
        if window:
            mask &= slot_pos[:, None, :] > positions[:, :, None] - window
        o, m, l = A.attend_tokens(q, k_all, v_all, mask)
    if kv_groups is not None:
        o, m, l = A.combine_partials(o, m, l, dist.tp_axis, groups=kv_groups)
    if sp_axis is not None:
        o, m, l = A.combine_partials(o, m, l, sp_axis)
    # fresh (intra-chunk) part: causal within the chunk (and within the
    # token's own segment, for packed streams)
    if packed:
        mask_f = A.segment_mask(seg_ids, positions, seg_ids, positions,
                                window=window)
        of, mf, lf = A.attend_tokens(q, k, v, mask_f)
    elif t == 1:
        mask_f = jnp.ones((b, 1, 1), bool)
        of, mf, lf = A.attend_tokens(q, k, v, mask_f)
    elif t <= 256:
        mask_f = positions[:, None, :] <= positions[:, :, None]
        if window:
            mask_f &= positions[:, None, :] > positions[:, :, None] - window
        of, mf, lf = A.attend_tokens(q, k, v, mask_f)
    else:
        of, mf, lf = A.flash_attention_partials(
            q, k, v, causal=True, window=window)
    o, m, l = A.merge_partials(o, m, l, of, mf, lf)
    out = A.finalize_softmax(o, l).reshape(b, t, -1).astype(x.dtype)
    y = dense(out, p["o"])
    return x + psum_tp(y, dist), k, v


def attn_write(buf, view_shape, layer, write_eids, positions, k, v):
    """Phase 3 (WRITE): stream this step's K/V into its pages."""
    tpp = view_shape[3]
    return A.write_token_kv(buf, view_shape, layer, write_eids,
                            positions % tpp, k, v)


def paged_q_block(kv_heads: int, groups: int) -> int:
    """Query tokens per prefill tile of the in-place kernel: about 128
    (token, head) rows (decode tokens always take one-token tiles).
    ``ModelRunner`` sizes its ``kv_pages_read`` count with it."""
    return max(1, 128 // PK.padded_heads(kv_heads * groups))


def paged_pages_per_wave(view_shape) -> int:
    """Pages per DMA wave of the in-place kernel: about 2048 K rows, at
    most 16 pages (the v5e optimum at internlm2's and granite's widths)."""
    tpp, kvl, d = view_shape[3:]
    return max(1, min(16, 2048 * PK.LANES // (tpp * kvl * d)))


def paged_work(view_shape, groups, seg_ids, positions, page_start,
               page_count, *, window=0):
    """The in-place kernel's work plan for one KV type and step (shared
    by every layer of the type). seg_ids/positions: (1, T);
    page_start/page_count: (S,)."""
    return PK.plan_work(seg_ids[0], positions[0], page_start, page_count,
                        q_block=paged_q_block(view_shape[4], groups),
                        tokens_per_page=view_shape[3], window=window)


def attn_paged(p, x, buf, view_shape, dist: Dist, *, layer, kv_local,
               head_dim, positions, write_eids, page_ids, work, window=0,
               rope_theta=1e6, mrope_positions=None, norm_eps=1e-5):
    """Packed self-attention read IN PLACE from the unified buffer: write
    this step's K/V into its pages first, then one Pallas call
    (``kernels.paged_attention``) attends every segment's pages through
    the page table, the chunk's own slots included, under the one rule
    ``kseg == qseg and kpos <= qpos`` (plus the window). Nothing of the
    pool is gathered or copied, and the buffer carry stays in place: the
    kernel reads the newest buffer and no older version outlives the
    write. Returns (x_out, buf)."""
    b, t, _ = x.shape
    xn = rms_norm(x, p["attn_norm"], norm_eps)
    q, k, v = qkv_proj(p, xn, kv_local=kv_local, head_dim=head_dim,
                       positions=positions, rope_theta=rope_theta,
                       mrope_positions=mrope_positions)
    buf = attn_write(buf, view_shape, layer, write_eids, positions, k, v)
    vp, nl = view_shape[:2]
    rows = buf.reshape(vp * nl, -1, buf.shape[-1])
    qf = q * (1.0 / head_dim ** 0.5)              # the ref path's bf16 scale
    out = PK.paged_attention(
        qf[0], rows, page_ids, work, layer, num_layers=nl,
        tokens_per_page=view_shape[3], window=window,
        q_block=paged_q_block(kv_local, q.shape[3]),
        pages_per_wave=paged_pages_per_wave(view_shape),
        interpret=jax.default_backend() != "tpu")
    y = dense(out.reshape(b, t, -1).astype(x.dtype), p["o"])
    return x + psum_tp(y, dist), buf


def attn_cached(p, x, buf, view_shape, dist: Dist, *, layer, kv_local,
                head_dim, tables, page_pos, write_eids, positions, seq_lens,
                window=0, rope_theta=1e6, mrope_positions=None,
                norm_eps=1e-5, prefill=False, sp_axis: Optional[str] = None,
                kv_groups=None):
    """Convenience gather->compute->write for one attention layer per scan
    iteration. Models with several attention layers per iteration must call
    the phases separately (all gathers before any write)."""
    gathered = attn_gather(buf, view_shape, tables, page_pos, layer)
    x, k, v = attn_compute(
        p, x, gathered, dist, kv_local=kv_local, head_dim=head_dim,
        positions=positions, seq_lens=seq_lens, window=window,
        rope_theta=rope_theta, mrope_positions=mrope_positions,
        norm_eps=norm_eps, prefill=prefill, sp_axis=sp_axis,
        kv_groups=kv_groups)
    buf = attn_write(buf, view_shape, layer, write_eids, positions, k, v)
    return x, buf


def _prefill_flash(q, k, v, slot_pos, q_pos, *, window, chunk_start=None,
                   block=512, q_seg=None, kv_seg=None):
    """Flash attention over OLD pages for a prefill chunk.
    Returns un-normalized partials (acc, m, l) for cross-shard combining.

    chunk_start: (B,1) per row — or (B,T) per token for PACKED streams —
    old slots are valid iff slot_pos < chunk_start (the chunk itself
    attends via the fresh-KV path). q_seg (B,T) / kv_seg (B,S): packed
    segment ids; when given, the mask additionally requires
    kv_seg == q_seg so no token reads another sequence's pages.
    q: (B,T,KVL,G,D); k/v: (B,S,KVL,D); slot_pos: (B,S); q_pos: (B,T).

    PACKED streams (q_seg/kv_seg given, B == 1) run a segment-block-sparse
    schedule: queries are blocked too, and (q block, kv block) pairs whose
    segment-id intervals don't overlap are skipped entirely via lax.cond —
    the page stream is segment-contiguous, so per-token KV work tracks the
    token's own context length instead of the whole batch's S_flat. The
    skip is exact: a non-overlapping block's mask is all-false, and an
    all-masked block update is the identity (corr=1, pexp=0)."""
    b, t, kvl, g, d = q.shape
    s = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    qf = q * scale
    sparse = (q_seg is not None and kv_seg is not None
              and chunk_start is not None and b == 1)
    if sparse:
        q_block, block = sparse_blocks(t, s)
    nblk = -(-s // block)
    pad = nblk * block - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, pad)),
                           constant_values=jnp.iinfo(jnp.int32).max // 2)
        if kv_seg is not None:
            kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad)),
                             constant_values=-2)
    kb = k.reshape(b, nblk, block, kvl, d)
    vb = v.reshape(b, nblk, block, kvl, d)
    pb = slot_pos.reshape(b, nblk, block)
    sb = None if kv_seg is None else kv_seg.reshape(b, nblk, block)

    if sparse:
        return _prefill_flash_sparse(
            qf, kb, vb, pb, sb, q_seg, q_pos, chunk_start,
            window=window, q_block=q_block)

    def body(carry, blk):
        m, l, acc = carry
        if sb is None:
            kblk, vblk, pblk = blk
            sblk = None
        else:
            kblk, vblk, pblk, sblk = blk
        logit = jnp.einsum("btkgd,bjkd->bkgtj", qf, kblk,
                           preferred_element_type=jnp.float32)
        if chunk_start is not None:
            mask = jnp.broadcast_to(
                pblk[:, None, :] < chunk_start[:, :, None],
                (pblk.shape[0], q_pos.shape[1], pblk.shape[1]))
        else:
            mask = pblk[:, None, :] <= q_pos[:, :, None]       # (B,T,blk)
        if window:
            mask &= pblk[:, None, :] > q_pos[:, :, None] - window
        if sblk is not None:
            mask &= sblk[:, None, :] == q_seg[:, :, None]
        mask = mask[:, None, None]                             # (B,1,1,T,blk)
        logit = jnp.where(mask, logit, A.NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logit, axis=-1))
        pexp = jnp.exp(logit - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(pexp, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgtj,bjkd->bkgtd", pexp.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvl, g, t), A.NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvl, g, t), jnp.float32)
    a0 = jnp.zeros((b, kvl, g, t, d), jnp.float32)
    xs = [jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
          jnp.moveaxis(pb, 1, 0)]
    if sb is not None:
        xs.append(jnp.moveaxis(sb, 1, 0))
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), tuple(xs))
    return acc, m, l


def _prefill_flash_sparse(qf, kb, vb, pb, sb, q_seg, q_pos, chunk_start, *,
                          window, q_block):
    """Segment-block-sparse inner schedule for _prefill_flash (B == 1).

    qf: pre-scaled queries (1,T,KVL,G,D); kb/vb/pb/sb: KV blocked
    (1,nblk,block,...). Outer scan over query blocks, inner scan over KV
    blocks; a lax.cond skips the whole tile when the blocks' segment-id
    intervals don't overlap. Returns partials (acc, m, l) shaped exactly
    like the dense path."""
    _, t, kvl, g, d = qf.shape
    nblk, block = kb.shape[1], kb.shape[2]
    nqb = -(-t // q_block)
    pad_q = nqb * q_block - t
    qfp, qsp, qpp = qf[0], q_seg[0], q_pos[0]
    csp = jnp.broadcast_to(chunk_start, (1, t))[0]
    if pad_q:
        qfp = jnp.pad(qfp, ((0, pad_q), (0, 0), (0, 0), (0, 0)))
        qsp = jnp.pad(qsp, (0, pad_q), constant_values=-1)
        qpp = jnp.pad(qpp, (0, pad_q))
        csp = jnp.pad(csp, (0, pad_q))
    qfb = qfp.reshape(nqb, q_block, kvl, g, d)
    qsb = qsp.reshape(nqb, q_block)
    qpb = qpp.reshape(nqb, q_block)
    csb = csp.reshape(nqb, q_block)
    kbr, vbr, pbr, sbr = kb[0], vb[0], pb[0], sb[0]

    # per-block segment-id intervals (pads excluded: q pads -1, kv -2)
    k_lo, k_hi = block_intervals(sbr, block)                    # (nblk,)
    q_lo, q_hi = block_intervals(qsb, q_block)                  # (nqb,)

    def qblock(_, qx):
        qfb_i, qsb_i, qpb_i, csb_i, qlo_i, qhi_i = qx

        def kvblock(carry, kx):
            kblk, vblk, pblk, sblk, klo_j, khi_j = kx
            hit = (klo_j <= qhi_i) & (khi_j >= qlo_i)

            def update(c):
                m, l, acc = c
                logit = jnp.einsum("tkgd,jkd->kgtj", qfb_i, kblk,
                                   preferred_element_type=jnp.float32)
                mask = pblk[None, :] < csb_i[:, None]          # (qb, blk)
                if window:
                    mask &= pblk[None, :] > qpb_i[:, None] - window
                mask &= sblk[None, :] == qsb_i[:, None]
                logit_m = jnp.where(mask[None, None], logit, A.NEG_INF)
                m_new = jnp.maximum(m, jnp.max(logit_m, axis=-1))
                pexp = jnp.exp(logit_m - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(pexp, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "kgtj,jkd->kgtd", pexp.astype(vblk.dtype), vblk,
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new

            return jax.lax.cond(hit, update, lambda c: c, carry), None

        m0 = jnp.full((kvl, g, q_block), A.NEG_INF, jnp.float32)
        l0 = jnp.zeros((kvl, g, q_block), jnp.float32)
        a0 = jnp.zeros((kvl, g, q_block, d), jnp.float32)
        out, _ = jax.lax.scan(kvblock, (m0, l0, a0),
                              (kbr, vbr, pbr, sbr, k_lo, k_hi))
        return None, out

    _, (ms, ls, accs) = jax.lax.scan(
        qblock, None, (qfb, qsb, qpb, csb, q_lo, q_hi))
    m = jnp.moveaxis(ms, 0, 2).reshape(kvl, g, nqb * q_block)[..., :t][None]
    l = jnp.moveaxis(ls, 0, 2).reshape(kvl, g, nqb * q_block)[..., :t][None]
    acc = jnp.moveaxis(accs, 0, 2).reshape(
        kvl, g, nqb * q_block, d)[:, :, :t][None]
    return acc, m, l


def _bh_streams(q, k, v, groups):
    """(1,T,KVL,G,D) q + (1,S,KVL,D) k/v -> (BH,·,D) head streams for the
    Pallas varlen kernel, kv heads repeated per q group (kv head h serves
    q heads h*g .. h*g+g-1, matching the (KVL, G) flattening order)."""
    b, t, kvl, g, d = q.shape
    qbh = q[0].transpose(1, 2, 0, 3).reshape(kvl * g, t, d)
    kbh = jnp.repeat(k[0].transpose(1, 0, 2), g, axis=0)
    vbh = jnp.repeat(v[0].transpose(1, 0, 2), g, axis=0)
    return qbh, kbh, vbh


def _bh_out(out, kvl, g):
    """(BH,T,D) kernel output back to (1,T,KVL,G,D)."""
    bh, t, d = out.shape
    return out.reshape(kvl, g, t, d).transpose(2, 0, 1, 3)[None]


def packed_cross_attn_kernel(q, k_all, v_all, slot_pos, slot_seg, seg_ids,
                             enc_lens):
    """Packed cross-attention via the varlen kernel: each decoder token
    attends every encoder slot of its own segment with slot_pos <
    enc_lens, encoded as the kernel's ``kpos <= qpos`` rule with
    q_pos := enc_lens - 1. Text-only rows (enc_lens == 0) get q_pos -1,
    match nothing, and come out exactly zero — the ref path's explicit
    zero guard. Returns (1,T,KVL,G,D) normalized output in q.dtype."""
    b, t, kvl, g, d = q.shape
    qbh, kbh, vbh = _bh_streams(q, k_all, v_all, g)
    blk_q, blk_k = sparse_blocks(t, k_all.shape[1])
    out = flash_attention_varlen_tpu(
        qbh, kbh, vbh, seg_ids[0], slot_seg[0], enc_lens[0] - 1,
        slot_pos[0], blk_q=blk_q, blk_k=blk_k,
        interpret=jax.default_backend() != "tpu")
    return _bh_out(out, kvl, g)


def cross_attn_train(p, x, enc_out, dist: Dist, *, kv_local, head_dim,
                     norm_eps=1e-5):
    b, t, _ = x.shape
    xn = rms_norm(x, p["attn_norm"], norm_eps)
    q = dense(xn, p["q"]).reshape(b, t, -1, head_dim)
    q = A.group_q(q, kv_local)
    k = dense(enc_out, p["k"]).reshape(b, enc_out.shape[1], kv_local, head_dim)
    v = dense(enc_out, p["v"]).reshape(b, enc_out.shape[1], kv_local, head_dim)
    s = k.shape[1]
    mask = jnp.ones((b, t, s), bool)
    o, m, l = A.attend_tokens(q, k, v, mask)
    out = A.finalize_softmax(o, l).reshape(b, t, -1).astype(x.dtype)
    return x + psum_tp(dense(out, p["o"]), dist)


# ---------------------------------------------------------------------- MLP
def mlp_block(p, x, dist: Dist, norm_eps=1e-5):
    xn = rms_norm(x, p["mlp_norm"], norm_eps)
    g = dense(xn, p["gate"])
    u = dense(xn, p["up"])
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    y = dense(h, p["down"])
    return x + psum_tp(y, dist)


# ---------------------------------------------------------------------- MoE
def moe_block(p, x, dist: Dist, *, num_experts, top_k, capacity_factor=1.25,
              norm_eps=1e-5, aux_weight=0.01, ep_axis: str = "data"):
    """GShard-style MoE with a 2-D expert sharding (big-model scale):
    experts over ``ep_axis`` (EP, all_to_all dispatch) x per-expert FFN dim
    over the tp axis (expert-TP, psum after down-proj). Pods replicate
    experts, so the all_to_all never crosses the DCN.

    Expert weights local: (E_local, d, ffe_local). Returns (x_out, aux)."""
    b, t, d = x.shape
    e = num_experts
    ep = dist.mesh.shape[ep_axis]
    e_local = p["moe_gate"].shape[0]
    assert e_local * ep == e, (e_local, ep, e)
    xn = rms_norm(x, p["mlp_norm"], norm_eps)
    tok = xn.reshape(b * t, d)
    n = tok.shape[0]

    router = jnp.einsum("nd,de->ne", tok.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(router, axis=-1)                    # (N, E)
    gate_vals, idx = jax.lax.top_k(probs, top_k)               # (N, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (n * top_k)
    aux = aux_weight * e * jnp.sum(me * ce)

    cap = int(max(1, round(n * top_k / e * capacity_factor)))
    # position of each (token, k) copy within its expert queue
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)           # (N, K, E)
    flat = onehot.reshape(n * top_k, e)
    pos = jnp.cumsum(flat, axis=0) * flat - 1                  # (N*K, E)
    pos_in_e = jnp.max(pos, axis=-1)                           # (N*K,)
    e_flat = idx.reshape(-1)
    keep = pos_in_e < cap
    slot = jnp.where(keep, e_flat * cap + pos_in_e, e * cap)   # drop -> OOB

    dispatch = jnp.zeros((e * cap + 1, d), tok.dtype)
    src = jnp.repeat(tok, top_k, axis=0)                       # (N*K, d)
    dispatch = dispatch.at[slot].set(src, mode="drop")
    dispatch = dispatch[:-1].reshape(e, cap, d)

    # EP all_to_all: (E, C, d) -> (E_local, ep*C, d)
    shuffled = jax.lax.all_to_all(
        dispatch.reshape(ep, e_local, cap, d), ep_axis,
        split_axis=0, concat_axis=0, tiled=False)              # (ep, e_local, C, d)
    shuffled = jnp.moveaxis(shuffled, 0, 1).reshape(e_local, ep * cap, d)

    # expert-TP: ffe sharded over tp; psum after down-proj
    g = jnp.einsum("ecd,edf->ecf", shuffled, p["moe_gate"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", shuffled, p["moe_up"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, p["moe_down"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    y = psum_tp(y, dist).astype(x.dtype)

    # return path
    y = jnp.moveaxis(y.reshape(e_local, ep, cap, d), 1, 0)     # (ep, e_local, C, d)
    back = jax.lax.all_to_all(y, ep_axis, split_axis=0,
                              concat_axis=0, tiled=False)
    back = back.reshape(e * cap, d)
    back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)], axis=0)

    gathered = jnp.take(back, jnp.where(keep, slot, e * cap), axis=0)
    gathered = gathered.reshape(n, top_k, d)
    out = jnp.sum(gathered.astype(jnp.float32)
                  * gate_vals[..., None], axis=1).astype(x.dtype)
    return x + out.reshape(b, t, d), aux
