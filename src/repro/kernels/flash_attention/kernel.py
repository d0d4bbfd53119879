"""Pallas TPU kernel: flash prefill attention (causal + sliding window).

Grid: (B*H, nQ, nKV) — kv blocks innermost (sequential); online-softmax
state in VMEM scratch. Q/K/V tiles are (blk, D) with D on lanes; the MXU
sees (blk_q x D) @ (D x blk_k) matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, blk_q: int, blk_k: int, causal: bool, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (blk_q, D)
    k = k_ref[0].astype(jnp.float32)                    # (blk_k, D)
    v = v_ref[0].astype(jnp.float32)
    d = q.shape[-1]
    logit = (q * (1.0 / d ** 0.5)) @ k.T                # (blk_q, blk_k)

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    mask = jnp.ones_like(logit, dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    logit = jnp.where(mask, logit, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logit, axis=-1))
    p = jnp.exp(logit - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _final():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def _varlen_kernel(qlo_ref, qhi_ref, klo_ref, khi_ref,
                   q_ref, k_ref, v_ref, qseg_ref, kseg_ref, qpos_ref,
                   kpos_ref, o_ref, m_ref, l_ref, acc_ref, *, window: int):
    """Segment-id variant: the batch is ONE packed token stream; the causal
    structure is block-diagonal over segments (q attends k iff
    kseg == qseg and kpos <= qpos). Pad q tokens carry seg -1, pad k slots
    seg -2, so pads never match anything.

    Page streams are segment-contiguous (the host packs each segment's
    pages back to back), so a KV block covers a tight interval of segment
    ids; a whole (q block, kv block) pair is skipped when the two segment
    intervals don't overlap — per-token KV work then tracks the token's
    own context length instead of the whole batch's stream. The per-block
    intervals arrive as scalar-prefetched SMEM vectors.

    Metadata tiles are TPU-tile shaped: q-side ids/positions are
    (blk_q, 128) lane-broadcast, kv-side ones (8, blk_k) sublane-broadcast;
    the kernel reads one column / one row of each."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hit = (klo_ref[ki] <= qhi_ref[qi]) & (khi_ref[ki] >= qlo_ref[qi])

    @pl.when(hit)
    def _update():
        # operands in their own dtype, f32 accumulation; probabilities
        # enter P.V in V's dtype — the jnp reference path's precision
        q = q_ref[0]                                    # (blk_q, D)
        k = k_ref[0]                                    # (blk_k, D)
        v = v_ref[0]
        d = q.shape[-1]
        logit = jax.lax.dot_general(                    # (blk_q, blk_k)
            q * (1.0 / d ** 0.5), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        q_seg = qseg_ref[:, :1]                         # (blk_q, 1)
        q_pos = qpos_ref[:, :1]
        k_seg = kseg_ref[:1, :]                         # (1, blk_k)
        k_pos = kpos_ref[:1, :]
        mask = (k_seg == q_seg) & (k_pos <= q_pos)
        if window:
            mask &= k_pos > q_pos - window
        logit = jnp.where(mask, logit, NEG_INF)

        m_prev = m_ref[...]                             # (blk_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logit, axis=-1, keepdims=True))
        # fully-masked block rows contribute NOTHING (p would otherwise
        # degenerate to exp(NEG_INF - NEG_INF) = 1 per slot — a uniform
        # average leaking other segments' values into no-slot rows)
        p = jnp.where(m_new > NEG_INF / 2, jnp.exp(logit - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _final():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def block_intervals(seg, blk):
    """Per-block (lo, hi) segment-id intervals of a padded id stream, pads
    (negative ids) excluded; an all-pad block gets an empty interval."""
    big = jnp.int32(1 << 30)
    b = seg.reshape(-1, blk)
    return (jnp.min(jnp.where(b >= 0, b, big), axis=1),
            jnp.max(jnp.where(b >= 0, b, -big), axis=1))


def flash_attention_varlen_tpu(q, k, v, q_seg, kv_seg, q_pos, kv_pos, *,
                               window=0, blk_q=128, blk_k=128,
                               interpret=True):
    """Varlen (token-packed) flash attention: the T axis is one packed
    stream of concatenated segments, not one sequence.

    q: (BH, T, D); k/v: (BH, S, D); q_seg/q_pos: (T,); kv_seg/kv_pos: (S,)
    — segment ids and absolute in-sequence positions shared across the BH
    heads. Streams are padded up to block multiples internally (pad q rows
    seg -1, pad kv slots seg -2), so any ``_tok_bucket``-sized packed
    stream is accepted. For the TPU compiler blk_q must be a multiple of
    8 and blk_k a multiple of 128 (``blocks_attn.sparse_blocks`` sizes
    them so). Returns (BH, T, D); rows whose segment matches no kv slot
    (pad rows included) come out exactly zero."""
    bh, t, d = q.shape
    s = k.shape[1]
    t0 = t
    pad_t = -t % blk_q
    pad_s = -s % blk_k
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0)))
        q_seg = jnp.pad(q_seg, (0, pad_t), constant_values=-1)
        q_pos = jnp.pad(q_pos, (0, pad_t))
        t += pad_t
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_s), (0, 0)))
        kv_seg = jnp.pad(kv_seg, (0, pad_s), constant_values=-2)
        kv_pos = jnp.pad(kv_pos, (0, pad_s))
        s += pad_s
    q_seg, kv_seg, q_pos, kv_pos = (
        a.astype(jnp.int32) for a in (q_seg, kv_seg, q_pos, kv_pos))
    q_lo, q_hi = block_intervals(q_seg, blk_q)
    k_lo, k_hi = block_intervals(kv_seg, blk_k)
    # TPU tiles are (8, 128): q-side metadata rides lane-broadcast as
    # (T, 128), kv-side metadata sublane-broadcast as (8, S)
    q_meta = [jnp.broadcast_to(a[:, None], (t, 128)) for a in (q_seg, q_pos)]
    k_meta = [jnp.broadcast_to(a[None, :], (8, s)) for a in (kv_seg, kv_pos)]
    grid = (bh, t // blk_q, s // blk_k)
    q_spec = pl.BlockSpec((blk_q, 128), lambda b, qi, ki, *_: (qi, 0))
    k_spec = pl.BlockSpec((8, blk_k), lambda b, qi, ki, *_: (0, ki))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, qi, ki, *_: (b, qi, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, qi, ki, *_: (b, ki, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, qi, ki, *_: (b, ki, 0)),
            q_spec, k_spec, q_spec, k_spec,
        ],
        out_specs=pl.BlockSpec((1, blk_q, d),
                               lambda b, qi, ki, *_: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_varlen_kernel, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q_lo, q_hi, k_lo, k_hi, q, k, v,
      q_meta[0], k_meta[0], q_meta[1], k_meta[1])[:, :t0]


def flash_attention_tpu(q, k, v, *, causal=True, window=0,
                        blk_q=128, blk_k=128, interpret=True):
    """q: (BH, T, D); k/v: (BH, S, D). Returns (BH, T, D)."""
    bh, t, d = q.shape
    s = k.shape[1]
    blk_q = min(blk_q, t)
    blk_k = min(blk_k, s)
    assert t % blk_q == 0 and s % blk_k == 0, (t, s, blk_q, blk_k)
    grid = (bh, t // blk_q, s // blk_k)
    kernel = functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k,
                               causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, qi, ki: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
