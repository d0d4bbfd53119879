"""Pallas TPU kernel: paged decode attention over the Jenga unified buffer.

One query token per sequence attends to its pages (exec ids from the block
table). TPU adaptation of PagedAttention's CUDA gather loops:
  * the block table rides in SMEM via PrefetchScalarGridSpec — the page
    BlockSpec's index_map reads it to stream exactly this sequence's pages
    HBM->VMEM (no materialized gather);
  * page slices are (TPP, KVL*D) tiles — lane dim 128-aligned by
    construction (head_dim 128/64, tokens_per_page >= 8);
  * online softmax state (m, l, acc) lives in VMEM scratch and persists
    across the sequential page-grid dimension.

Grid: (B, P) — P pages per sequence, iterated innermost (sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(tables_ref, page_pos_ref, positions_ref,   # scalar prefetch
            q_ref, kv_ref, o_ref,                      # VMEM refs
            m_ref, l_ref, acc_ref,                     # scratch
            *, tokens_per_page: int, window: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (KVL, G, D)
    kvl, g, d = q.shape
    kv = kv_ref[0]                                     # (2, TPP, KVL, D)
    k = kv[0].astype(jnp.float32)                      # (TPP, KVL, D)
    v = kv[1].astype(jnp.float32)

    scale = 1.0 / (d ** 0.5)
    logit = jnp.einsum("kgd,tkd->kgt", q * scale, k)   # (KVL, G, TPP)

    base = page_pos_ref[b, p]
    qpos = positions_ref[b]
    slot_pos = base + jax.lax.broadcasted_iota(jnp.int32, (tokens_per_page,), 0)
    mask = slot_pos <= qpos
    if window:
        mask &= slot_pos > qpos - window
    logit = jnp.where(mask[None, None, :], logit, NEG_INF)

    m_prev = m_ref[...]                                # (KVL, G)
    m_new = jnp.maximum(m_prev, jnp.max(logit, axis=-1))
    pexp = jnp.exp(logit - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + \
        jnp.einsum("kgt,tkd->kgd", pexp, v)
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _final():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_decode_attention(q, kv_view, tables, page_pos, positions, *,
                           window: int = 0, interpret: bool = True):
    """q: (B, KVL, G, D); kv_view: (VP, 2, TPP, KVL, D) — ONE layer's view of
    the unified buffer; tables: (B, P) exec ids (<0 masked); page_pos: (B, P)
    absolute position of each page's first token (huge sentinel when
    invalid); positions: (B,) query positions. Returns (B, KVL, G, D)."""
    b, kvl, g, d = q.shape
    vp, _, tpp, kvl2, d2 = kv_view.shape
    assert (kvl, d) == (kvl2, d2)
    n_pages = tables.shape[1]
    tables_safe = jnp.maximum(tables, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, kvl, g, d), lambda bi, p, *_: (bi, 0, 0, 0)),
            pl.BlockSpec((1, 2, tpp, kvl, d),
                         lambda bi, p, tables_ref, *_:
                         (tables_ref[bi, p], 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvl, g, d), lambda bi, p, *_: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvl, g), jnp.float32),
            pltpu.VMEM((kvl, g), jnp.float32),
            pltpu.VMEM((kvl, g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, tokens_per_page=tpp, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvl, g, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(tables_safe, page_pos.astype(jnp.int32), positions.astype(jnp.int32),
      q, kv_view)
