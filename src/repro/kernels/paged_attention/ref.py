"""Pure-jnp oracle for the in-place paged attention kernel."""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention_ref(q, rows, page_ids, seg_ids, positions, page_start,
                        page_count, layer, *, num_layers, tokens_per_page,
                        window=0):
    """Same contract as ``kernel.paged_attention``, from the segments'
    page runs instead of the kernel's work plan: token i attends slot j
    iff j lies in a page of i's own segment run, the page id is valid,
    and j's position is at most i's (and inside the window)."""
    t, kvl, g, d = q.shape
    tpp = tokens_per_page
    n = page_ids.shape[0]
    pages = rows[jnp.maximum(page_ids, 0) * num_layers + layer]
    half = pages.shape[1] // 2
    k = pages[:, :half].reshape(n * tpp, kvl, d)
    v = pages[:, half:].reshape(n * tpp, kvl, d)
    idx = jnp.arange(n)
    in_run = ((idx[None] >= page_start[:, None])
              & (idx[None] < (page_start + page_count)[:, None]))   # (S, P)
    page_seg = jnp.where(in_run.any(0) & (page_ids >= 0),
                         jnp.argmax(in_run, axis=0), -2)
    page_rel = idx - page_start[jnp.maximum(page_seg, 0)]
    slot_seg = jnp.repeat(page_seg, tpp)
    slot_pos = (page_rel[:, None] * tpp + jnp.arange(tpp)).reshape(-1)
    mask = ((seg_ids[:, None] == slot_seg[None])
            & (slot_pos[None] <= positions[:, None]))
    if window:
        mask &= slot_pos[None] > positions[:, None] - window
    logit = jnp.einsum("tkgd,skd->kgts", q, k,
                       preferred_element_type=jnp.float32)
    logit = jnp.where(mask, logit, NEG_INF)
    m = jnp.max(logit, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(logit - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("kgts,skd->kgtd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-30)
    return jnp.moveaxis(out, 2, 0).astype(q.dtype)
