"""Pallas TPU kernel: ragged paged attention read in place from the Jenga
unified buffer.

One call serves a whole packed step of one attention layer: decode tokens
and prefill chunks alike, each segment attending its own pages straight
out of the KV pool, with no gathered copy of the page stream.

* The buffer arrives as its ``(pages * layers, rows, 128)`` row view in
  ``pl.ANY`` memory. A page-layer is ``rows`` whole 128-lane rows: the K
  rows of its ``tokens_per_page`` slots, then the V rows. The kernel DMAs
  row block ``page * layers + layer`` of each page it needs, several
  pages per wave, double-buffered across waves and across work items.
* Work items are (segment, query tile) pairs, flattened by ``plan_work``
  into a 1-D grid with their scalars in SMEM: the tile's first token and
  length, its first query position, and the span of the flat page stream
  it reads. A decode token is a one-token tile; a prefill chunk is cut
  into ``q_block``-token tiles. The page loop of a tile runs over the
  pages it can see only: past its last query position nothing is read,
  and a sliding window starts at the first page the window reaches.
* All KV heads of a page arrive in one DMA. Queries are laid out one row
  per (token, query head), each head's vector placed in the 128 lanes at
  the offset its KV head occupies in a K/V row (``lane_expand``), so one
  matmul against the page's K rows scores every head, and a mask keeps
  each query row to the rows of its own KV head. No K/V is repeated per
  query group.
* Precision is the jnp reference's: bf16 operands, fp32 logits, max, sum
  and accumulator, probabilities cast to V's dtype before P.V. Masked
  probabilities are exactly 0; a row that sees no KV comes out 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
SUBLANES = 16          # bf16 rows per (16, 128) tile


def eligible(kv_heads: int, head_dim: int) -> bool:
    """Shapes the kernel serves: whole 128-lane K/V rows per token, and a
    head that fills or halves a row."""
    return head_dim in (64, 128) and (kv_heads * head_dim) % LANES == 0


def padded_heads(h: int) -> int:
    return -(-h // SUBLANES) * SUBLANES


def lane_expand(q, head_dim: int):
    """(T, KVL, G, D) queries -> (T, Hp, 128): one row per query head,
    its D values at the lane offset its KV head holds in a K/V row, zeros
    elsewhere; heads padded to a whole bf16 tile (pad rows match no KV)."""
    t, kvl, g, d = q.shape
    if d < LANES:
        per_row = LANES // d
        half = (jnp.arange(kvl) % per_row)[:, None, None]       # (KVL,1,1)
        lane = jnp.arange(LANES)[None, None, :] // d            # (1,1,128)
        q = jnp.where(half == lane, jnp.tile(q, (1, 1, 1, per_row)), 0)
    q = q.reshape(t, kvl * g, LANES)
    return jnp.pad(q, ((0, 0), (0, padded_heads(kvl * g) - kvl * g), (0, 0)))


def lane_select(out, kv_heads: int, groups: int, head_dim: int):
    """Inverse of ``lane_expand`` on the kernel output: (T, Hp, 128) ->
    (T, KVL, G, D)."""
    t = out.shape[0]
    out = out[:, :kv_heads * groups].reshape(t, kv_heads, groups, LANES)
    if head_dim == LANES:
        return out
    per_row = LANES // head_dim
    out = out.reshape(t, kv_heads, groups, per_row, head_dim)
    half = jnp.arange(kv_heads) % per_row
    return jnp.take_along_axis(
        out, half[None, :, None, None, None], axis=3)[:, :, :, 0]


def plan_work(seg_ids, positions, page_start, page_count, *, q_block: int,
              tokens_per_page: int, window: int = 0):
    """Flatten a packed step into (segment, query tile) work items.

    seg_ids/positions: (T,) per token (pads carry a negative segment id);
    page_start/page_count: (S,) each segment's run in the flat page
    stream, whose i-th page holds positions [i*tpp, (i+1)*tpp). Returns a
    dict of (W,) int32 arrays, W = S + ceil(T / q_block) (an upper bound
    on the items): ``q_start`` (first token), ``q_len`` (0: no-op item),
    ``q_pos`` (first query position), ``p_start`` (flat index of the first
    page read), ``p_count`` (pages read) and ``p_rel`` (that first page's
    index within its segment, which fixes its positions)."""
    t = seg_ids.shape[0]
    s = page_start.shape[0]
    w = s + -(-t // q_block)
    tok = jnp.arange(t, dtype=jnp.int32)
    seg = jnp.where(seg_ids >= 0, seg_ids, s)                  # pads -> bin s
    q_len = jnp.zeros((s + 1,), jnp.int32).at[seg].add(1)[:s]
    q_first = jnp.full((s + 1,), t, jnp.int32).at[seg].min(tok)[:s]
    pos0 = positions[jnp.minimum(q_first, t - 1)]
    tiles = -(-q_len // q_block)
    ends = jnp.cumsum(tiles)
    item = jnp.arange(w, dtype=jnp.int32)
    iseg = jnp.searchsorted(ends, item, side="right").astype(jnp.int32)
    live = iseg < s
    iseg = jnp.minimum(iseg, s - 1)
    tile = item - (ends[iseg] - tiles[iseg])
    off = tile * q_block
    n = jnp.where(live, jnp.clip(q_len[iseg] - off, 0, q_block), 0)
    qpos = pos0[iseg] + off
    hi = jnp.minimum(page_count[iseg], (qpos + n - 1) // tokens_per_page + 1)
    lo = jnp.zeros_like(hi)
    if window:
        lo = jnp.maximum(0, (qpos - window + 1) // tokens_per_page)
    cnt = jnp.where(n > 0, jnp.maximum(hi - lo, 0), 0)
    return dict(q_start=jnp.where(n > 0, q_first[iseg] + off, 0),
                q_len=n, q_pos=qpos, p_start=page_start[iseg] + lo,
                p_count=cnt, p_rel=lo)


def pages_read(work) -> jax.Array:
    """Pages one call reads (one DMA each)."""
    return jnp.sum(work["p_count"])


_WORK_KEYS = ("q_start", "q_len", "q_pos", "p_start", "p_count", "p_rel")


def _kernel(layer_ref, ids_ref, qs_ref, qn_ref, qp_ref, ps_ref, pc_ref,
            pr_ref,                                   # scalar prefetch
            q_ref, rows_hbm, o_ref,                   # q / out VMEM, pool HBM
            kv_buf, sems, slot_ref,                   # scratch
            *, num_layers, tpp, rpt, hp, groups, head_dim, window, q_block,
            npw):
    w = pl.program_id(0)
    n_items = pl.num_programs(0)
    n_ids = ids_ref.shape[0]
    half = kv_buf.shape[2] // 2                       # K rows per page-layer
    ncol = npw * half
    layer = layer_ref[0]

    def page_id(item, wave, i):
        return ids_ref[jnp.minimum(ps_ref[item] + wave * npw + i, n_ids - 1)]

    def copy(item, wave, slot, i):
        """(pred, descriptor) of page slot ``i`` of one wave."""
        pid = page_id(item, wave, i)
        pred = (wave * npw + i < pc_ref[item]) & (pid >= 0)
        src = rows_hbm.at[jnp.maximum(pid, 0) * num_layers + layer]
        return pred, pltpu.make_async_copy(src, kv_buf.at[slot, i],
                                           sems.at[slot])

    def start(item, wave, slot):
        def body(i, carry):
            pred, cp = copy(item, wave, slot, i)
            pl.when(pred)(cp.start)
            return carry
        jax.lax.fori_loop(0, npw, body, 0)

    def wait(item, wave, slot):
        """Waits for one wave; returns the bits of its page slots whose
        page id is invalid (nothing was read there)."""
        def body(i, bad):
            pred, cp = copy(item, wave, slot, i)
            pl.when(pred)(cp.wait)
            return bad | jnp.where(page_id(item, wave, i) < 0, 1 << i, 0)
        return jax.lax.fori_loop(0, npw, body, 0)

    @pl.when(w == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        kv_buf[...] = jnp.zeros_like(kv_buf)          # stale-free slots
        slot_ref[0] = 0
        start(0, 0, 0)

    n_waves = pl.cdiv(pc_ref[w], npw)
    slot0 = slot_ref[0]
    has_next = w + 1 < n_items

    def attend(bt):
        """The item's tile as ``bt`` tokens x ``hp`` head rows."""
        m_rows = bt * hp
        q = q_ref[pl.ds(qs_ref[w], bt)].reshape(m_rows, LANES)
        row = jax.lax.broadcasted_iota(jnp.int32, (m_rows, 1), 0)
        q_pos = jnp.where(row // hp < qn_ref[w],   # rows past the tile's
                          qp_ref[w] + row // hp, -1)  # length see nothing
        kv_head = (row % hp) // groups
        row_grp = (kv_head * head_dim) // LANES     # pad heads: >= rpt
        col = jax.lax.broadcasted_iota(jnp.int32, (1, ncol), 1)
        col_page = col // (tpp * rpt)
        col_slot = (col // rpt) % tpp
        col_grp = col % rpt

        def wave_body(j, carry):
            slot, m, l, acc = carry
            nxt = 1 - slot

            @pl.when(j + 1 < n_waves)
            def _():
                start(w, j + 1, nxt)

            @pl.when((j + 1 == n_waves) & has_next)
            def _():
                start(w + 1, 0, nxt)

            bad = wait(w, j, slot)
            k = kv_buf[slot, :, :half, :].reshape(ncol, LANES)
            v = kv_buf[slot, :, half:, :].reshape(ncol, LANES)
            ok = ((col_page < pc_ref[w] - j * npw)
                  & ((jnp.right_shift(bad, col_page) & 1) == 0))
            kv_pos = (pr_ref[w] + j * npw + col_page) * tpp + col_slot
            mask = ok & (col_grp == row_grp) & (kv_pos <= q_pos)
            if window:
                mask &= kv_pos > q_pos - window
            logit = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            logit = jnp.where(mask, logit, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(logit, axis=-1, keepdims=True))
            p = jnp.where(m_new > NEG_INF / 2, jnp.exp(logit - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                       preferred_element_type=jnp.float32)
            return nxt, m_new, l, acc

        init = (slot0, jnp.full((m_rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((m_rows, 1), jnp.float32),
                jnp.zeros((m_rows, LANES), jnp.float32))
        slot, _, l, acc = jax.lax.fori_loop(0, n_waves, wave_body, init)
        slot_ref[0] = slot
        out = acc / jnp.maximum(l, 1e-30)
        o_ref[pl.ds(qs_ref[w], bt)] = out.reshape(bt, hp, LANES).astype(
            o_ref.dtype)

    pl.when(qn_ref[w] == 1)(lambda: attend(1))
    pl.when(qn_ref[w] > 1)(lambda: attend(q_block))

    @pl.when((n_waves == 0) & has_next)
    def _():
        start(w + 1, 0, slot0)


def paged_attention(q, rows, page_ids, work, layer, *, num_layers: int,
                    tokens_per_page: int, window: int = 0, q_block: int,
                    pages_per_wave: int, interpret: bool = False):
    """q: (T, KVL, G, D) pre-scaled queries of one packed step; rows: the
    unified buffer as ``(pages * num_layers, rows, 128)``; page_ids: (P,)
    the flat page stream (entries < 0 are never read); work: ``plan_work``
    of the step; layer: this layer's index within its KV type. Returns
    (T, KVL, G, D) in q.dtype."""
    t, kvl, g, d = q.shape
    assert eligible(kvl, d), (kvl, d)
    rpt = kvl * d // LANES                     # rows per token (K or V)
    half = tokens_per_page * rpt
    assert rows.shape[1:] == (2 * half, LANES), (rows.shape, half)
    hp = padded_heads(kvl * g)
    qx = jnp.pad(lane_expand(q, d), ((0, q_block), (0, 0), (0, 0)))
    scalars = (jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
               page_ids.astype(jnp.int32)) + tuple(
                   work[k].astype(jnp.int32) for k in _WORK_KEYS)
    n_items = work["q_len"].shape[0]
    kv_bytes = 2 * pages_per_wave * 2 * half * LANES * rows.dtype.itemsize
    vmem = 2 * qx.size * qx.dtype.itemsize + kv_bytes
    kernel = functools.partial(
        _kernel, num_layers=num_layers, tpp=tokens_per_page, rpt=rpt, hp=hp,
        groups=g, head_dim=d, window=window, q_block=q_block,
        npw=pages_per_wave)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_items,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, pages_per_wave, 2 * half, LANES), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct(qx.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem + (16 << 20))),
    )(*scalars, qx, rows)
    return lane_select(out[:t], kvl, g, d)
