"""Pallas TPU kernel: Mamba2 SSD chunk step (intra-chunk quadratic +
inter-chunk state carry), the MXU-native form of the selective scan
(DESIGN.md §3 hardware adaptation).

Grid: (B, H, n_chunks) — chunks innermost (sequential); the (P, N) state
persists in VMEM scratch across chunk steps."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, b_ref, c_ref, dt_ref, alog_ref, y_ref, s_ref,
            *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # (L, P)
    bm = b_ref[0, 0].astype(jnp.float32)         # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)         # (L, N)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)     # (L,)
    a_log = alog_ref[0]                          # scalar

    ldec = dt * (-jnp.exp(a_log))                # (L,) <= 0
    lcum = jnp.cumsum(ldec)
    cb = cm @ bm.T                               # (L, L)
    diff = lcum[:, None] - lcum[None, :]
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    dec = jnp.where(mask, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    score = cb * dec * dt[None, :]
    y = score @ x                                # (L, P)
    # inter-chunk read
    y += (cm * jnp.exp(lcum)[:, None]) @ s_ref[...].T
    # state update
    sfac = jnp.exp(lcum[-1] - lcum) * dt         # (L,)
    s_ref[...] = s_ref[...] * jnp.exp(lcum[-1]) + (sfac[:, None] * x).T @ bm
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def mamba_chunk_scan(x, bm, cm, dt, a_log, *, chunk=64, interpret=True):
    """x: (B, T, H, P); bm/cm: (B, T, N); dt: (B, T, H) (post-softplus);
    a_log: (H,). Returns y: (B, T, H, P) (before D-residual/gating)."""
    b, t, h, p = x.shape
    n = bm.shape[-1]
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    xg = jnp.moveaxis(x.reshape(b, nc, chunk, h, p), 3, 1)   # (B,H,nc,L,P)
    dtg = jnp.moveaxis(dt.reshape(b, nc, chunk, h), 3, 1)    # (B,H,nc,L)
    bg = bm.reshape(b, nc, chunk, n)
    cg = cm.reshape(b, nc, chunk, n)
    kernel = functools.partial(_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, h, nc, chunk, p), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xg, bg, cg, dtg, a_log.astype(jnp.float32))
    return jnp.moveaxis(out, 1, 3).reshape(b, t, h, p)
