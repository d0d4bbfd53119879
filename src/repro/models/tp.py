"""Manual tensor-parallel primitives used inside shard_map.

Design (DESIGN.md §5): the whole model step runs in one shard_map over the
mesh; params are stored in an *expanded layout* with a leading ``tp`` dim so
that a plain ``P("model", ...)`` in_spec hands every device exactly its
Megatron slice — including GQA KV-head *replication* groups, which plain
PartitionSpecs cannot express.

GQA layout (``gqa_tp_layout``): with ``kv_tp = gcd(kv_heads, tp)`` real KV
shards and ``repl = tp // kv_tp`` replicas, device ``m`` owns KV heads
``[kg*kv_local, (kg+1)*kv_local)`` where ``kg = m // repl``, and the q heads
of those groups are split across the ``repl`` replicas (padded to equal
size). Padded q heads have zero projection rows; their attention output is
annihilated by zero o-proj rows.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .common import PARAM_DTYPE, gqa_tp_layout


@dataclasses.dataclass(frozen=True)
class Dist:
    """Mesh + axis-role bookkeeping passed through all model code."""

    mesh: Mesh
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    sp: bool = False                  # sequence-parallel decode (long-context)
    fsdp: bool = False                # shard layer weights over "data" (train)

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def dp(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.dp_axes]))

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.dp_axes) + (self.tp_axis,)

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))


def single_device_dist() -> Dist:
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:1])
    return Dist(mesh=mesh)


# --------------------------------------------------------------- param layout
def expand_rows(key, shape_per_shard, tp: int, init, **kw):
    """Init an expanded param (tp, *shape_per_shard): independent shards."""
    keys = jax.random.split(key, tp)
    return jnp.stack([init(k, shape_per_shard, **kw) for k in keys])


def expand_gqa_q(key, d_model: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, tp: int, scale=0.02):
    """Q-projection in padded GQA layout: (tp, d_model, q_local*head_dim).

    Real heads get normal init; padded slots are zero."""
    q_pad, q_local, kv_tp, kv_local = gqa_tp_layout(num_heads, num_kv_heads, tp)
    repl = tp // kv_tp
    group = num_heads // num_kv_heads
    group_pad = q_pad // num_kv_heads
    gpp = group_pad // repl
    w = scale * jax.random.normal(
        key, (num_kv_heads, group_pad, d_model, head_dim))
    # zero the padded group slots
    mask = (jnp.arange(group_pad) < group)[None, :, None, None]
    w = jnp.where(mask, w, 0.0)
    # device m = (kg, r): heads = w[kg*kv_local:(kg+1)*kv_local, r*gpp:(r+1)*gpp]
    w = w.reshape(kv_tp, kv_local, repl, gpp, d_model, head_dim)
    w = jnp.transpose(w, (0, 2, 1, 3, 4, 5))       # (kv_tp, repl, kv_local, gpp, d, hd)
    w = w.reshape(tp, kv_local * gpp, d_model, head_dim)
    w = jnp.transpose(w, (0, 2, 1, 3))             # (tp, d, q_local, hd)
    return w.astype(PARAM_DTYPE).reshape(tp, d_model, q_local * head_dim)


def expand_gqa_o(key, d_model: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, tp: int, scale=0.02):
    """O-projection transpose-layout: (tp, q_local*head_dim, d_model)."""
    q_pad, q_local, kv_tp, kv_local = gqa_tp_layout(num_heads, num_kv_heads, tp)
    repl = tp // kv_tp
    group = num_heads // num_kv_heads
    group_pad = q_pad // num_kv_heads
    gpp = group_pad // repl
    w = scale * jax.random.normal(
        key, (num_kv_heads, group_pad, head_dim, d_model))
    mask = (jnp.arange(group_pad) < group)[None, :, None, None]
    w = jnp.where(mask, w, 0.0)
    w = w.reshape(kv_tp, kv_local, repl, gpp, head_dim, d_model)
    w = jnp.transpose(w, (0, 2, 1, 3, 4, 5))
    return w.reshape(tp, q_local * head_dim, d_model).astype(PARAM_DTYPE)


def expand_gqa_kv(key, d_model: int, num_kv_heads: int, head_dim: int,
                  tp: int, scale=0.02):
    """K or V projection with replication: (tp, d_model, kv_local*head_dim).
    Replicas share identical weights (same KV content on each replica)."""
    _, _, kv_tp, kv_local = gqa_tp_layout(1 * num_kv_heads, num_kv_heads, tp)
    repl = tp // kv_tp
    w = scale * jax.random.normal(key, (kv_tp, d_model, kv_local * head_dim))
    w = jnp.broadcast_to(w[:, None], (kv_tp, repl, d_model, kv_local * head_dim))
    return w.reshape(tp, d_model, kv_local * head_dim).astype(PARAM_DTYPE)


def expand_replicated(key, shape, tp: int, scale=0.02):
    """Expanded param whose content is identical on every shard (e.g. Mamba
    B/C projections shared by all head groups)."""
    w = scale * jax.random.normal(key, shape)
    return jnp.broadcast_to(w[None], (tp,) + tuple(shape)).astype(PARAM_DTYPE)


# ------------------------------------------------------- inside-shard_map ops
def psum_tp(x, dist: Dist):
    return jax.lax.psum(x, dist.tp_axis)


def psum_dp(x, dist: Dist):
    return jax.lax.psum(x, dist.dp_axes)


def embed_lookup(tokens, table_local, dist: Dist):
    """Vocab-sharded embedding lookup (inside shard_map).

    tokens: (..., ) int32; table_local: (V_local, d). Returns (..., d)."""
    v_local = table_local.shape[0]
    shard = jax.lax.axis_index(dist.tp_axis)
    lo = shard * v_local
    idx = tokens - lo
    ok = (idx >= 0) & (idx < v_local)
    idx = jnp.clip(idx, 0, v_local - 1)
    out = jnp.take(table_local, idx, axis=0).astype(jnp.bfloat16)
    out = jnp.where(ok[..., None], out, 0)
    return psum_tp(out, dist)


def logits_local(x, table_local):
    """x: (..., d) -> vocab-sharded logits (..., V_local), fp32."""
    return jnp.einsum("...d,vd->...v", x, table_local.astype(x.dtype),
                      preferred_element_type=jnp.float32)


def sharded_softmax_xent(logits_loc, targets, dist: Dist, mask=None):
    """Cross-entropy over vocab-sharded fp32 logits (..., V_local)."""
    v_local = logits_loc.shape[-1]
    shard = jax.lax.axis_index(dist.tp_axis)
    lo = shard * v_local
    # global max via all_gather (differentiable, unlike pmax); the shift is
    # stop_gradient'd — it cancels in d/dx logsumexp anyway.
    lmax = jnp.max(logits_loc, axis=-1)
    gmax = jnp.max(
        jax.lax.all_gather(jax.lax.stop_gradient(lmax), dist.tp_axis), axis=0)
    z = jnp.sum(jnp.exp(logits_loc - gmax[..., None]), axis=-1)
    z = psum_tp(z, dist)
    logz = jnp.log(z) + gmax
    idx = targets - lo
    ok = (idx >= 0) & (idx < v_local)
    idx = jnp.clip(idx, 0, v_local - 1)
    gold = jnp.take_along_axis(logits_loc, idx[..., None], axis=-1)[..., 0]
    gold = psum_tp(jnp.where(ok, gold, 0.0), dist)
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def mask_pad_vocab(logits_loc, vocab_size: int, dist: Dist):
    """Mask pad-vocab columns (global id >= vocab_size) to -1e30.

    The unembed table is padded to V_local * tp rows whose logits are
    garbage (random init); the serve heads mask them here so BOTH the
    device sampler and the host sampler can operate on full v_pad rows
    identically — exp(-1e30) underflows to exactly 0 in a softmax and
    pads sort last under top-k, so no caller ever needs to slice
    [:vocab_size] again. Keep in sync with serving.sampler.NEG."""
    v_local = logits_loc.shape[-1]
    shard = jax.lax.axis_index(dist.tp_axis)
    gid = shard * v_local + jnp.arange(v_local)
    return jnp.where(gid < vocab_size, logits_loc, -1e30)


def gather_logits(logits_loc, dist: Dist):
    """(..., V_local) -> (..., V) via all-gather over the tp axis."""
    g = jax.lax.all_gather(logits_loc, dist.tp_axis, axis=-1, tiled=True)
    return g


def replica_info(num_heads: int, num_kv_heads: int, tp: int):
    q_pad, q_local, kv_tp, kv_local = gqa_tp_layout(num_heads, num_kv_heads, tp)
    repl = tp // kv_tp
    return dict(q_pad=q_pad, q_local=q_local, kv_tp=kv_tp,
                kv_local=kv_local, repl=repl)
