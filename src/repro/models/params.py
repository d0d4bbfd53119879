"""Mini param-definition framework: one template tree drives real init,
abstract ShapeDtypeStruct init (dry-run), and PartitionSpec trees."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import PARAM_DTYPE


# Leaves (by their dict key) that every use casts to the compute dtype
# (``common.DTYPE``) before a matmul or an embedding lookup, so storing
# them in bf16 changes no result. Every other leaf — norms, biases, SSM
# decays and skip weights, RWKV mixing coefficients, bonus and decay LoRA
# output, the MoE router — is read in fp32.
MATMUL_WEIGHTS = frozenset({
    "q", "k", "v", "o", "gate", "up", "down", "embed", "unembed", "dec_pos",
    "moe_gate", "moe_up", "moe_down", "w1", "w2",
    "w_z", "w_x", "w_B", "w_C", "w_dt", "w_out",
    "w_r", "w_k", "w_v", "w_g", "w_o", "w_lora_a",
    "cm_wk", "cm_wr", "cm_wv",
})


@dataclasses.dataclass
class PD:
    """One parameter definition."""

    shape: Tuple[int, ...]
    spec: P = P()
    init: str = "normal"             # normal | zeros | ones | custom
    scale: float = 0.02
    fn: Optional[Callable[[jax.Array], jax.Array]] = None  # custom init
    dtype: Any = PARAM_DTYPE


def _init_leaf(pd: PD, key):
    if pd.init == "custom":
        out = pd.fn(key)
        assert out.shape == pd.shape, (out.shape, pd.shape)
        return out.astype(pd.dtype)
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, pd.dtype)
    if pd.init == "ones":
        return jnp.ones(pd.shape, pd.dtype)
    return (pd.scale * jax.random.normal(key, pd.shape)).astype(pd.dtype)


def is_pd(x):
    return isinstance(x, PD)


def init_params(template, seed: int = 0):
    leaves, treedef = jax.tree.flatten(template, is_leaf=is_pd)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    vals = [_init_leaf(pd, k) for pd, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def param_struct(template):
    return jax.tree.map(
        lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype), template,
        is_leaf=is_pd)


def param_specs(template):
    return jax.tree.map(lambda pd: pd.spec, template, is_leaf=is_pd)


def sharded_init(template, mesh, seed: int = 0):
    """Init each param directly with its target sharding (avoids a host
    gather; fine on 1 device too)."""
    from jax.sharding import NamedSharding

    def one(pd: PD, key):
        shard = NamedSharding(mesh, pd.spec)
        return jax.jit(lambda k: _init_leaf(pd, k),
                       out_shardings=shard)(key)

    leaves, treedef = jax.tree.flatten(template, is_leaf=is_pd)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    vals = [one(pd, k) for pd, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)
