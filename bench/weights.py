"""Random weights of a dense decoder, made on the device from the seed.

The benchmark, not the program, makes the weights, in the layout of a
published checkpoint (per-layer q, k, v, o, gate, up, down matrices stacked
over layers; norms; embedding and, if untied, unembedding), so that the
plain reference can make the same ones again without taking anything from
the program. Matrices are bf16, as they are served; norm gains are fp32.
Scales follow the usual initialisation: 1 / sqrt(fan-in) for every matrix
(0.022 at d_model 2048), divided by sqrt(2 * layers) for the projections
into the residual stream; 0.02 for the embedding and unembedding; norm
gains 1 + 0.1 * N(0, 1).

``program_tree`` lays the same arrays out as the serving model expects
(one tensor-parallel shard: a leading axis of 1 on the matrices) and checks
the result against the model's own parameter shapes, so that a layout
change in the program stops the run instead of feeding it wrong weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16


def shapes(m: dict) -> dict:
    L, d, hd = m["num_layers"], m["d_model"], m["head_dim"]
    H, KV, ff, V = m["num_heads"], m["num_kv_heads"], m["d_ff"], m["vocab_size"]
    out = {
        "attn_norm": (L, d), "mlp_norm": (L, d), "final_norm": (d,),
        "wq": (L, d, H * hd), "wk": (L, d, KV * hd), "wv": (L, d, KV * hd),
        "wo": (L, H * hd, d), "w_gate": (L, d, ff), "w_up": (L, d, ff),
        "w_down": (L, ff, d), "embed": (V, d),
    }
    if not m.get("tie_embeddings"):
        out["unembed"] = (V, d)
    return out


def _make(m: dict, seed_key):
    L = m["num_layers"]
    shp = shapes(m)
    keys = dict(zip(sorted(shp), jax.random.split(seed_key, len(shp))))
    out = {}
    for name, s in shp.items():
        k = keys[name]
        if name.endswith("norm"):
            out[name] = 1.0 + 0.1 * jax.random.normal(k, s, jnp.float32)
            continue
        if name in ("embed", "unembed"):
            scale = 0.02
        else:
            scale = 1.0 / math.sqrt(s[-2])
            if name in ("wo", "w_down"):
                scale /= math.sqrt(2 * L)
        out[name] = (scale * jax.random.normal(k, s, jnp.float32)).astype(BF16)
    return out


def seed_key(seed: int) -> np.ndarray:
    """Raw threefry key data from any whole number, 64 bits wide."""
    s = seed % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def make(m: dict, seed: int) -> dict:
    """The canonical weights, in one jitted call on the default device."""
    return jax.jit(lambda k: _make(m, jax.random.wrap_key_data(k)))(
        seed_key(seed))


def _to_program(w: dict) -> dict:
    lay = {
        "attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
        "q": w["wq"][:, None], "k": w["wk"][:, None], "v": w["wv"][:, None],
        "o": w["wo"][:, None], "gate": w["w_gate"][:, None],
        "up": w["w_up"][:, None], "down": w["w_down"][:, None],
    }
    out = {"embed": w["embed"][None], "final_norm": w["final_norm"],
           "layers": lay}
    if "unembed" in w:
        out["unembed"] = w["unembed"][None]
    return out


def program_tree(m: dict, seed: int, expected) -> dict:
    """The weights as the serving model takes them, made in one jitted call.
    ``expected`` is the model's parameter tree of ShapeDtypeStructs (dtypes
    as served); any difference in structure or shape is an error."""
    fn = jax.jit(lambda k: _to_program(_make(m, jax.random.wrap_key_data(k))))
    got = jax.eval_shape(fn, seed_key(seed))
    want = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)),
                        expected)
    have = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)), got)
    if jax.tree.structure(want) != jax.tree.structure(have) or \
            jax.tree.leaves(want) != jax.tree.leaves(have):
        raise ValueError(f"the program's parameter layout changed: expected "
                         f"{want}, the benchmark makes {have}")
    return fn(seed_key(seed))
