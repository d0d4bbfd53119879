"""The plain reference forward pass of a dense decoder, and its fp8 control.

A straightforward transcription of the published architecture (Llama-style
pre-norm decoder: RMSNorm, rotary embeddings on the two halves of each head,
grouped-query causal attention, SwiGLU MLP, final RMSNorm, unembedding) in
float32 at ``HIGHEST`` matmul precision, over the weights of
``bench/weights.py``. It imports nothing of the program. One sequence runs
at a time over its whole length, layer after layer in a scan, with the
attention taken in blocks of queries so that it fits beside the weights.

``mode="fp8"`` is the control: every weight matrix (per output channel) and
every activation entering one (per token) is rounded to float8 e4m3 before
the matmul, the precision a later change might be tempted to serve in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
E4M3_MAX = 448.0


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, w, mode):
    w = w.astype(F32)
    if mode == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.dot(a, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _forward(w, tokens, rows, *, m, mode):
    (L, d, H, KV, hd, eps, theta) = m
    n = tokens.shape[0]
    G = H // KV
    pos = jnp.arange(n)
    x = w["embed"][tokens].astype(F32)

    def attend(q, k, v):
        nb = n // Q_BLOCK
        qb = q.reshape(nb, Q_BLOCK, KV, G, hd)

        def block(args):
            qi, i = args
            s = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HI)
            s = s / math.sqrt(hd)
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                          s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

        out = jax.lax.map(block, (qb, jnp.arange(nb)))
        return out.reshape(n, H * hd)

    def layer(x, lw):
        h = _rms(x, lw["attn_norm"], eps)
        q = _rope(_mm(h, lw["wq"], mode).reshape(n, H, hd), pos, theta)
        k = _rope(_mm(h, lw["wk"], mode).reshape(n, KV, hd), pos, theta)
        v = _mm(h, lw["wv"], mode).reshape(n, KV, hd)
        x = x + _mm(attend(q.reshape(n, KV, G, hd), k, v), lw["wo"], mode)
        h = _rms(x, lw["mlp_norm"], eps)
        up = jax.nn.silu(_mm(h, lw["w_gate"], mode)) * _mm(h, lw["w_up"], mode)
        return x + _mm(up, lw["w_down"], mode), None

    names = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate",
             "w_up", "w_down")
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in names})
    xf = _rms(x[rows], w["final_norm"], eps)
    unembed = w.get("unembed", w["embed"])
    return _mm(xf, unembed.T, mode)


def _bucket(n: int, quantum: int) -> int:
    """n rounded up to quantum * {1, 2, 3, 4, 6, 8, 12, ...}: few shapes."""
    blocks, p = -(-n // quantum), 1
    while True:
        for c in (p, p + p // 2):
            if c >= blocks:
                return c * quantum
        p *= 2


def shape_key(m: dict) -> tuple:
    return (m["num_layers"], m["d_model"], m["num_heads"],
            m["num_kv_heads"], m["head_dim"], float(m.get("norm_eps", 1e-5)),
            float(m["rope_theta"]))


def logits(w, m: dict, tokens, rows, mode: str = "fp32") -> np.ndarray:
    """Reference logits (len(rows), vocab) of the causal LM over ``tokens``
    at the given positions."""
    n = _bucket(len(tokens), Q_BLOCK)
    r = _bucket(len(rows), 64)
    tok = np.zeros(n, np.int32)
    tok[: len(tokens)] = tokens
    rw = np.zeros(r, np.int32)
    rw[: len(rows)] = rows
    out = _forward(w, tok, rw, m=shape_key(m), mode=mode)
    return np.asarray(out[: len(rows)], np.float32)


def gaps(w, m: dict, prompt, output, mode: str = "fp32") -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position. With ``mode="fp8"`` the token read
    at each position is the one the fp8 control puts first, and its gap is
    taken in the fp32 reference's row."""
    seq = list(prompt) + list(output[:-1])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits(w, m, seq, rows)
    picks = np.asarray(output)
    if mode != "fp32":
        picks = logits(w, m, seq, rows, mode).argmax(-1)
    best = ref.max(-1)
    return best - ref[np.arange(len(rows)), picks]
