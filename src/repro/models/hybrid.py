"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention block
applied every ``attn_every`` mamba blocks (weight sharing — the Zamba2
signature). KV types: one Mamba state spec covering all mamba layers + one
full-attn spec with a cache layer per shared-block invocation."""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.spec import KVCacheSpec, attention_spec, mamba_spec
from . import attention as A
from . import blocks_attn as BA
from . import blocks_seq as BS
from .common import rms_norm
from .lm import DecoderLM, DecodeBatch, _dp_spec
from .params import PD
from .tp import (embed_lookup, expand_gqa_kv, expand_gqa_o, expand_gqa_q,
                 logits_local, mask_pad_vocab, psum_dp, sharded_softmax_xent)


class HybridLM(DecoderLM):
    def __init__(self, cfg: ModelConfig, dist):
        # bypass DecoderLM pattern machinery; reuse its vocab/ri helpers
        cfg.validate()
        self.cfg = cfg
        self.dist = dist
        tp = dist.tp
        from .tp import replica_info
        self.ri = replica_info(cfg.num_heads, cfg.num_kv_heads, tp)
        self.v_local = -(-cfg.vocab_size // tp)
        self.v_pad = self.v_local * tp
        self.is_moe = False
        assert cfg.attn_every > 0
        self.n_super = cfg.num_layers // cfg.attn_every
        self.n_tail = cfg.num_layers % cfg.attn_every
        self.md = BS.mamba2_dims(cfg.d_model, cfg.mamba_expand,
                                 cfg.mamba_headdim, cfg.mamba_d_state,
                                 cfg.mamba_conv_width, tp)

    # ------------------------------------------------------------ kv specs
    def attends_in_place(self, attention_impl: str, lanes: int) -> bool:
        return False        # the shared attention block interleaves with recurrent state

    def kv_specs(self) -> Tuple[KVCacheSpec, ...]:
        cfg, md = self.cfg, self.md
        return (
            attention_spec(
                "full_attn", num_layers=self.n_super,
                kv_heads=self.ri["kv_local"], head_dim=cfg.head_dim,
                tokens_per_page=cfg.tokens_per_page),
            # fp32 state stored as bf16 pairs -> x2 units
            mamba_spec("mamba", num_layers=cfg.num_layers,
                       conv_units=2 * md["conv_units"],
                       ssm_units=2 * md["ssm_units"]),
        )

    def page_shapes(self) -> Dict[str, Tuple[int, ...]]:
        cfg, md = self.cfg, self.md
        return {
            "full_attn": (2, cfg.tokens_per_page, self.ri["kv_local"],
                          cfg.head_dim),
            "mamba": (2 * (md["ssm_units"] + md["conv_units"]),),
        }

    # ----------------------------------------------------------- template
    def _mamba_layer_tmpl(self, n: int):
        cfg, dist, md = self.cfg, self.dist, self.md
        tp = dist.tp
        d = cfg.d_model
        dil = md["d_in_local"]
        hl = md["h_local"]
        N = cfg.mamba_d_state
        W = cfg.mamba_conv_width
        sp = P(None, "model")
        from .tp import expand_replicated

        def repl_stack(shape):
            def fn(key):
                keys = jax.random.split(key, n)
                return jnp.stack(
                    [expand_replicated(k, shape, tp) for k in keys])
            return fn

        return {
            "norm": PD((n, d), P(), init="ones"),
            "w_z": PD((n, tp, d, dil), sp),
            "w_x": PD((n, tp, d, dil), sp),
            # B/C are shared across head groups -> identical on every shard
            "w_B": PD((n, tp, d, N), sp, init="custom",
                      fn=repl_stack((d, N))),
            "w_C": PD((n, tp, d, N), sp, init="custom",
                      fn=repl_stack((d, N))),
            "w_dt": PD((n, tp, d, hl), sp),
            "dt_bias": PD((n, tp, hl), sp, init="zeros"),
            "A_log": PD((n, tp, hl), sp, init="zeros"),
            "D": PD((n, tp, hl), sp, init="ones"),
            "conv_w": PD((n, tp, W, dil + 2 * N), sp, scale=0.2),
            "out_norm": PD((n, tp, dil), sp, init="ones"),
            "w_out": PD((n, tp, dil, d), sp,
                        scale=0.02 / (2 * cfg.num_layers) ** 0.5),
        }

    def template(self):
        cfg, dist, ri = self.cfg, self.dist, self.ri
        tp = dist.tp
        d, hd = cfg.d_model, cfg.head_dim
        ffl = cfg.d_ff // tp
        qfn = lambda k: expand_gqa_q(k, d, cfg.num_heads, cfg.num_kv_heads, hd, tp)
        kvfn = lambda k: expand_gqa_kv(k, d, cfg.num_kv_heads, hd, tp)
        ofn = lambda k: expand_gqa_o(k, d, cfg.num_heads, cfg.num_kv_heads, hd, tp)
        shared = {
            "attn_norm": PD((d,), P(), init="ones"),
            "q": PD((tp, d, ri["q_local"] * hd), P("model"), init="custom", fn=qfn),
            "k": PD((tp, d, ri["kv_local"] * hd), P("model"), init="custom", fn=kvfn),
            "v": PD((tp, d, ri["kv_local"] * hd), P("model"), init="custom", fn=kvfn),
            "o": PD((tp, ri["q_local"] * hd, d), P("model"), init="custom", fn=ofn),
            "mlp_norm": PD((d,), P(), init="ones"),
            "gate": PD((tp, d, ffl), P("model")),
            "up": PD((tp, d, ffl), P("model")),
            "down": PD((tp, ffl, d), P("model")),
        }
        tmpl = {
            "embed": PD((tp, self.v_local, d), P("model")),
            "final_norm": PD((d,), P(), init="ones"),
            "mamba_main": self._mamba_layer_tmpl(self.n_super * cfg.attn_every),
            "shared_attn": shared,
        }
        if self.n_tail:
            tmpl["mamba_tail"] = self._mamba_layer_tmpl(self.n_tail)
        if not cfg.tie_embeddings:
            tmpl["unembed"] = PD((tp, self.v_local, d), P("model"))
        return tmpl

    # ----------------------------------------------------------------- run
    def _mamba_kw(self):
        cfg = self.cfg
        return dict(d_state=cfg.mamba_d_state, headdim=cfg.mamba_headdim,
                    conv_width=cfg.mamba_conv_width, norm_eps=cfg.norm_eps)

    def _train_body(self, params, tokens, targets, *mm, has_mm=False):
        cfg, dist = self.cfg, self.dist
        params = self._squeeze_params(params)
        b, t = tokens.shape
        x = embed_lookup(tokens, params["embed"], dist)
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        ae = cfg.attn_every
        main = jax.tree.map(
            lambda a: a.reshape(self.n_super, ae, *a.shape[1:]),
            params["mamba_main"])
        shared = params["shared_attn"]
        mkw = self._mamba_kw()

        def super_body(x, xs):
            mp = xs
            for j in range(ae):
                pj = jax.tree.map(lambda a: a[j], mp)
                x, _ = BS.mamba2_chunked(pj, x, dist, self.md, **mkw)
            x = BA.attn_train(shared, x, dist, kv_local=self.ri["kv_local"],
                              head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                              positions=positions, norm_eps=cfg.norm_eps)
            x = BA.mlp_block(shared, x, dist, cfg.norm_eps)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(super_body), x, main)
        if self.n_tail:
            def tail_body(x, pj):
                x, _ = BS.mamba2_chunked(pj, x, dist, self.md, **mkw)
                return x, None
            x, _ = jax.lax.scan(jax.checkpoint(tail_body), x,
                                params["mamba_tail"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = logits_local(x, self._unembed(params))
        loss = sharded_softmax_xent(logits, targets, dist)
        return psum_dp(loss, dist) / dist.dp

    def _serve_body(self, params, buffer, batch: DecodeBatch, *, prefill,
                    attention_impl="ref"):
        # attention_impl is accepted for serve_step signature parity but
        # unused: the shared attention block takes the gather + ref path
        cfg, dist = self.cfg, self.dist
        params = self._squeeze_params(params)
        tokens = batch.tokens
        b, t = tokens.shape
        positions = batch.positions
        x = embed_lookup(tokens, params["embed"], dist)
        views = self._layer_views(buffer)
        sq = lambda a: jnp.squeeze(a, axis=(0, 1))
        tables = sq(batch.tables["full_attn"])
        page_pos = sq(batch.page_pos["full_attn"])
        write_eids = sq(batch.write_eids["full_attn"])
        state_eids = jnp.squeeze(batch.state_eids["mamba"], axis=0)
        packed = batch.seg_ids is not None
        page_seg = sq(batch.page_seg["full_attn"]) if packed else None
        kv_groups = (None if self.ri["repl"] == 1 else
                     A.replica_groups(self.ri["kv_tp"], self.ri["repl"]))
        ae = cfg.attn_every
        main = jax.tree.map(
            lambda a: a.reshape(self.n_super, ae, *a.shape[1:]),
            params["mamba_main"])
        shared = params["shared_attn"]
        mkw = self._mamba_kw()
        sp_axis = "data" if dist.sp else None

        # ragged mixed batch: rows may have fewer valid tokens than T; the
        # chunked scan must not fold padded tokens into the carried state
        lidx = batch.last_idx
        lmask = (None if lidx is None else
                 jnp.arange(t)[None] <= lidx[:, None])
        seg_kw = {} if not packed else dict(
            seg_ids=batch.seg_ids[0], seg_start=batch.seg_start_tok[0],
            seg_last=batch.seg_last_tok)

        def run_mamba(pj, x, buf, layer_idx):
            view = buf.reshape(views["mamba"])
            st = A.read_state(view, layer_idx, state_eids)
            if packed:
                x, st = BS.mamba2_packed(pj, x, dist, self.md,
                                         init_state=st, **seg_kw, **mkw)
            elif prefill:
                x, st = BS.mamba2_chunked(pj, x, dist, self.md,
                                          init_state=st, length_mask=lmask,
                                          last_idx=lidx, **mkw)
            else:
                x, st = BS.mamba2_step(pj, x, st, dist, self.md, **mkw)
            buf = A.write_state(buf, views["mamba"], layer_idx,
                                state_eids, st)
            return x, buf

        def super_body(carry, xs):
            x, buf = carry
            mp, cyc = xs
            # READ phase first: gather the shared-attn pages before any of
            # this iteration's buffer writes (in-place aliasing)
            gathered = BA.attn_gather(buf, views["full_attn"], tables,
                                      page_pos, cyc, page_seg)
            # inner scan: one mamba block per iteration (read own state,
            # then write it -> read-before-write per inner iteration)
            def mamba_iter(carry, xs2):
                x, buf = carry
                pj, j = xs2
                x, buf = run_mamba(pj, x, buf, cyc * ae + j)
                return (x, buf), None
            (x, buf), _ = jax.lax.scan(
                mamba_iter, (x, buf), (mp, jnp.arange(ae)))
            x, k, v = BA.attn_compute(
                shared, x, gathered, dist,
                kv_local=self.ri["kv_local"], head_dim=cfg.head_dim,
                positions=positions, seq_lens=batch.seq_lens,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                prefill=prefill, sp_axis=sp_axis, kv_groups=kv_groups,
                seg_ids=batch.seg_ids, chunk_start=batch.chunk_start)
            x = BA.mlp_block(shared, x, dist, cfg.norm_eps)
            buf = BA.attn_write(buf, views["full_attn"], cyc, write_eids,
                                positions, k, v)
            return (x, buf), None

        (x, buffer), _ = jax.lax.scan(
            super_body, (x, buffer), (main, jnp.arange(self.n_super)))
        if self.n_tail:
            tail = params["mamba_tail"]
            base = self.n_super * ae

            def tail_body(carry, xs):
                x, buf = carry
                pj, k = xs
                x, buf = run_mamba(pj, x, buf, base + k)
                return (x, buf), None

            (x, buffer), _ = jax.lax.scan(
                tail_body, (x, buffer), (tail, jnp.arange(self.n_tail)))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if packed:
            x = jnp.take(x[0], batch.seg_last_tok, axis=0)[:, None]
        elif batch.last_idx is not None:
            x = jnp.take_along_axis(
                x, batch.last_idx[:, None, None].astype(jnp.int32), axis=1)
        else:
            x = x[:, -1:]
        logits = logits_local(x, self._unembed(params))[:, 0]
        logits = mask_pad_vocab(logits, cfg.vocab_size, dist)
        return logits, buffer
