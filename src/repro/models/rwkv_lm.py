"""RWKV6 ("Finch") language model: attention-free, data-dependent decay.

KV type: a single "rwkv" state spec (wkv matrix state + token-shift states
per layer). No token pages at all — the paper's 'state space' extreme."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.spec import KVCacheSpec, rwkv_spec
from . import attention as A
from . import blocks_seq as BS
from .common import rms_norm
from .lm import DecoderLM, DecodeBatch
from .params import PD
from .tp import (embed_lookup, expand_replicated, logits_local,
                 mask_pad_vocab, psum_dp, sharded_softmax_xent)

LORA_RANK = 32


class RWKVLM(DecoderLM):
    def __init__(self, cfg: ModelConfig, dist):
        self.cfg = cfg
        self.dist = dist
        tp = dist.tp
        self.v_local = -(-cfg.vocab_size // tp)
        self.v_pad = self.v_local * tp
        self.is_moe = False
        self.rd = BS.rwkv6_dims(cfg.d_model, cfg.rwkv_head_size, tp)
        self.ri = {"kv_local": 1}  # unused

    def attends_in_place(self, attention_impl: str, lanes: int) -> bool:
        return False        # no attention layers

    def kv_specs(self) -> Tuple[KVCacheSpec, ...]:
        cfg, rd = self.cfg, self.rd
        return (
            rwkv_spec("rwkv", num_layers=cfg.num_layers,
                      att_state_units=2 * rd["wkv_units"],
                      shift_state_units=2 * rd["shift_units"]),
        )

    def page_shapes(self) -> Dict[str, Tuple[int, ...]]:
        rd = self.rd
        return {"rwkv": (2 * (rd["wkv_units"] + rd["shift_units"]),)}

    def template(self):
        cfg, dist, rd = self.cfg, self.dist, self.rd
        tp = dist.tp
        d = cfg.d_model
        L = cfg.num_layers
        dal = rd["d_att_local"]
        hl, hs = rd["h_local"], cfg.rwkv_head_size
        assert cfg.d_ff % tp == 0 and d % tp == 0, (cfg.d_ff, d, tp)
        ffl = cfg.d_ff // tp
        dl = d // tp              # channel-mix output column shard
        sp = P(None, "model")

        def repl_stack(shape, scale=0.02):
            def fn(key):
                keys = jax.random.split(key, L)
                return jnp.stack(
                    [expand_replicated(k, shape, tp, scale) for k in keys])
            return fn

        layers = {
            "ln1": PD((L, d), P(), init="ones"),
            "ln2": PD((L, d), P(), init="ones"),
            "ln_x": PD((L, tp, dal), sp, init="ones"),
            # token-shift mixing coefficients (replicated)
            "mu_r": PD((L, d), P(), scale=0.5),
            "mu_k": PD((L, d), P(), scale=0.5),
            "mu_v": PD((L, d), P(), scale=0.5),
            "mu_g": PD((L, d), P(), scale=0.5),
            "mu_w": PD((L, d), P(), scale=0.5),
            "w_r": PD((L, tp, d, dal), sp),
            "w_k": PD((L, tp, d, dal), sp),
            "w_v": PD((L, tp, d, dal), sp),
            "w_g": PD((L, tp, d, dal), sp),
            "w_o": PD((L, tp, dal, d), sp, scale=0.02 / (2 * L) ** 0.5),
            # data-dependent decay lora (Finch): d -> rank -> d_att_local
            "w_lora_a": PD((L, tp, d, LORA_RANK), sp, init="custom",
                           fn=repl_stack((d, LORA_RANK))),
            "w_lora_b": PD((L, tp, LORA_RANK, dal), sp, scale=0.01),
            "w_base": PD((L, tp, dal), sp, init="custom",
                         fn=lambda key: jnp.broadcast_to(
                             jnp.full((dal,), 0.6), (L, tp, dal))),
            "u": PD((L, tp, hl, hs), sp, scale=0.5),
            # channel mix
            "cm_mu_k": PD((L, d), P(), scale=0.5),
            "cm_mu_r": PD((L, d), P(), scale=0.5),
            "cm_wk": PD((L, tp, d, ffl), sp),
            "cm_wv": PD((L, tp, ffl, dl), sp, scale=0.02 / (2 * L) ** 0.5),
            "cm_wr": PD((L, tp, d, dl), sp),
        }
        tmpl = {
            "embed": PD((tp, self.v_local, d), P("model")),
            "final_norm": PD((d,), P(), init="ones"),
            "layers": layers,
        }
        if not cfg.tie_embeddings:
            tmpl["unembed"] = PD((tp, self.v_local, d), P("model"))
        return tmpl

    # ------------------------------------------------------------------ run
    def _train_body(self, params, tokens, targets, *mm, has_mm=False):
        cfg, dist = self.cfg, self.dist
        params = self._squeeze_params(params)
        x = embed_lookup(tokens, params["embed"], dist)

        def body(x, pj):
            x, _ = BS.rwkv6_chunked(pj, x, dist, self.rd,
                                    head_size=cfg.rwkv_head_size,
                                    norm_eps=cfg.norm_eps)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = logits_local(x, self._unembed(params))
        loss = sharded_softmax_xent(logits, targets, dist)
        return psum_dp(loss, dist) / dist.dp

    def _serve_body(self, params, buffer, batch: DecodeBatch, *, prefill,
                    attention_impl="ref"):
        # attention_impl is accepted for serve_step signature parity but
        # unused: RWKV has no attention layers to dispatch
        cfg, dist = self.cfg, self.dist
        params = self._squeeze_params(params)
        x = embed_lookup(batch.tokens, params["embed"], dist)
        views = self._layer_views(buffer)
        state_eids = jnp.squeeze(batch.state_eids["rwkv"], axis=0)
        # ragged mixed batch: padded tokens must not enter the wkv state
        t = batch.tokens.shape[1]
        packed = batch.seg_ids is not None
        lidx = batch.last_idx
        lmask = (None if lidx is None else
                 jnp.arange(t)[None] <= lidx[:, None])
        seg_kw = {} if not packed else dict(
            seg_ids=batch.seg_ids[0], seg_start=batch.seg_start_tok[0],
            seg_last=batch.seg_last_tok)

        def body(carry, xs):
            x, buf = carry
            pj, layer = xs
            view = buf.reshape(views["rwkv"])
            st = A.read_state(view, layer, state_eids)
            if packed:
                x, st = BS.rwkv6_packed(pj, x, dist, self.rd,
                                        head_size=cfg.rwkv_head_size,
                                        norm_eps=cfg.norm_eps, init_state=st,
                                        **seg_kw)
            elif prefill:
                x, st = BS.rwkv6_chunked(pj, x, dist, self.rd,
                                         head_size=cfg.rwkv_head_size,
                                         norm_eps=cfg.norm_eps, init_state=st,
                                         length_mask=lmask, last_idx=lidx)
            else:
                x, st = BS.rwkv6_step(pj, x, st, dist, self.rd,
                                      head_size=cfg.rwkv_head_size,
                                      norm_eps=cfg.norm_eps)
            buf = A.write_state(buf, views["rwkv"], layer, state_eids, st)
            return (x, buf), None

        (x, buffer), _ = jax.lax.scan(
            body, (x, buffer),
            (params["layers"], jnp.arange(cfg.num_layers)))
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
