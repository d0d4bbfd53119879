"""Whisper-style encoder-decoder backbone (audio family).

The conv frontend is a STUB per the assignment: ``input_specs()`` supplies
precomputed log-mel frame embeddings (B, S_enc, d). The transformer backbone
is real: LayerNorm + GELU MLP + MHA, sinusoidal encoder positions, learned
decoder positions, causal decoder self-attention (paged at serve time) and
cross-attention over encoder KV (paged "cross_attn" type — the Llama-3.2-
Vision memory pattern of Jenga §3.2)."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.spec import KVCacheSpec, attention_spec, cross_attention_spec
from . import attention as A
from .common import dense, layer_norm
from . import blocks_attn as BA
from .lm import DecoderLM, DecodeBatch, _dp_spec
from .params import PD
from .rotary import sinusoidal_positions
from .tp import (embed_lookup, expand_gqa_kv, expand_gqa_o, expand_gqa_q,
                 logits_local, mask_pad_vocab, psum_dp, psum_tp, replica_info,
                 sharded_softmax_xent)

MAX_DEC_POS = 32768 + 8


class EncDecLM(DecoderLM):
    def __init__(self, cfg: ModelConfig, dist):
        self.cfg = cfg
        self.dist = dist
        tp = dist.tp
        self.ri = replica_info(cfg.num_heads, cfg.num_kv_heads, tp)
        self.v_local = -(-cfg.vocab_size // tp)
        self.v_pad = self.v_local * tp
        self.is_moe = False
        self.max_dec_pos = min(MAX_DEC_POS, 32768 + 8)

    def attends_in_place(self, attention_impl: str, lanes: int) -> bool:
        return False        # self-attention stays on the gather + ref path

    def kv_specs(self) -> Tuple[KVCacheSpec, ...]:
        cfg = self.cfg
        return (
            attention_spec("full_attn", num_layers=cfg.num_layers,
                           kv_heads=self.ri["kv_local"], head_dim=cfg.head_dim,
                           tokens_per_page=cfg.tokens_per_page),
            cross_attention_spec("cross_attn", num_layers=cfg.num_layers,
                                 kv_heads=self.ri["kv_local"],
                                 head_dim=cfg.head_dim,
                                 tokens_per_page=cfg.tokens_per_page),
        )

    def page_shapes(self) -> Dict[str, Tuple[int, ...]]:
        cfg = self.cfg
        shp = (2, cfg.tokens_per_page, self.ri["kv_local"], cfg.head_dim)
        return {"full_attn": shp, "cross_attn": shp}

    # ----------------------------------------------------------- template
    def _attn_tmpl(self, n, with_kv=True):
        cfg, ri = self.cfg, self.ri
        tp = self.dist.tp
        d, hd = cfg.d_model, cfg.head_dim
        qfn = lambda k: expand_gqa_q(k, d, cfg.num_heads, cfg.num_kv_heads, hd, tp)
        kvfn = lambda k: expand_gqa_kv(k, d, cfg.num_kv_heads, hd, tp)
        ofn = lambda k: expand_gqa_o(k, d, cfg.num_heads, cfg.num_kv_heads, hd, tp)

        def stack(fn):
            def f(key):
                return jnp.stack([fn(k) for k in jax.random.split(key, n)])
            return f

        t = {
            "ln_w": PD((n, d), P(), init="ones"),
            "ln_b": PD((n, d), P(), init="zeros"),
            "q": PD((n, tp, d, ri["q_local"] * hd), P(None, "model"),
                    init="custom", fn=stack(qfn)),
            "q_bias": PD((n, tp, ri["q_local"] * hd), P(None, "model"),
                         init="zeros"),
            "o": PD((n, tp, ri["q_local"] * hd, d), P(None, "model"),
                    init="custom", fn=stack(ofn)),
            "o_bias": PD((n, d), P(), init="zeros"),
        }
        if with_kv:
            t["k"] = PD((n, tp, d, ri["kv_local"] * hd), P(None, "model"),
                        init="custom", fn=stack(kvfn))
            t["v"] = PD((n, tp, d, ri["kv_local"] * hd), P(None, "model"),
                        init="custom", fn=stack(kvfn))
            t["v_bias"] = PD((n, tp, ri["kv_local"] * hd), P(None, "model"),
                             init="zeros")
        return t

    def _mlp_tmpl(self, n):
        cfg = self.cfg
        tp = self.dist.tp
        d = cfg.d_model
        ffl = cfg.d_ff // tp
        return {
            "ln_w": PD((n, d), P(), init="ones"),
            "ln_b": PD((n, d), P(), init="zeros"),
            "w1": PD((n, tp, d, ffl), P(None, "model")),
            "b1": PD((n, tp, ffl), P(None, "model"), init="zeros"),
            "w2": PD((n, tp, ffl, d), P(None, "model"),
                     scale=0.02 / (2 * cfg.num_layers) ** 0.5),
            "b2": PD((n, d), P(), init="zeros"),
        }

    def template(self):
        cfg = self.cfg
        tp = self.dist.tp
        d = cfg.d_model
        Le, Ld = cfg.encoder_layers, cfg.num_layers
        tmpl = {
            "embed": PD((tp, self.v_local, d), P("model")),
            "dec_pos": PD((self.max_dec_pos, d), P(), scale=0.01),
            "enc": {"attn": self._attn_tmpl(Le), "mlp": self._mlp_tmpl(Le)},
            "enc_ln_post_w": PD((d,), P(), init="ones"),
            "enc_ln_post_b": PD((d,), P(), init="zeros"),
            "dec_self": self._attn_tmpl(Ld),
            "dec_cross": self._attn_tmpl(Ld),
            "dec_mlp": self._mlp_tmpl(Ld),
            "final_ln_w": PD((d,), P(), init="ones"),
            "final_ln_b": PD((d,), P(), init="zeros"),
        }
        return tmpl

    # ----------------------------------------------------------- building blocks
    def _mha(self, p, x, kv_src, *, causal, eps):
        """Plain MHA (train path / encoder): q from x, k/v from kv_src."""
        cfg, dist, ri = self.cfg, self.dist, self.ri
        b, t, d = x.shape
        xn = layer_norm(x, p["ln_w"], p["ln_b"], eps)
        kv_n = xn if kv_src is None else kv_src
        q = dense(xn, p["q"], p["q_bias"]).reshape(b, t, -1, cfg.head_dim)
        k = dense(kv_n, p["k"]).reshape(b, kv_n.shape[1], ri["kv_local"],
                                        cfg.head_dim)
        v = dense(kv_n, p["v"], p["v_bias"]).reshape(
            b, kv_n.shape[1], ri["kv_local"], cfg.head_dim)
        q = A.group_q(q, ri["kv_local"])
        out = A.flash_attention(q, k, v, causal=causal)
        out = out.reshape(b, t, -1)
        y = psum_tp(dense(out, p["o"]), self.dist)
        return x + y + p["o_bias"].astype(y.dtype)

    def _mlp(self, p, x, eps):
        xn = layer_norm(x, p["ln_w"], p["ln_b"], eps)
        h = dense(xn, p["w1"], p["b1"])
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        y = psum_tp(dense(h, p["w2"]), self.dist)
        return x + y + p["b2"].astype(y.dtype)

    def _encode(self, params, enc_embeds, eps):
        d = self.cfg.d_model
        x = enc_embeds.astype(jnp.bfloat16)
        x = x + sinusoidal_positions(x.shape[1], d).astype(x.dtype)[None]

        def body(x, pj):
            x = self._mha(pj["attn"], x, None, causal=False, eps=eps)
            x = self._mlp(pj["mlp"], x, eps)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["enc"])
        return layer_norm(x, params["enc_ln_post_w"], params["enc_ln_post_b"],
                          eps)

    # ------------------------------------------------------------------ train
    def train_loss(self, params, tokens, targets, *, enc_embeds=None, **kw):
        dist = self.dist
        dp = _dp_spec(dist)
        fn = jax.shard_map(
            self._train_body_ed, mesh=dist.mesh,
            in_specs=(self.specs(), P(dp), P(dp), P(dp)),
            out_specs=P(), check_vma=False)
        return fn(params, tokens, targets, enc_embeds)

    def _train_body_ed(self, params, tokens, targets, enc_embeds):
        cfg, dist = self.cfg, self.dist
        eps = cfg.norm_eps
        params = self._squeeze_params(params)
        enc_out = self._encode(params, enc_embeds, eps)
        b, t = tokens.shape
        x = embed_lookup(tokens, params["embed"], dist)
        x = x + params["dec_pos"][:t].astype(x.dtype)[None]

        def body(x, pj):
            ps, pc, pm = pj
            x = self._mha(ps, x, None, causal=True, eps=eps)
            x = self._mha(pc, x, enc_out, causal=False, eps=eps)
            x = self._mlp(pm, x, eps)
            return x, None

        x, _ = jax.lax.scan(
            jax.checkpoint(body), x,
            (params["dec_self"], params["dec_cross"], params["dec_mlp"]))
        x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], eps)
        logits = logits_local(x, params["embed"])
        loss = sharded_softmax_xent(logits, targets, dist)
        return psum_dp(loss, dist) / dist.dp

    # ------------------------------------------------------------------ serve
    def _serve_body(self, params, buffer, batch: DecodeBatch, *, prefill,
                    attention_impl="ref"):
        cfg, dist, ri = self.cfg, self.dist, self.ri
        eps = cfg.norm_eps
        params = self._squeeze_params(params)
        views = self._layer_views(buffer)
        sq = lambda a: jnp.squeeze(a, axis=(0, 1))
        tables_sa = sq(batch.tables["full_attn"])
        page_pos_sa = sq(batch.page_pos["full_attn"])
        write_sa = sq(batch.write_eids["full_attn"])
        tables_ca = sq(batch.tables["cross_attn"])
        packed = batch.seg_ids is not None
        page_seg_sa = page_seg_ca = page_pos_ca = None
        if packed:
            page_seg_sa = sq(batch.page_seg["full_attn"])
            page_seg_ca = sq(batch.page_seg["cross_attn"])
            page_pos_ca = sq(batch.page_pos["cross_attn"])
        kv_groups = (None if ri["repl"] == 1 else
                     A.replica_groups(ri["kv_tp"], ri["repl"]))
        # the kernel impl reaches cross-attention only (packed, single
        # shard: the Pallas call returns normalized output, sharded
        # partials keep the ref path); self-attention always takes ref
        use_kernel = (attention_impl == "kernel" and packed
                      and kv_groups is None)

        if prefill and batch.enc_embeds is not None:
            # run encoder once; write per-layer cross KV pages
            enc_out = self._encode(params, batch.enc_embeds, eps)
            enc_write = sq(batch.enc_write_eids)

            def wr(buf, xs):
                pj, layer = xs
                vshape = views["cross_attn"]
                tpp = vshape[3]
                b_, s_, _ = enc_out.shape
                k = dense(enc_out, pj["k"]).reshape(
                    b_, s_, ri["kv_local"], cfg.head_dim)
                v = dense(enc_out, pj["v"], pj["v_bias"]).reshape(
                    b_, s_, ri["kv_local"], cfg.head_dim)
                slots = jnp.broadcast_to(
                    (jnp.arange(s_) % tpp)[None], (b_, s_))
                buf = A.write_token_kv(buf, vshape, layer, enc_write,
                                       slots, k, v)
                return buf, None

            buffer, _ = jax.lax.scan(
                wr, buffer,
                (params["dec_cross"], jnp.arange(cfg.num_layers)))

        tokens = batch.tokens
        b, t = tokens.shape
        positions = batch.positions
        x = embed_lookup(tokens, params["embed"], dist)
        pos_emb = jnp.take(params["dec_pos"],
                           jnp.clip(positions, 0, self.max_dec_pos - 1),
                           axis=0)
        x = x + pos_emb.astype(x.dtype)

        def body(carry, xs):
            x, buf = carry
            (ps, pc, pm), layer = xs
            # READ phase: gather self + cross pages before any write
            vshape = views["full_attn"]
            tpp = vshape[3]
            k_all, v_all, slot_pos, slot_seg = BA.attn_gather(
                buf, vshape, tables_sa, page_pos_sa, layer, page_seg_sa)
            if packed:
                kc, vc, slot_pos_ca, slot_seg_ca = BA.attn_gather(
                    buf, views["cross_attn"], tables_ca, page_pos_ca,
                    layer, page_seg_ca)
            else:
                kc, vc = A.gather_pages(buf, views["cross_attn"], tables_ca,
                                        layer)
            # --- causal self attention (paged, fresh KV merged from registers)
            xn = layer_norm(x, ps["ln_w"], ps["ln_b"], eps)
            q = dense(xn, ps["q"], ps["q_bias"]).reshape(b, t, -1, cfg.head_dim)
            k = dense(xn, ps["k"]).reshape(b, t, ri["kv_local"], cfg.head_dim)
            v = dense(xn, ps["v"], ps["v_bias"]).reshape(
                b, t, ri["kv_local"], cfg.head_dim)
            q = A.group_q(q, ri["kv_local"])
            s = k_all.shape[1]
            chunk_start = (batch.chunk_start if packed
                           else positions[:, :1])
            if prefill or packed:
                from .blocks_attn import _prefill_flash
                o, m, l = _prefill_flash(q, k_all, v_all, slot_pos,
                                         positions,
                                         chunk_start=chunk_start,
                                         window=0, q_seg=batch.seg_ids,
                                         kv_seg=slot_seg)
            else:
                mask = slot_pos[:, None, :] < chunk_start[:, :, None]
                o, m, l = A.attend_tokens(q, k_all, v_all, mask)
            if kv_groups is not None:
                o, m, l = A.combine_partials(o, m, l, dist.tp_axis,
                                             groups=kv_groups)
            # fresh intra-chunk part
            if packed:
                mask_f = A.segment_mask(batch.seg_ids, positions,
                                        batch.seg_ids, positions)
                of, mf, lf = A.attend_tokens(q, k, v, mask_f)
            elif t == 1:
                mask_f = jnp.ones((b, 1, 1), bool)
                of, mf, lf = A.attend_tokens(q, k, v, mask_f)
            elif t <= 256:
                mask_f = positions[:, None, :] <= positions[:, :, None]
                of, mf, lf = A.attend_tokens(q, k, v, mask_f)
            else:
                of, mf, lf = A.flash_attention_partials(q, k, v,
                                                        causal=True)
            o, m, l = A.merge_partials(o, m, l, of, mf, lf)
            out = A.finalize_softmax(o, l).reshape(b, t, -1)
            out = out.astype(x.dtype)
            y = psum_tp(dense(out, ps["o"]), dist)
            x = x + y + ps["o_bias"].astype(y.dtype)
            # --- cross attention (pre-gathered encoder KV)
            xn = layer_norm(x, pc["ln_w"], pc["ln_b"], eps)
            q = dense(xn, pc["q"], pc["q_bias"]).reshape(b, t, -1, cfg.head_dim)
            q = A.group_q(q, ri["kv_local"])
            sc = kc.shape[1]
            if use_kernel:
                # kernel zeroes fully-masked rows (enc_lens == 0) exactly,
                # matching the explicit zero guard of the ref path below
                out = BA.packed_cross_attn_kernel(
                    q, kc, vc, slot_pos_ca, slot_seg_ca, batch.seg_ids,
                    batch.enc_lens)
                out = out.reshape(b, t, -1).astype(x.dtype)
            else:
                if packed:
                    # enc_lens is per TOKEN; slot_pos_ca carries each flat
                    # cross slot's encoder position, slot_seg_ca its segment
                    mask = (slot_seg_ca[:, None, :]
                            == batch.seg_ids[:, :, None]) \
                        & (slot_pos_ca[:, None, :]
                           < batch.enc_lens[:, :, None])
                else:
                    mask = jnp.broadcast_to(
                        (jnp.arange(sc)[None]
                         < batch.enc_lens[:, None])[:, None],
                        (b, t, sc))
                o, m, l = A.attend_tokens(q, kc, vc, mask)
                out = A.finalize_softmax(o, l).reshape(b, t, -1)
                out = out.astype(x.dtype)
                if packed:
                    # all-masked rows degenerate to a uniform average over
                    # the WHOLE flat slot stream (other segments' values);
                    # a padded row would average its own zeroed pages
                    # instead — zero no-encoder tokens explicitly so the
                    # layouts agree
                    out = out * (batch.enc_lens > 0)[..., None].astype(
                        out.dtype)
            y = psum_tp(dense(out, pc["o"]), dist)
            x = x + y + pc["o_bias"].astype(y.dtype)
            x = self._mlp(pm, x, eps)
            # WRITE phase: stream this step's self-attn KV
            buf = A.write_token_kv(buf, vshape, layer, write_sa,
                                   positions % tpp, k, v)
            return (x, buf), None

        (x, buffer), _ = jax.lax.scan(
            body, (x, buffer),
            ((params["dec_self"], params["dec_cross"], params["dec_mlp"]),
             jnp.arange(cfg.num_layers)))
        x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], eps)
        if packed:
            x = jnp.take(x[0], batch.seg_last_tok, axis=0)[:, None]
        elif batch.last_idx is not None:
            x = jnp.take_along_axis(
                x, batch.last_idx[:, None, None].astype(jnp.int32), axis=1)
        else:
            x = x[:, -1:]
        logits = logits_local(x, params["embed"])[:, 0]
        logits = mask_pad_vocab(logits, cfg.vocab_size, dist)
        return logits, buffer
