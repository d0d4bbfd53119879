"""Decoder-only LM (dense / MoE / SWA-mix / VLM backbones).

One shard_map over the whole mesh per step function (train / prefill /
decode); layers run under ``lax.scan`` over stacked params (one compiled
layer body regardless of depth — essential for 512-device dry-run compiles).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.layout import as_rows
from ..core.spec import KVCacheSpec, attention_spec
from . import attention as A
from . import blocks_attn as BA
from .common import rms_norm
from ..kernels.paged_attention.kernel import LANES, eligible
from .params import PD, init_params, param_specs, param_struct
from .rotary import mrope_positions as _mrope3
from .tp import (Dist, embed_lookup, expand_gqa_kv, expand_gqa_o,
                 expand_gqa_q, gather_logits, logits_local, mask_pad_vocab,
                 psum_dp, psum_tp, replica_info, sharded_softmax_xent)


@dataclasses.dataclass
class DecodeBatch:
    """One serving step's device inputs. Two layouts share this container:

    * padded — one row per sequence, (B, T) padded to the longest chunk;
      the packed fields below are all None.
    * packed — ALL sequences flattened into one (1, TT) token stream with
      per-token segment ids; rows of per-type page tables are likewise
      flattened into one stream with per-page owning segments. T/TT are
      interchangeable in the shape comments below.
    """
    tokens: Any            # (B, T) i32
    positions: Any         # (B, T) i32 absolute positions of the new tokens
    seq_lens: Any          # (B,) i32 total kv length after this step
    tables: Dict[str, Any]       # type -> (S, B_loc, P) i32
    page_pos: Dict[str, Any]     # type -> (S, B_loc, P) i32
    write_eids: Dict[str, Any]   # type -> (S, B_loc, T) i32 (<0 drop)
    state_eids: Dict[str, Any]   # type -> (S, B_loc) i32
    mm_embeds: Any = None        # (B, T, d) prefilled vision embeddings
    mm_mask: Any = None          # (B, T) bool
    mrope_pos: Any = None        # (3, B, T)
    last_idx: Any = None         # (B,) index of last valid token (prefill)
    enc_embeds: Any = None       # (B, S_enc, d) enc-dec stub frontend
    enc_write_eids: Any = None   # (S, B_loc, S_enc)
    enc_lens: Any = None         # (B,) — packed: (1, TT) per token
    # ---- packed-stream fields (None in the padded layout) ----
    seg_ids: Any = None          # (1, TT) i32 segment id per token (-1 pad)
    chunk_start: Any = None      # (1, TT) i32 chunk-start position per token
    seg_start_tok: Any = None    # (1, TT) i32 stream idx of segment's first tok
    seg_last_tok: Any = None     # (N_seg,) i32 stream idx of segment's last tok
    page_seg: Any = None         # type -> (S, B_loc, P) i32 owning segment
    page_start: Any = None       # type -> (S, B_loc, N_seg) i32 first page
    page_count: Any = None       # type -> (S, B_loc, N_seg) i32 of each seg


jax.tree_util.register_dataclass(
    DecodeBatch,
    data_fields=["tokens", "positions", "seq_lens", "tables", "page_pos",
                 "write_eids", "state_eids", "mm_embeds", "mm_mask",
                 "mrope_pos", "last_idx", "enc_embeds", "enc_write_eids",
                 "enc_lens", "seg_ids", "chunk_start", "seg_start_tok",
                 "seg_last_tok", "page_seg", "page_start", "page_count"],
    meta_fields=[])


def _dp_spec(dist: Dist):
    return tuple(dist.dp_axes) if len(dist.dp_axes) > 1 else dist.dp_axes[0]


class DecoderLM:
    family_handles = ("dense", "moe", "vlm")

    def __init__(self, cfg: ModelConfig, dist: Dist):
        cfg.validate()
        self.cfg = cfg
        self.dist = dist
        tp = dist.tp
        self.ri = replica_info(cfg.num_heads, cfg.num_kv_heads, tp)
        self.v_local = -(-cfg.vocab_size // tp)
        self.v_pad = self.v_local * tp
        self.period = len(cfg.attn_pattern)
        assert cfg.num_layers % self.period == 0, (cfg.num_layers, self.period)
        self.cycles = cfg.num_layers // self.period
        kinds = cfg.attn_kind_per_layer
        self.period_kinds = kinds[: self.period]
        self.cnt = {
            "full": self.period_kinds.count("full"),
            "swa": self.period_kinds.count("swa"),
        }
        # rank of each period slot within its kind
        self.rank_in_period = []
        seen = {"full": 0, "swa": 0}
        for k in self.period_kinds:
            self.rank_in_period.append(seen[k])
            seen[k] += 1
        self.is_moe = cfg.num_experts > 0
        # FSDP: shard stacked layer weights over "data"; per-layer all_gather
        # in the scan body (transpose = reduce_scatter of grads = ZeRO-2).
        self.fsdp = bool(dist.fsdp) and dist.mesh.shape["data"] > 1
        self._fsdp_dims: Dict[str, int] = {}

    # ----------------------------------------------------------- kv specs
    # Prefix for KV type names — lets several models (speculative decoding
    # draft + target, §6.1) share one Jenga pool without name collisions.
    kv_prefix = ""

    def kv_type_of_kind(self, kind: str) -> str:
        return self.kv_prefix + ("full_attn" if kind == "full" else "swa")

    def kv_specs(self) -> Tuple[KVCacheSpec, ...]:
        cfg, ri = self.cfg, self.ri
        out = []
        n_full = self.cnt["full"] * self.cycles
        n_swa = self.cnt["swa"] * self.cycles
        if n_full:
            out.append(attention_spec(
                self.kv_prefix + "full_attn", num_layers=n_full,
                kv_heads=ri["kv_local"], head_dim=cfg.head_dim,
                tokens_per_page=cfg.tokens_per_page))
        if n_swa:
            out.append(attention_spec(
                self.kv_prefix + "swa", num_layers=n_swa,
                kv_heads=ri["kv_local"], head_dim=cfg.head_dim,
                tokens_per_page=cfg.tokens_per_page,
                kind="swa", sliding_window=cfg.sliding_window))
        return tuple(out)

    def page_shapes(self) -> Dict[str, Tuple[int, ...]]:
        cfg, ri = self.cfg, self.ri
        shp = (2, cfg.tokens_per_page, ri["kv_local"], cfg.head_dim)
        out = {}
        if self.cnt["full"]:
            out[self.kv_prefix + "full_attn"] = shp
        if self.cnt["swa"]:
            out[self.kv_prefix + "swa"] = shp
        return out

    def attends_in_place(self, attention_impl: str, lanes: int) -> bool:
        """Whether the PACKED serve step reads its KV pages in place
        through ``kernels.paged_attention`` (``blocks_attn.attn_paged``):
        the kernel impl, a single shard of each KV head (no replica groups,
        no sequence parallelism), whole 128-lane K/V rows per token and a
        head_dim of 64 or 128. Everything else takes the gather + ref
        path. ``lanes``: the unified buffer's minor dimension."""
        return (attention_impl == "kernel" and self.ri["repl"] == 1
                and not self.dist.sp and lanes == LANES
                and eligible(self.ri["kv_local"], self.cfg.head_dim))

    # ----------------------------------------------------------- template
    def template(self):
        cfg, dist, ri = self.cfg, self.dist, self.ri
        tp = dist.tp
        d, hd = cfg.d_model, cfg.head_dim
        L = cfg.num_layers
        ffl = cfg.d_ff // tp

        def stack(key_shape_fn, n=L):
            """Layer-stacked custom init."""
            def fn(key):
                keys = jax.random.split(key, n)
                return jnp.stack([key_shape_fn(k) for k in keys])
            return fn

        qfn = lambda k: expand_gqa_q(k, d, cfg.num_heads, cfg.num_kv_heads, hd, tp)
        kvfn = lambda k: expand_gqa_kv(k, d, cfg.num_kv_heads, hd, tp)
        ofn = lambda k: expand_gqa_o(k, d, cfg.num_heads, cfg.num_kv_heads, hd, tp,
                                     scale=0.02 / (2 * L) ** 0.5)
        layers = {
            "attn_norm": PD((L, d), P(), init="ones"),
            "q": PD((L, tp, d, ri["q_local"] * hd), P(None, "model"),
                    init="custom", fn=stack(qfn)),
            "k": PD((L, tp, d, ri["kv_local"] * hd), P(None, "model"),
                    init="custom", fn=stack(kvfn)),
            "v": PD((L, tp, d, ri["kv_local"] * hd), P(None, "model"),
                    init="custom", fn=stack(kvfn)),
            "o": PD((L, tp, ri["q_local"] * hd, d), P(None, "model"),
                    init="custom", fn=stack(ofn)),
            "mlp_norm": PD((L, d), P(), init="ones"),
        }
        if cfg.qkv_bias:
            layers["q_bias"] = PD((L, tp, ri["q_local"] * hd), P(None, "model"),
                                  init="zeros")
            layers["k_bias"] = PD((L, tp, ri["kv_local"] * hd), P(None, "model"),
                                  init="zeros")
            layers["v_bias"] = PD((L, tp, ri["kv_local"] * hd), P(None, "model"),
                                  init="zeros")
        if self.is_moe:
            # 2-D expert sharding: experts over "data" (EP all_to_all),
            # per-expert FFN over "model" (expert-TP) — fits 100B+ MoEs.
            ffe = cfg.moe_d_ff
            ep_spec = P(None, "data", None, "model")
            layers.update({
                "router": PD((L, d, cfg.num_experts), P()),
                "moe_gate": PD((L, cfg.num_experts, d, ffe), ep_spec),
                "moe_up": PD((L, cfg.num_experts, d, ffe), ep_spec),
                "moe_down": PD((L, cfg.num_experts, ffe, d),
                               P(None, "data", "model"),
                               scale=0.02 / (2 * L) ** 0.5),
            })
        else:
            layers.update({
                "gate": PD((L, tp, d, ffl), P(None, "model")),
                "up": PD((L, tp, d, ffl), P(None, "model")),
                "down": PD((L, tp, ffl, d), P(None, "model"),
                           scale=0.02 / (2 * L) ** 0.5),
            })
        if self.fsdp:
            data = self.dist.mesh.shape["data"]
            for name, pd in layers.items():
                if len(pd.spec) >= 2 and pd.spec[1] == "model" and \
                        len(pd.shape) >= 3:
                    for i in range(2, len(pd.shape)):
                        if pd.shape[i] % data == 0 and pd.shape[i] >= data:
                            spec = list(pd.spec) + [None] * (
                                len(pd.shape) - len(pd.spec))
                            spec[i] = "data"
                            layers[name] = dataclasses.replace(
                                pd, spec=P(*spec))
                            # dim index after scan-slice (drop L) + tp squeeze
                            self._fsdp_dims[name] = i - 2
                            break
        tmpl = {
            "embed": PD((tp, self.v_local, d), P("model")),
            "final_norm": PD((d,), P(), init="ones"),
            "layers": layers,
        }
        if not self.cfg.tie_embeddings:
            tmpl["unembed"] = PD((tp, self.v_local, d), P("model"))
        return tmpl

    # Optional dtype override for float params (serving uses bf16 weights;
    # training keeps fp32 masters). Set via ``model.param_dtype = ...``.
    param_dtype = None

    def _retype(self, tmpl):
        if self.param_dtype is None:
            return tmpl
        from .common import PARAM_DTYPE
        from .params import is_pd

        def go(pd):
            if pd.dtype == PARAM_DTYPE:
                return dataclasses.replace(pd, dtype=self.param_dtype)
            return pd

        return jax.tree.map(go, tmpl, is_leaf=is_pd)

    def init(self, seed=0):
        return init_params(self._retype(self.template()), seed)

    def struct(self):
        return param_struct(self._retype(self.template()))

    def specs(self):
        return param_specs(self.template())

    # ------------------------------------------------------------ helpers
    def _squeeze_params(self, params):
        """Drop the (local size-1) tp dim from expanded-layout params.
        MoE / FSDP leaves shard real dims over "model"/"data" — those local
        dims are > 1 and stay."""
        specs = self.specs()

        def go(a, s):
            for i, ax in enumerate(s):
                if ax == "model" and a.shape[i] == 1:
                    return jnp.squeeze(a, axis=i)
            return a

        return jax.tree.map(go, params, specs)

    def _fsdp_gather(self, pj):
        """FSDP: all_gather the weight shards of one layer before use.

        Perf hillclimb (EXPERIMENTS.md #A1): gather in bf16 — compute casts
        weights to bf16 anyway, so casting BEFORE the gather is lossless for
        the step math and halves FSDP's dominant collective bytes."""
        if not self._fsdp_dims:
            return pj
        out = dict(pj)
        for name, dim in self._fsdp_dims.items():
            if name in out:
                w = out[name]
                if w.dtype == jnp.float32:
                    w = w.astype(jnp.bfloat16)
                out[name] = jax.lax.all_gather(w, "data", axis=dim,
                                               tiled=True)
        return out

    def _unembed(self, params):
        return params.get("unembed", params["embed"])

    def _stacked(self, p_layers):
        """(L, ...) -> (cycles, period, ...) for scan xs."""
        return jax.tree.map(
            lambda a: a.reshape(self.cycles, self.period, *a.shape[1:]),
            p_layers)

    # --------------------------------------------------------------- train
    def train_loss(self, params, tokens, targets, *, mm_embeds=None,
                   mm_mask=None, mrope_pos=None):
        """Global arrays in; replicated scalar loss out."""
        cfg, dist = self.cfg, self.dist
        dp = _dp_spec(dist)
        in_specs = (self.specs(), P(dp), P(dp))
        args = [params, tokens, targets]
        extra_specs = []
        if cfg.family == "vlm" and mm_embeds is not None:
            extra_specs = [P(dp), P(dp), P(None, dp)]
            args += [mm_embeds, mm_mask, mrope_pos]
        fn = jax.shard_map(
            partial(self._train_body, has_mm=bool(extra_specs)),
            mesh=dist.mesh,
            in_specs=tuple(in_specs) + tuple(extra_specs),
            out_specs=P(),
            check_vma=False,
        )
        return fn(*args)

    def _train_body(self, params, tokens, targets, *mm, has_mm=False):
        cfg, dist = self.cfg, self.dist
        params = self._squeeze_params(params)
        b, t = tokens.shape
        x = embed_lookup(tokens, params["embed"], dist)
        mrope_pos = None
        if has_mm:
            mm_embeds, mm_mask, mrope_pos = mm
            x = jnp.where(mm_mask[..., None], mm_embeds.astype(x.dtype), x)
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        stacked = self._stacked(params["layers"])

        def cycle_body(carry, xs):
            x, aux = carry
            layer_params = xs
            for j, kind in enumerate(self.period_kinds):
                pj = self._fsdp_gather(jax.tree.map(lambda a: a[j],
                                                    layer_params))
                window = cfg.sliding_window if kind == "swa" else 0
                x = BA.attn_train(
                    pj, x, dist, kv_local=self.ri["kv_local"],
                    head_dim=cfg.head_dim, window=window,
                    rope_theta=cfg.rope_theta, positions=positions,
                    mrope_positions=mrope_pos, norm_eps=cfg.norm_eps)
                if self.is_moe:
                    x, a = BA.moe_block(
                        pj, x, dist, num_experts=cfg.num_experts,
                        top_k=cfg.experts_per_token,
                        capacity_factor=cfg.capacity_factor,
                        norm_eps=cfg.norm_eps,
                        aux_weight=cfg.router_aux_weight)
                    aux = aux + a
                else:
                    x = BA.mlp_block(pj, x, dist, cfg.norm_eps)
            return (x, aux), None

        cycle_body = jax.checkpoint(cycle_body)
        (x, aux), _ = jax.lax.scan(cycle_body, (x, jnp.float32(0.0)), stacked)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = logits_local(x, self._unembed(params))
        loss = sharded_softmax_xent(logits, targets, dist)
        loss = psum_dp(loss, dist) / dist.dp
        aux = psum_dp(aux / max(1, self.cycles), dist) / dist.dp
        return loss + aux

    # --------------------------------------------------------------- serve
    def serve_step(self, params, buffer, batch: DecodeBatch, *,
                   prefill: bool, attention_impl: str = "ref"):
        """One serving step over a MIXED batch: rows are independent
        sequences with ragged per-row token counts (concurrent prefill
        chunks and single-token decodes share the dispatch). Correctness is
        carried by per-row data, not a global phase: absolute ``positions``
        (SENTINEL at padded slots — never attended), per-row chunk starts
        for the old-page mask, ``last_idx`` to pick each row's logits, and
        negative ``write_eids`` to drop padded writes. The ``prefill`` flag
        only selects the kernel schedule (chunked flash vs materialized
        T=1 decode), never the masking semantics.

        PACKED layout (``batch.seg_ids`` is not None): the whole step is one
        (1, TT) token stream; per-token/per-segment arrays are replicated
        across the dp axis and logits come back one row PER SEGMENT (in
        plan order) instead of per batch row.

        Returns (logits (B or N_seg, V_pad), buffer)."""
        cfg, dist = self.cfg, self.dist
        dp = _dp_spec(dist)
        sp = dist.sp
        packed = batch.seg_ids is not None
        bspec = P(None) if (sp or packed) else P(dp)
        shard_dim_spec = "data" if sp else dp
        batch_specs = DecodeBatch(
            tokens=bspec, positions=bspec, seq_lens=bspec,
            tables={k: P(shard_dim_spec, "model") for k in batch.tables},
            page_pos={k: P(shard_dim_spec, "model") for k in batch.page_pos},
            write_eids={k: P(shard_dim_spec, "model")
                        for k in batch.write_eids},
            state_eids={k: P(shard_dim_spec) for k in batch.state_eids},
            mm_embeds=bspec if batch.mm_embeds is not None else None,
            mm_mask=bspec if batch.mm_mask is not None else None,
            mrope_pos=P(None, *([None] if (sp or packed) else [dp])) if batch.mrope_pos is not None else None,
            last_idx=bspec if batch.last_idx is not None else None,
            enc_embeds=bspec if batch.enc_embeds is not None else None,
            enc_write_eids=(P(shard_dim_spec, "model")
                            if batch.enc_write_eids is not None else None),
            enc_lens=bspec if batch.enc_lens is not None else None,
            seg_ids=bspec if packed else None,
            chunk_start=bspec if packed else None,
            seg_start_tok=bspec if packed else None,
            seg_last_tok=P(None) if packed else None,
            page_seg=({k: P(shard_dim_spec, "model") for k in batch.page_seg}
                      if packed else None),
            page_start=({k: P(shard_dim_spec, "model")
                         for k in batch.page_start} if packed else None),
            page_count=({k: P(shard_dim_spec, "model")
                         for k in batch.page_count} if packed else None),
        )
        buf_spec = P(shard_dim_spec, "model")
        out_logit_spec = (P(None, "model") if (sp or packed)
                          else P(dp, "model"))
        body = partial(self._serve_body, prefill=prefill,
                       attention_impl=attention_impl)
        kv_specs = self.kv_specs()

        def on_rows(params, buf, batch):
            # every serve body reads and writes the local unified buffer
            # as its (rows, lanes) view (core.layout) and returns that view
            logits, rows = body(params, as_rows(buf, kv_specs), batch)
            return logits, rows.reshape(buf.shape)

        fn = jax.shard_map(
            on_rows,
            mesh=dist.mesh,
            in_specs=(self.specs(), buf_spec, batch_specs),
            out_specs=(out_logit_spec, buf_spec),
            check_vma=False,
        )
        return fn(params, buffer, batch)

    def _layer_views(self, buffer):
        """Per-type reshape views of the unified buffer (paper Fig. 7c):
        type t sees (total_units // S_t, num_layers_t, *page_shape)."""
        specs = self.kv_specs()
        shapes = self.page_shapes()
        total = buffer.size
        views = {}
        for s in specs:
            assert total % s.page_units == 0, (
                f"buffer ({total}u) must be a multiple of every small-page "
                f"size (LCM geometry); {s.name} page = {s.page_units}u")
            vp = total // s.page_units
            views[s.name] = (vp, s.num_layers) + shapes[s.name]
        return views

    def _serve_body(self, params, buffer, batch: DecodeBatch, *, prefill,
                    attention_impl="ref"):
        cfg, dist = self.cfg, self.dist
        params = self._squeeze_params(params)
        tokens = batch.tokens
        b, t = tokens.shape
        positions = batch.positions
        x = embed_lookup(tokens, params["embed"], dist)
        mrope_pos = batch.mrope_pos
        if batch.mm_embeds is not None:
            x = jnp.where(batch.mm_mask[..., None],
                          batch.mm_embeds.astype(x.dtype), x)
        views = self._layer_views(buffer)
        stacked = self._stacked(params["layers"])
        sq = lambda a: jnp.squeeze(a, axis=(0, 1))         # drop shard dims
        tables = {k: sq(v) for k, v in batch.tables.items()}
        page_pos = {k: sq(v) for k, v in batch.page_pos.items()}
        write_eids = {k: sq(v) for k, v in batch.write_eids.items()}
        packed = batch.seg_ids is not None
        page_seg = ({k: sq(v) for k, v in batch.page_seg.items()}
                    if packed else {})
        sp_axis = "data" if dist.sp else None
        ri = self.ri
        kv_groups = (None if ri["repl"] == 1 else
                     A.replica_groups(ri["kv_tp"], ri["repl"]))

        in_place = packed and self.attends_in_place(attention_impl,
                                                    buffer.shape[-1])
        if in_place:
            groups = ri["q_local"] // ri["kv_local"]
            page_ids = {k: v[0] for k, v in tables.items()}
            work = {}
            for kind in set(self.period_kinds):
                tname = self.kv_type_of_kind(kind)
                work[tname] = BA.paged_work(
                    views[tname], groups, batch.seg_ids, positions,
                    sq(batch.page_start[tname])[0],
                    sq(batch.page_count[tname])[0],
                    window=cfg.sliding_window if kind == "swa" else 0)

        def ffn(pj, x):
            if self.is_moe:
                x, _ = BA.moe_block(
                    pj, x, dist, num_experts=cfg.num_experts,
                    top_k=cfg.experts_per_token,
                    capacity_factor=cfg.capacity_factor,
                    norm_eps=cfg.norm_eps)
                return x
            return BA.mlp_block(pj, x, dist, cfg.norm_eps)

        def cycle_in_place(carry, xs):
            # each layer writes its own K/V rows, then reads them in place
            x, buf = carry
            layer_params, cycle = xs
            for j, kind in enumerate(self.period_kinds):
                pj = self._fsdp_gather(jax.tree.map(lambda a: a[j],
                                                    layer_params))
                tname = self.kv_type_of_kind(kind)
                layer_in_type = cycle * self.cnt[kind] + self.rank_in_period[j]
                x, buf = BA.attn_paged(
                    pj, x, buf, views[tname], dist, layer=layer_in_type,
                    kv_local=ri["kv_local"], head_dim=cfg.head_dim,
                    positions=positions, write_eids=write_eids[tname],
                    page_ids=page_ids[tname], work=work[tname],
                    window=cfg.sliding_window if kind == "swa" else 0,
                    rope_theta=cfg.rope_theta, mrope_positions=mrope_pos,
                    norm_eps=cfg.norm_eps)
                x = ffn(pj, x)
            return (x, buf), None

        def cycle_body(carry, xs):
            x, buf = carry
            layer_params, cycle = xs
            # phase 1: ALL gathers (buffer reads) before any write — keeps
            # the pool carry in-place (EXPERIMENTS.md buffer-copy study)
            gathered = []
            for j, kind in enumerate(self.period_kinds):
                tname = self.kv_type_of_kind(kind)
                layer_in_type = cycle * self.cnt[kind] + self.rank_in_period[j]
                gathered.append(BA.attn_gather(
                    buf, views[tname], tables[tname], page_pos[tname],
                    layer_in_type, page_seg.get(tname)))
            writes = []
            for j, kind in enumerate(self.period_kinds):
                pj = self._fsdp_gather(jax.tree.map(lambda a: a[j],
                                                    layer_params))
                tname = self.kv_type_of_kind(kind)
                layer_in_type = cycle * self.cnt[kind] + self.rank_in_period[j]
                window = cfg.sliding_window if kind == "swa" else 0
                x, k, v = BA.attn_compute(
                    pj, x, gathered[j], dist,
                    kv_local=self.ri["kv_local"], head_dim=cfg.head_dim,
                    positions=positions, seq_lens=batch.seq_lens,
                    window=window, rope_theta=cfg.rope_theta,
                    mrope_positions=mrope_pos, norm_eps=cfg.norm_eps,
                    prefill=prefill, sp_axis=sp_axis, kv_groups=kv_groups,
                    seg_ids=batch.seg_ids, chunk_start=batch.chunk_start)
                writes.append((tname, layer_in_type, k, v))
                x = ffn(pj, x)
            # phase 3: all writes at the end of the iteration
            for tname, layer_in_type, k, v in writes:
                buf = BA.attn_write(buf, views[tname], layer_in_type,
                                    write_eids[tname], positions, k, v)
            return (x, buf), None

        (x, buffer), _ = jax.lax.scan(
            cycle_in_place if in_place else cycle_body, (x, buffer),
            (stacked, jnp.arange(self.cycles)))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if packed:
            # one logits row per SEGMENT: its last token in the stream
            x = jnp.take(x[0], batch.seg_last_tok, axis=0)[:, None]
        elif batch.last_idx is not None:
            x = jnp.take_along_axis(
                x, batch.last_idx[:, None, None].astype(jnp.int32), axis=1)
        else:
            x = x[:, -1:]
        logits = logits_local(x, self._unembed(params))[:, 0]  # (B, V_loc)
        logits = mask_pad_vocab(logits, cfg.vocab_size, dist)
        return logits, buffer
