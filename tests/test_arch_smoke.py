"""Per-architecture smoke tests: reduced same-family config, one forward
train step + serve prefill/decode on CPU; asserts shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.spec import lcm as _lcm
from repro.models.lm import DecodeBatch
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.serving.engine import serving_params

ARCH_IDS = sorted(ARCHS)


def buffer_for(model, min_units=1 << 20):
    """Unified buffer sized as a multiple of the LCM page (geometry rule)."""
    big = _lcm([s.page_units for s in model.kv_specs()])
    units = -(-min_units // big) * big
    return jnp.zeros((1, 1, units), jnp.bfloat16)


def make_serve_batch(model, cfg, B, T, n_tokens, *, prefill, buffer_units,
                     enc_seq=0):
    """Hand-rolled page tables with DISJOINT unit ranges per type (the real
    Jenga allocator guarantees this; here we emulate with a unit cursor)."""
    tpp = cfg.tokens_per_page
    specs = {s.name: s for s in model.kv_specs()}
    tables, page_pos, write_eids, state_eids = {}, {}, {}, {}
    n_pages = -(-n_tokens // tpp)
    cursor = 0  # unit offset; each type's pages start at the next S_t boundary

    def take(s, count):
        nonlocal cursor
        start = -(-cursor // s.page_units)
        cursor = (start + count) * s.page_units
        assert cursor <= buffer_units, (s.name, cursor, buffer_units)
        return jnp.arange(start, start + count, dtype=jnp.int32)

    for name, s in specs.items():
        if s.kind in ("mamba", "rwkv"):
            state_eids[name] = take(s, B)[None]
            continue
        if s.kind == "cross_attn":
            npg = -(-enc_seq // tpp)
            tables[name] = take(s, B * npg).reshape(1, 1, B, npg)
            page_pos[name] = jnp.broadcast_to(
                (jnp.arange(npg, dtype=jnp.int32) * tpp)[None, None, None],
                (1, 1, B, npg))
            write_eids[name] = jnp.repeat(
                tables[name], tpp, axis=3)[:, :, :, :enc_seq]
            continue
        tables[name] = take(s, B * n_pages).reshape(1, 1, B, n_pages)
        page_pos[name] = jnp.broadcast_to(
            (jnp.arange(n_pages, dtype=jnp.int32) * tpp)[None, None, None],
            (1, 1, B, n_pages))
    if prefill:
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    else:
        pos = jnp.full((B, 1), n_tokens - 1, jnp.int32)
    for name in tables:
        if name == "cross_attn" and enc_seq:
            continue
        write_eids[name] = jnp.take_along_axis(
            tables[name][0, 0], pos // tpp, axis=1)[None, None]
    kw = {}
    if cfg.family == "encdec":
        kw["enc_lens"] = jnp.full((B,), enc_seq, jnp.int32)
        if prefill:
            kw["enc_embeds"] = jnp.zeros((B, enc_seq, cfg.d_model),
                                         jnp.float32) + 0.1
            ew = tables["cross_attn"][0, 0]
            kw["enc_write_eids"] = jnp.repeat(
                ew, tpp, axis=1)[:, :enc_seq][None, None]
    if cfg.family == "vlm" and prefill:
        kw["mm_embeds"] = jnp.full((B, T, cfg.d_model), 0.05, jnp.float32)
        kw["mm_mask"] = (jnp.arange(T)[None] < 2).repeat(B, 0)
        kw["mrope_pos"] = jnp.stack([pos] * 3)
    batch = DecodeBatch(
        tokens=(jnp.arange(B * (T if prefill else 1), dtype=jnp.int32)
                .reshape(B, -1) % cfg.vocab_size),
        positions=pos,
        seq_lens=jnp.full((B,), n_tokens, jnp.int32),
        tables=tables, page_pos=page_pos, write_eids=write_eids,
        state_eids=state_eids,
        last_idx=jnp.full((B,), T - 1, jnp.int32) if prefill else None,
        **kw)
    return batch


def train_loss_fn(model, cfg, B=2, T=16):
    """Jitted params -> train loss on a fixed (B, T) batch."""
    tokens = (jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
              % cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    kw = {}
    if cfg.family == "encdec":
        kw["enc_embeds"] = jnp.full((B, cfg.encoder_seq, cfg.d_model), 0.1,
                                    jnp.float32)
    if cfg.family == "vlm":
        kw["mm_embeds"] = jnp.full((B, T, cfg.d_model), 0.05, jnp.float32)
        kw["mm_mask"] = (jnp.arange(T)[None] < 2).repeat(B, 0)
        kw["mrope_pos"] = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, None], (3, B, T))
    return jax.jit(lambda p: model.train_loss(p, tokens, targets, **kw))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = reduced(ARCHS[arch])
    dist = single_device_dist()
    model = build_model(cfg, dist)
    params = model.init(0)
    loss = train_loss_fn(model, cfg)(params)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), (arch, float(loss))
    assert 1.0 < float(loss) < 20.0, (arch, float(loss))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serving_params_cast_only_matmul_weights(arch):
    """``serving_params`` stores in bf16 only leaves the model rounds to
    bf16 anyway: with every leaf moved off the bf16 grid (zero/one inits
    included), the forward pass is bit-identical on the fp32 masters and
    on the served tree, while casting every leaf does change it. The cast
    leaves hold most of the bytes (the point of serving them in bf16)."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    leaves, treedef = jax.tree.flatten(model.init(0))
    rng = np.random.default_rng(0)
    params = jax.tree.unflatten(treedef, [
        a + jnp.asarray(0.1 * rng.standard_normal(a.shape), a.dtype)
        * (1 + jnp.abs(a)) for a in leaves])
    loss = train_loss_fn(model, cfg)
    served = serving_params(params)
    assert float(loss(served)) == float(loss(params))
    all_cast = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    assert float(loss(all_cast)) != float(loss(params))
    nbytes = {dt: sum(a.size for a in jax.tree.leaves(served)
                      if a.dtype == dt) for dt in (jnp.bfloat16, jnp.float32)}
    assert nbytes[jnp.bfloat16] > 0.8 * sum(nbytes.values()), nbytes


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_serve_prefill_decode(arch):
    cfg = reduced(ARCHS[arch])
    dist = single_device_dist()
    model = build_model(cfg, dist)
    params = model.init(0)
    B, T = 2, 12
    enc_seq = cfg.encoder_seq if cfg.family == "encdec" else 0
    buffer = buffer_for(model)
    U = buffer.shape[-1]
    pre = make_serve_batch(model, cfg, B, T, T, prefill=True,
                           buffer_units=U, enc_seq=enc_seq)
    logits, buffer = jax.jit(
        lambda p, b, ba: model.serve_step(p, b, ba, prefill=True)
    )(params, buffer, pre)
    assert logits.shape[0] == B
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    dec = make_serve_batch(model, cfg, B, 1, T + 1, prefill=False,
                           buffer_units=U, enc_seq=enc_seq)
    dlogits, buffer = jax.jit(
        lambda p, b, ba: model.serve_step(p, b, ba, prefill=False)
    )(params, buffer, dec)
    assert dlogits.shape[0] == B
    assert np.isfinite(np.asarray(dlogits, np.float32)).all(), arch


@pytest.mark.parametrize("arch", ["rwkv6-3b", "zamba2-1.2b"])
def test_recurrent_prefill_decode_consistency(arch):
    """Chunked prefill then N decode steps must equal one long prefill."""
    cfg = reduced(ARCHS[arch])
    dist = single_device_dist()
    model = build_model(cfg, dist)
    params = model.init(0)
    B, T = 1, 8
    U = buffer_for(model).shape[-1]
    toks = jnp.arange(T + 3, dtype=jnp.int32)[None] % cfg.vocab_size

    def prefill_upto(n):
        buffer = buffer_for(model)
        batch = make_serve_batch(model, cfg, B, n, n, prefill=True,
                                 buffer_units=U)
        batch = DecodeBatch(**{**batch.__dict__,
                               "tokens": toks[:, :n]})
        lg, buf = jax.jit(lambda p, b, ba: model.serve_step(
            p, b, ba, prefill=True))(params, buffer, batch)
        return lg, buf

    # long prefill of T+2 tokens -> logits predicting token T+2
    l_long, _ = prefill_upto(T + 2)
    # prefill T then decode 2 steps
    l, buf = prefill_upto(T)
    for i in range(2):
        n = T + i + 1
        dec = make_serve_batch(model, cfg, B, 1, n, prefill=False,
                               buffer_units=U)
        dec = DecodeBatch(**{**dec.__dict__, "tokens": toks[:, n - 1:n]})
        l, buf = jax.jit(lambda p, b, ba: model.serve_step(
            p, b, ba, prefill=False))(params, buf, dec)
    err = float(jnp.max(jnp.abs(l - l_long)))
    assert err < 0.25, (arch, err)
