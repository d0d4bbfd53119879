"""Compiles for a described TPU v5e at granite-3-2b's and internlm2-1.8b's
published widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached (``v5e:2x2``; one chip's device is used). This
guards what interpret mode cannot see: tile-legal Pallas block shapes, the
unified buffer's padded bytes, and whether the serve step fits one chip.
The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests; the persistent compilation cache is
off around the compiles (entries written here could not be read back
without a chip).
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (AxisType, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import ARCHS
from repro.core.layout import buffer_shape
from repro.core.manager import JengaKVCacheManager
from repro.core.spec import lcm
from repro.kernels.flash_attention.kernel import flash_attention_varlen_tpu
from repro.kernels.paged_attention.kernel import paged_attention, plan_work
from repro.models.blocks_attn import (paged_pages_per_wave, paged_q_block,
                                      sparse_blocks)
from repro.models.lm import DecodeBatch
from repro.models.registry import build_model
from repro.models.tp import Dist
from repro.serving.engine import serving_params

GRANITE = ARCHS["granite-3-2b"]
INTERNLM2 = ARCHS["internlm2-1.8b"]
POOL_BYTES = 2 << 30
# the benchmark cell's pool (bench/configs/internlm2-1.8b.json) and the
# largest serve-step temp of the gather path there (512 tokens, 64
# segments, an 8192-page stream, compiled for v5e)
INTERNLM2_POOL_BYTES = 11274289152
GATHER_STEP_TEMP = 1085192704


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def pool_units(cfg=GRANITE, pool_bytes=POOL_BYTES):
    """Units of the runner's buffer for ``cfg`` and a pool of
    ``pool_bytes`` (2 GiB of granite-3-2b by default)."""
    model = build_model(cfg, Dist(mesh=jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2,
        devices=jax.devices()[:1])))
    specs = model.kv_specs()
    mgr = JengaKVCacheManager(specs, total_memory_bytes=pool_bytes)
    return specs, mgr.geometry.total_units + lcm(
        [s.page_units for s in specs])


@pytest.mark.parametrize("t,s", [(16, 528), (16, 4112), (256, 4096)])
def test_varlen_kernel_compiles_at_granite_widths(one_chip, t, s):
    """The packed-attention kernel lowers to a Mosaic custom call at
    32 query heads x head_dim 64 with the block sizes the serve path
    picks: a decode-sized bucket over a short and a long page stream
    (the short one sits at the 128-slot KV block floor), and a long
    prefill."""
    blk_q, blk_k = sparse_blocks(t, s)
    bh, d = GRANITE.num_heads, GRANITE.head_dim

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(partial(flash_attention_varlen_tpu, blk_q=blk_q,
                         blk_k=blk_k, interpret=False))
    compiled = fn.lower(
        sds((bh, t, d), jnp.bfloat16), sds((bh, s, d), jnp.bfloat16),
        sds((bh, s, d), jnp.bfloat16), sds((t,), jnp.int32),
        sds((s,), jnp.int32), sds((t,), jnp.int32),
        sds((s,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cfg", [INTERNLM2, GRANITE], ids=lambda c: c.name)
def test_paged_kernel_compiles_at_published_widths(one_chip, cfg):
    """The in-place paged kernel lowers to a Mosaic custom call, its VMEM
    and SMEM within the chip's, at a serve step of the benchmark's size:
    512 tokens, 256 segments, an 8192-page stream, a 7168-page pool."""
    t, s, p, vp, tpp = 512, 256, 8192, 7168, cfg.tokens_per_page
    kvl, g, d, nl = (cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads,
                     cfg.head_dim, cfg.num_layers)
    view = (vp, nl, 2, tpp, kvl, d)
    qb = paged_q_block(kvl, g)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, rows, ids, seg, pos, start, count, layer):
        work = plan_work(seg, pos, start, count, q_block=qb,
                         tokens_per_page=tpp)
        return paged_attention(q, rows, ids, work, layer, num_layers=nl,
                               tokens_per_page=tpp, q_block=qb,
                               pages_per_wave=paged_pages_per_wave(view))

    compiled = jax.jit(step).lower(
        sds((t, kvl, g, d), jnp.bfloat16),
        sds((vp * nl, 2 * tpp * kvl * d // 128, 128), jnp.bfloat16),
        sds((p,)), sds((t,)), sds((t,)), sds((s,)), sds((s,)),
        sds(())).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_unified_buffer_takes_nominal_bytes(one_chip):
    """The KV pool's device layout adds no padding: a 2 GiB granite pool
    allocates exactly its units x 2 bytes (a flat (1, 1, units) bf16
    array would take twice that)."""
    specs, units = pool_units()
    shape = buffer_shape(specs, units)
    alloc = jax.jit(lambda: jnp.zeros(shape, jnp.bfloat16),
                    out_shardings=one_chip)
    mem = alloc.lower().compile().memory_analysis()
    assert mem.output_size_in_bytes == units * 2


def compile_serve_step(topo, monkeypatch, cfg, pool_bytes, tt, segs, pages,
                       impl="kernel"):
    """The packed serve step at published widths, the params as engines
    serve them, compiled for one v5e. Returns (compiled, pool units)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=topo.devices[:1])
    model = build_model(cfg, Dist(mesh=mesh))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        jax.eval_shape(serving_params, model.struct()), model.specs())
    specs, units = pool_units(cfg, pool_bytes)
    buf = jax.ShapeDtypeStruct(buffer_shape(specs, units), jnp.bfloat16,
                               sharding=rep)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    page = {"full_attn": i32(1, 1, 1, pages)}
    seg = {"full_attn": i32(1, 1, 1, segs)}
    batch = DecodeBatch(
        tokens=i32(1, tt), positions=i32(1, tt), seq_lens=i32(segs),
        tables=page, page_pos=page, write_eids={"full_attn": i32(1, 1, 1, tt)},
        state_eids={}, seg_ids=i32(1, tt), chunk_start=i32(1, tt),
        seg_start_tok=i32(1, tt), seg_last_tok=i32(segs), page_seg=page,
        page_start=seg, page_count=seg)
    # the kernel picks compiled (not interpreted) mode from the backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = jax.jit(partial(model.serve_step, prefill=True,
                           attention_impl=impl), donate_argnums=(1,))
    return step.lower(params, buf, batch).compile(), units


def test_granite_serve_step_fits_one_chip(topo, monkeypatch):
    """The packed kernel-attention serve step at published widths, the
    params as engines serve them and a 2 GiB pool: updates the pool in
    place (output aliases the argument), copies no pool-sized temp, and
    holds the kernel."""
    compiled, units = compile_serve_step(topo, monkeypatch, GRANITE,
                                         POOL_BYTES, 512, 8, 512)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == units * 2
    assert mem.temp_size_in_bytes < 256 << 20
    assert "tpu_custom_call" in compiled.as_text()


def test_internlm2_inplace_step_keeps_pool_in_place(topo, monkeypatch):
    """The benchmark cell's serve step (internlm2-1.8b, its 10.5 GiB pool)
    on the in-place path at 512 tokens, 256 segments and an 8192-page
    stream: the pool is updated in place, the step's temp stays below the
    gather path's 1.09 GB with no pool-sized temp, and the paged
    kernel is there."""
    compiled, units = compile_serve_step(
        topo, monkeypatch, INTERNLM2, INTERNLM2_POOL_BYTES, 512, 256, 8192)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == units * 2
    assert mem.temp_size_in_bytes < min(GATHER_STEP_TEMP, 64 << 20)
    assert "tpu_custom_call" in compiled.as_text()
