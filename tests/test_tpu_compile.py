"""Compiles for a described TPU v5e at granite-3-2b's published widths.

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
from repro.models.blocks_attn import sparse_blocks
from repro.models.lm import DecodeBatch
from repro.models.registry import build_model
from repro.models.tp import Dist
from repro.serving.engine import serving_params

GRANITE = ARCHS["granite-3-2b"]
POOL_BYTES = 2 << 30


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


def pool_units():
    """Units of the runner's buffer for granite-3-2b and a 2 GiB pool."""
    model = build_model(GRANITE, Dist(mesh=jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2,
        devices=jax.devices()[:1])))
    specs = model.kv_specs()
    mgr = JengaKVCacheManager(specs, total_memory_bytes=POOL_BYTES)
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


def test_granite_serve_step_fits_one_chip(topo, monkeypatch):
    """The packed kernel-attention serve step at published widths, the
    params as engines serve them and a 2 GiB pool: updates the pool in place (output aliases
    the argument), copies no pool-sized temp, and holds the kernel."""
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=topo.devices[:1])
    model = build_model(GRANITE, Dist(mesh=mesh))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        jax.eval_shape(serving_params, model.struct()), model.specs())
    specs, units = pool_units()
    buf = jax.ShapeDtypeStruct(buffer_shape(specs, units), jnp.bfloat16,
                               sharding=rep)
    tt, segs, pages = 512, 8, 512

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    page = {"full_attn": i32(1, 1, 1, pages)}
    batch = DecodeBatch(
        tokens=i32(1, tt), positions=i32(1, tt), seq_lens=i32(segs),
        tables=page, page_pos=page, write_eids={"full_attn": i32(1, 1, 1, tt)},
        state_eids={}, seg_ids=i32(1, tt), chunk_start=i32(1, tt),
        seg_start_tok=i32(1, tt), seg_last_tok=i32(segs), page_seg=page)
    # the kernel picks compiled (not interpreted) mode from the backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = jax.jit(partial(model.serve_step, prefill=True,
                           attention_impl="kernel"), donate_argnums=(1,))
    compiled = step.lower(params, buf, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == units * 2
    assert mem.temp_size_in_bytes < 256 << 20
    assert "tpu_custom_call" in compiled.as_text()
