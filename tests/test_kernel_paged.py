"""Paged decode attention kernel vs pure-jnp oracle: shape/dtype sweeps +
hypothesis property (page permutation invariance)."""
import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_decode_attention_ref


def make_case(b, kvl, g, d, tpp, n_pages, vp, seed=0, dtype=jnp.float32,
              window=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, kvl, g, d)), dtype)
    kv = jnp.asarray(rng.standard_normal((vp, 2, tpp, kvl, d)), dtype)
    # each seq: pages drawn without replacement from the pool
    tables = np.stack([rng.choice(vp, n_pages, replace=False)
                       for _ in range(b)]).astype(np.int32)
    page_pos = (np.arange(n_pages, dtype=np.int32) * tpp)[None].repeat(b, 0)
    positions = rng.integers(1, n_pages * tpp, b).astype(np.int32)
    return q, kv, jnp.asarray(tables), jnp.asarray(page_pos), \
        jnp.asarray(positions)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kvl,g,d,tpp,n_pages", [
    (2, 1, 4, 32, 8, 4),
    (3, 2, 2, 64, 16, 3),
    (1, 4, 1, 128, 8, 6),
])
def test_kernel_matches_ref_sweep(b, kvl, g, d, tpp, n_pages, dtype):
    case = make_case(b, kvl, g, d, tpp, n_pages, vp=n_pages * b + 3,
                     dtype=dtype)
    out_k = paged_decode_attention(*case, interpret=True)
    out_r = paged_decode_attention_ref(*case)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [4, 16])
def test_kernel_sliding_window(window):
    case = make_case(2, 1, 2, 32, 8, 5, vp=16, window=window)
    out_k = paged_decode_attention(*case, window=window, interpret=True)
    out_r = paged_decode_attention_ref(*case, window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), tpp=st.sampled_from([8, 16]),
       n_pages=st.integers(2, 6))
def test_page_id_permutation_invariance(seed, tpp, n_pages):
    """Jenga invariant: exec page ids are arbitrary — permuting which
    physical pages hold the data must not change attention output."""
    b, kvl, g, d = 2, 1, 2, 32
    vp = 24
    q, kv, tables, page_pos, positions = make_case(
        b, kvl, g, d, tpp, n_pages, vp, seed=seed)
    out1 = paged_decode_attention_ref(q, kv, tables, page_pos, positions)
    # move every page's content to a permuted slot; update tables
    rng = np.random.default_rng(seed + 1)
    perm = rng.permutation(vp)
    kv2 = jnp.asarray(np.asarray(kv)[np.argsort(perm)])
    tables2 = jnp.asarray(perm[np.asarray(tables)])
    out2k = paged_decode_attention(q, kv2, tables2, page_pos, positions,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2k),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("kvl,d", [(2, 16), (4, 64), (8, 64), (8, 120)])
def test_row_write_gather_match_flat_view(kvl, d):
    """``write_token_kv`` / ``gather_pages`` address the ``(rows, lanes)``
    buffer by whole rows — several per token where kv_heads * head_dim
    exceeds the lanes (2 and 4 rows here; 15 at the 64-lane width of
    8 x 120). Both must agree with the plain flat view
    ``(VP, L, 2, TPP, KVL, D)`` of the same units: dropped writes (eid < 0)
    land nowhere, and invalid table entries gather zeros."""
    from repro.core.layout import buffer_shape
    from repro.core.spec import attention_spec
    from repro.models import attention as A

    vp, nl, tpp, layer = 6, 3, 4, 1
    view_shape = (vp, nl, 2, tpp, kvl, d)
    units = int(np.prod(view_shape))
    spec = attention_spec("a", num_layers=nl, kv_heads=kvl, head_dim=d,
                          tokens_per_page=tpp)
    shape = buffer_shape([spec], units)
    rng = np.random.default_rng(0)
    flat = jnp.asarray(rng.standard_normal(units), jnp.float32)
    buf = flat.reshape(shape[-2:])
    eids = jnp.asarray([[0, 0, 3, -1], [5, 2, 2, 4]], jnp.int32)
    slots = jnp.asarray([[1, 2, 0, 3], [3, 0, 1, 2]], jnp.int32)
    k_new, v_new = (jnp.asarray(rng.standard_normal((2, 4, kvl, d)),
                                jnp.float32) for _ in range(2))
    out = A.write_token_kv(buf, view_shape, layer, eids, slots, k_new, v_new)
    want = flat.reshape(view_shape)
    ok = np.asarray(eids).reshape(-1) >= 0
    e, s = (np.asarray(a).reshape(-1)[ok] for a in (eids, slots))
    want = want.at[e, layer, 0, s].set(k_new.reshape(-1, kvl, d)[ok])
    want = want.at[e, layer, 1, s].set(v_new.reshape(-1, kvl, d)[ok])
    np.testing.assert_array_equal(out.reshape(view_shape), want)

    tables = jnp.asarray([[2, 5, -1], [0, 3, 4]], jnp.int32)
    k, v = A.gather_pages(out, view_shape, tables, layer)
    pages = jnp.where((tables >= 0)[:, :, None, None, None, None],
                      want[jnp.maximum(tables, 0), layer], 0)
    np.testing.assert_array_equal(k, pages[:, :, 0].reshape(k.shape))
    np.testing.assert_array_equal(v, pages[:, :, 1].reshape(v.shape))
