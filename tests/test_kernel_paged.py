"""In-place paged attention kernel vs its pure-jnp oracle: decode-only
shape/dtype sweeps, a sliding window, and a hypothesis property (page
permutation invariance); plus the row-addressed buffer writes and gathers
of the reference path."""
import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.kernels.paged_attention.kernel import paged_attention, plan_work
from repro.kernels.paged_attention.ref import paged_attention_ref

LAYERS, LAYER = 3, 1


def make_case(b, kvl, g, d, tpp, n_pages, vp, seed=0, dtype=jnp.float32):
    """``b`` decode segments of ``n_pages`` pages each, drawn without
    replacement from a pool of ``vp`` pages; each token sits at a random
    position of its segment's pages. Returns the kernel's arguments as a
    dict."""
    rng = np.random.default_rng(seed)
    rpt = kvl * d // 128
    rows = jnp.asarray(rng.standard_normal(
        (vp * LAYERS, 2 * tpp * rpt, 128)), dtype)
    q = jnp.asarray(rng.standard_normal((b, kvl, g, d)), dtype)
    ids = np.concatenate([rng.choice(vp, n_pages, replace=False)
                          for _ in range(b)]).astype(np.int32)
    return dict(q=q, rows=rows, page_ids=jnp.asarray(ids),
                seg_ids=jnp.arange(b, dtype=jnp.int32),
                positions=jnp.asarray(rng.integers(1, n_pages * tpp, b),
                                      jnp.int32),
                page_start=jnp.arange(b, dtype=jnp.int32) * n_pages,
                page_count=jnp.full((b,), n_pages, jnp.int32), tpp=tpp)


def run_kernel(c, window=0, page_ids=None, rows=None):
    work = plan_work(c["seg_ids"], c["positions"], c["page_start"],
                     c["page_count"], q_block=2, tokens_per_page=c["tpp"],
                     window=window)
    return paged_attention(
        c["q"], c["rows"] if rows is None else rows,
        c["page_ids"] if page_ids is None else page_ids, work, LAYER,
        num_layers=LAYERS, tokens_per_page=c["tpp"], window=window,
        q_block=2, pages_per_wave=2, interpret=True)


def run_ref(c, window=0):
    return paged_attention_ref(
        c["q"], c["rows"], c["page_ids"], c["seg_ids"], c["positions"],
        c["page_start"], c["page_count"], LAYER, num_layers=LAYERS,
        tokens_per_page=c["tpp"], window=window)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kvl,g,d,tpp,n_pages", [
    (2, 2, 4, 64, 8, 4),
    (3, 2, 2, 64, 16, 3),
    (1, 4, 1, 128, 8, 6),
])
def test_kernel_matches_ref_sweep(b, kvl, g, d, tpp, n_pages, dtype):
    case = make_case(b, kvl, g, d, tpp, n_pages, vp=n_pages * b + 3,
                     dtype=dtype)
    out_k = run_kernel(case)
    out_r = run_ref(case)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [4, 16])
def test_kernel_sliding_window(window):
    case = make_case(2, 2, 2, 64, 8, 5, vp=16)
    out_k = run_kernel(case, window=window)
    out_r = run_ref(case, window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), tpp=st.sampled_from([8, 16]),
       n_pages=st.integers(2, 6))
def test_page_id_permutation_invariance(seed, tpp, n_pages):
    """Jenga invariant: exec page ids are arbitrary — permuting which
    physical pages hold the data must not change attention output."""
    vp = 24
    case = make_case(2, 2, 1, 64, tpp, n_pages, vp, seed=seed)
    out1 = run_ref(case)
    # move every page's content to a permuted slot; update the page ids
    perm = np.random.default_rng(seed + 1).permutation(vp)
    pages = np.asarray(case["rows"]).reshape(vp, LAYERS,
                                             *case["rows"].shape[1:])
    rows2 = jnp.asarray(pages[np.argsort(perm)].reshape(case["rows"].shape))
    ids2 = jnp.asarray(perm[np.asarray(case["page_ids"])].astype(np.int32))
    out2k = run_kernel(case, page_ids=ids2, rows=rows2)
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
