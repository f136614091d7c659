"""Score-list merge kernel (bitonic, Merge-and-Backward) vs oracle."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.scorelist import empty_scorelist
from repro.kernels.merge import merge_pallas, merge_ref
from repro.kernels.topk import topk_ref


def _mk_list(key, shape, k):
    x = jax.random.normal(key, shape + (4 * k,))
    return topk_ref(x, k)


@pytest.mark.parametrize("k", [1, 4, 7, 16, 20, 64])
@pytest.mark.parametrize("lead", [(), (3,), (2, 5)])
def test_merge_matches_ref(k, lead):
    _check_merge_matches_ref(k, lead)


@pytest.mark.parametrize("k", [8, 20])
def test_merge_matches_ref_multi_block(k):
    """A batch of 4100 lists spans two (32 x 128)-list blocks, the second
    one ragged: the position-major tiling must not mix lists."""
    _check_merge_matches_ref(k, (4100,))


def _check_merge_matches_ref(k, lead):
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    va, ia = _mk_list(ka, lead, k)
    vb, ib = _mk_list(kb, lead, k)
    v1, i1 = merge_pallas(va, ia, vb, ib)
    v2, i2 = merge_ref(va, ia, vb, ib)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    # distinct random scores: no ties, so the owners agree too
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def _tied_lists(k, rows=64, seed=3):
    """Descending lists drawn from a few score levels, so ties within
    and across the two lists are everywhere; every entry has its own
    owner (a: 0..k-1, b: 1000..1000+k-1)."""
    rng = np.random.default_rng(seed)
    levels = np.array([1.0, 0.5, 0.25, -np.inf], np.float32)
    va = -np.sort(-rng.choice(levels[:3], (rows, k)), axis=1)
    vb = -np.sort(-rng.choice(levels, (rows, k)), axis=1)
    ia = np.broadcast_to(np.arange(k, dtype=np.int32), (rows, k))
    return va, ia, vb, ia + 1000


@pytest.mark.parametrize("k", [2, 5, 16, 20])
def test_merge_ties_lose_no_owner(k):
    """On tied scores both merge networks (the Pallas kernel and the
    fused jnp ``_merge_desc``) give merge_ref's values, and each output
    is an input entry used once — a tie never copies one owner over
    another.  Above the score at the cut-off the owner set is
    merge_ref's; at the cut-off any of the tied entries may survive.
    The two networks agree bit for bit."""
    from repro.engine.sim_jax import _merge_desc
    from repro.kernels.merge.merge import _next_pow2
    va, ia, vb, ib = _tied_lists(k)
    if k == 2:                       # the smallest case, written out
        va[0], vb[0] = [1.0, 0.5], [1.0, 0.25]
    rv, ri = (np.asarray(x) for x in merge_ref(va, ia, vb, ib))
    kv, ki = (np.asarray(x) for x in merge_pallas(va, ia, vb, ib))
    pad = _next_pow2(k) - k

    def padded(x, fill):
        return np.pad(x, ((0, 0), (0, pad)), constant_values=fill)
    dv, di = _merge_desc(padded(va, -np.inf), padded(ia, -1),
                         padded(vb, -np.inf), padded(ib, -1))
    dv, di = np.asarray(dv)[:, :k], np.asarray(di)[:, :k]
    for v, i in ((kv, ki), (dv, di)):
        np.testing.assert_array_equal(v, rv)
        for r in range(len(v)):
            score = dict(zip(ia[r], va[r])) | dict(zip(ib[r], vb[r]))
            assert len(set(i[r])) == k                  # no owner twice
            assert [score[o] for o in i[r]] == list(v[r])
            above = v[r] > v[r, -1]
            assert set(i[r][above]) == set(ri[r][above])
    np.testing.assert_array_equal(kv, dv)
    np.testing.assert_array_equal(ki, di)
    if k == 2:
        assert sorted(ki[0]) == [0, 1000]


def test_merge_identity():
    """empty list is the identity element of merge."""
    v, i = _mk_list(jax.random.PRNGKey(1), (), 8)
    ev, ei = empty_scorelist((), 8)
    mv, mi = merge_pallas(v, i, ev, ei)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(i))


@settings(max_examples=25, deadline=None)
@given(k=st.integers(1, 32), seed=st.integers(0, 999))
def test_merge_commutative_and_topk_of_union(k, seed):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    va, ia = _mk_list(ka, (), k)
    vb, ib = _mk_list(kb, (), k)
    v1, _ = merge_pallas(va, ia, vb, ib)
    v2, _ = merge_pallas(vb, ib, va, ia)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))
    # merge == top-k of the concatenated union
    union = np.concatenate([np.asarray(va), np.asarray(vb)])
    np.testing.assert_allclose(np.asarray(v1), np.sort(union)[::-1][:k],
                               rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(k=st.integers(1, 16), seed=st.integers(0, 99))
def test_merge_associative(k, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lists = [_mk_list(kk, (), k) for kk in ks]
    (va, ia), (vb, ib), (vc, ic) = lists
    l1 = merge_pallas(*merge_pallas(va, ia, vb, ib), vc, ic)
    l2 = merge_pallas(va, ia, *merge_pallas(vb, ib, vc, ic))
    np.testing.assert_allclose(np.asarray(l1[0]), np.asarray(l2[0]))


@pytest.mark.parametrize("k", [4, 8, 20])
def test_merge_valid_masks_match_premasked(k):
    """valid_a/valid_b row masks (churned-out peers) == pre-masking the
    input values to -inf, on the jnp oracle AND inside the Pallas
    kernel — and an invalid list is absorbed like the empty list."""
    ka, kb = jax.random.split(jax.random.PRNGKey(7))
    lead = (3, 5)
    va, ia = _mk_list(ka, lead, k)
    vb, ib = _mk_list(kb, lead, k)
    rng = np.random.default_rng(0)
    ma = rng.random(lead) < 0.5
    mb = rng.random(lead) < 0.5
    va_m = np.where(ma[..., None], np.asarray(va), -np.inf).astype(va.dtype)
    vb_m = np.where(mb[..., None], np.asarray(vb), -np.inf).astype(vb.dtype)
    for fn in (merge_ref, merge_pallas):
        v1, i1 = fn(va, ia, vb, ib, valid_a=ma, valid_b=mb)
        v2, i2 = fn(va_m, ia, vb_m, ib)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # one-sided mask, fully-valid rows: a no-op vs the unmasked merge
    ones = np.ones(lead, bool)
    v3, _ = merge_pallas(va, ia, vb, ib, valid_b=ones)
    v0, _ = merge_pallas(va, ia, vb, ib)
    np.testing.assert_array_equal(np.asarray(v3), np.asarray(v0))
    # an all-invalid b behaves like merging with the empty list
    v4, _ = merge_pallas(va, ia, vb, ib, valid_b=~ones)
    np.testing.assert_array_equal(np.asarray(v4), np.asarray(va))


def test_merge_float64_passthrough():
    """float64 lists (the x64 simulator sweep) merge in float64 on both
    the Pallas kernel and the jnp oracle — no silent f32 downcast."""
    with jax.enable_x64():
        rng = np.random.default_rng(0)
        va = np.sort(rng.random((4, 8)))[:, ::-1].copy()
        vb = np.sort(rng.random((4, 8)))[:, ::-1].copy()
        ia = rng.integers(0, 99, (4, 8)).astype(np.int32)
        ib = rng.integers(0, 99, (4, 8)).astype(np.int32)
        v1, i1 = merge_pallas(va, ia, vb, ib)
        v2, i2 = merge_ref(va, ia, vb, ib)
        assert v1.dtype == v2.dtype == np.float64
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        # exact top-k of the union, descending, in full precision
        both = np.concatenate([va, vb], axis=1)
        np.testing.assert_array_equal(
            np.asarray(v1), np.sort(both, axis=1)[:, ::-1][:, :8])
    # f32 inputs keep the historical f32 compute dtype
    v3, _ = merge_ref(va.astype(np.float32), ia, vb.astype(np.float32), ib)
    assert v3.dtype == np.float32
