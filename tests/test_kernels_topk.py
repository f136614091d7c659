"""Pallas blocked top-k kernel vs pure-jnp oracle: shape/dtype sweeps +
hypothesis property tests (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.topk import local_topk, topk_pallas, topk_ref


# (10, 300) spans two 8-row blocks; (2, 4, 4096) flattens to one full one
@pytest.mark.parametrize("shape", [(128,), (1, 1000), (3, 777), (2, 4, 4096),
                                   (10, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("k", [1, 8, 20])
def test_topk_matches_ref(shape, dtype, k):
    if k > shape[-1]:
        pytest.skip("k > n")
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    v1, i1 = topk_pallas(x, k, tile_n=256)
    v2, i2 = topk_ref(x, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("tile_n", [128, 256, 1024, 4096])
def test_topk_tile_sizes(tile_n):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3000))
    v1, i1 = topk_pallas(x, 16, tile_n=tile_n)
    v2, i2 = topk_ref(x, 16)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_topk_index_offset():
    x = jax.random.normal(jax.random.PRNGKey(2), (512,))
    v, i = topk_pallas(x, 4, index_offset=1000, tile_n=128)
    v2, i2 = topk_ref(x, 4, index_offset=1000)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    assert int(np.min(np.asarray(i))) >= 1000


def test_topk_with_ties_prefers_lowest_index():
    x = jnp.zeros((64,)).at[jnp.array([5, 17])].set(1.0)
    v, i = topk_pallas(x, 3, tile_n=128)
    assert list(np.asarray(i)[:2]) == [5, 17]


def test_topk_duplicate_values():
    x = jnp.array([3.0, 3.0, 3.0, 1.0, 2.0])
    v, i = topk_pallas(x, 4, tile_n=128)
    np.testing.assert_allclose(np.asarray(v), [3, 3, 3, 2])
    assert sorted(np.asarray(i)[:3].tolist()) == [0, 1, 2]


@settings(max_examples=30, deadline=None)
@given(n=st.integers(4, 600), k=st.integers(1, 16), seed=st.integers(0, 99))
def test_topk_property(n, k, seed):
    k = min(k, n)
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    v, i = topk_pallas(x, k, tile_n=128)
    v, i = np.asarray(v), np.asarray(i)
    xs = np.asarray(x)
    # values are the k largest, descending, and indices point at them
    assert np.all(np.diff(v) <= 0)
    np.testing.assert_allclose(xs[i], v, rtol=1e-6)
    np.testing.assert_allclose(np.sort(xs)[::-1][:k], v, rtol=1e-6)


def test_local_topk_dispatch():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 256))
    v1, i1 = local_topk(x, 5, use_pallas=True)
    v2, i2 = local_topk(x, 5, use_pallas=False)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
