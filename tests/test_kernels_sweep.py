"""Forward-sweep ops (level arrivals, Appendix-A wait) vs oracles.

Mirrors test_kernels_merge.py for the wait-propagation hot loop: the
Pallas kernel runs in interpret mode on CPU and must reproduce the jnp
oracle bit for bit in f64, preserve f32 / bf16 dtypes (no silent
upcast), and handle the churn-fused send variant's validity masking
(dead rows send at +inf).  The level-arrival gather+add has no kernel
(Mosaic has no dynamic lane gather); its jnp oracle is checked against
the numpy expression.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.sweep import wait_propagate
from repro.kernels.sweep.ref import arrivals_ref, wait_ref
from repro.kernels.sweep.sweep import wait_pallas


def _arrival_inputs(rng, E, L, Lp, dtype):
    tq_prev = rng.random((E, Lp)).astype(dtype)
    dn = rng.random((E, L)).astype(dtype)
    par_pos = rng.integers(0, Lp, L).astype(np.int64)
    return tq_prev, dn, par_pos


def _wait_inputs(rng, E, L, dtype):
    own = rng.random((E, L)).astype(dtype)
    all_in = rng.random((E, L)).astype(dtype)
    deadline = rng.random((E, L)).astype(dtype)
    return own, all_in, deadline


# the last two shapes span several (64, 2048) blocks with ragged edges
@pytest.mark.parametrize("E,L", [(1, 1), (4, 9), (6, 40), (70, 300),
                                 (9, 4200)])
def test_wait_pallas_matches_ref_f64(E, L):
    with jax.enable_x64():
        rng = np.random.default_rng(1)
        own, all_in, deadline = _wait_inputs(rng, E, L, np.float64)
        s1 = np.asarray(wait_pallas(own, all_in, deadline, None,
                                    interpret=True))
        s2 = np.asarray(wait_ref(own, all_in, deadline))
        assert s1.dtype == s2.dtype == np.float64
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(
            s2, np.minimum(np.maximum(own, all_in),
                           np.maximum(deadline, own)))


@pytest.mark.parametrize("dtype", [np.float64, np.float32, "bfloat16"])
def test_sweep_kernels_preserve_dtype(dtype):
    """f64 / f32 / bf16 inputs come back in the same dtype from the
    arrivals oracle and from both wait paths — no silent upcast."""
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    with jax.enable_x64():
        rng = np.random.default_rng(2)
        tq_prev, dn, par_pos = _arrival_inputs(rng, 3, 5, 4, np.float64)
        tq_prev = jnp.asarray(tq_prev, dt)
        dn = jnp.asarray(dn, dt)
        assert arrivals_ref(tq_prev, dn, par_pos).dtype == dt
        own, all_in, deadline = (jnp.asarray(x, dt) for x in
                                 _wait_inputs(rng, 3, 5, np.float64))
        death = jnp.asarray(rng.random((3, 5)), dt)
        for use_pallas in (False, True):
            s = wait_propagate(own, all_in, deadline,
                               use_pallas=use_pallas, interpret=True)
            assert s.dtype == dt
            s2, snd = wait_propagate(own, all_in, deadline, death=death,
                                     use_pallas=use_pallas,
                                     interpret=True)
            assert s2.dtype == dt and snd.dtype == dt


def test_wait_churn_send_masks_dead_rows():
    """The fused churn variant: ``send = s`` exactly where the peer is
    still alive at its send time (``death >= s``) and +inf elsewhere —
    identical between oracle and Pallas, and to masking by hand."""
    with jax.enable_x64():
        rng = np.random.default_rng(3)
        own, all_in, deadline = _wait_inputs(rng, 5, 11, np.float64)
        death = rng.random((5, 11))
        s_ref, snd_ref = wait_propagate(own, all_in, deadline,
                                        death=death, use_pallas=False)
        s_pl, snd_pl = wait_pallas(own, all_in, deadline, death,
                                   interpret=True)
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pl))
        np.testing.assert_array_equal(np.asarray(snd_ref),
                                      np.asarray(snd_pl))
        alive = death >= np.asarray(s_ref)
        np.testing.assert_array_equal(
            np.asarray(snd_ref),
            np.where(alive, np.asarray(s_ref), np.inf))
        assert not alive.all() and alive.any()   # both branches hit


@settings(max_examples=20, deadline=None)
@given(E=st.integers(1, 6), L=st.integers(1, 24), Lp=st.integers(1, 24),
       seed=st.integers(0, 999))
def test_sweep_kernels_property_parity(E, L, Lp, seed):
    """Random shapes: the arrivals oracle == the numpy expression and
    Pallas interpret == jnp oracle for the wait rule, bit for bit (f64),
    including the churn send."""
    with jax.enable_x64():
        rng = np.random.default_rng(seed)
        tq_prev, dn, par_pos = _arrival_inputs(rng, E, L, Lp, np.float64)
        np.testing.assert_array_equal(
            np.asarray(arrivals_ref(tq_prev, dn, par_pos)),
            tq_prev[:, par_pos] + dn)
        own, all_in, deadline = _wait_inputs(rng, E, L, np.float64)
        death = rng.random((E, L))
        s1, snd1 = wait_pallas(own, all_in, deadline, death,
                               interpret=True)
        s2, snd2 = wait_propagate(own, all_in, deadline, death=death,
                                  use_pallas=False)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        np.testing.assert_array_equal(np.asarray(snd1), np.asarray(snd2))
