"""Property-based backend parity: random overlays × policies × rng.

Two contracts, drawn over random small Barabási–Albert overlays:

* **f64 (bit-exactness)** — for every policy and both rng modes the
  jax sweep reproduces the numpy reference's per-entry metrics BIT FOR
  BIT (``fd-stats`` has no jax path and must *report* its numpy
  fallback rather than silently diverge).
* **f32 / bf16 (tolerance)** — the reduced-precision jax sweep is
  validated against its own f64 rerun by the recorded tolerance
  report: recall@k == 1.0 whenever the f64 scores are well separated
  at the k boundary (``separated``), and the positional score rtol
  within the per-precision bound always.  On ties / sub-spacing gaps
  (bf16 near 1.0 has spacing ~0.004, so U(0,1) top scores collapse)
  owner sets may legitimately differ — the contract's ``ok`` bit is
  the asserted invariant, never raw recall.

Runs under real hypothesis in CI (``--hypothesis-profile=ci``,
derandomized) and under the deterministic conftest stub when the
package is absent.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.engine import SimEngine
from repro.engine.api import QuerySpec, available_policies
from repro.engine.precision import PRECISION_RTOL
from repro.p2psim.graph import barabasi_albert
from repro.p2psim.simulate import SimParams

POLICIES = ("fd-basic", "fd-st1", "fd-st1+2", "fd-dynamic",
            "cn", "cn-star", "fd-stats")
RNG_MODES = ("shared", "independent")
_METRIC_FIELDS = ("m_fw", "m_bw", "m_rt", "b_fw", "b_bw", "b_rt",
                  "response_time_s", "accuracy")


def _engines(n, m, seed, **kw):
    top = barabasi_albert(n, m, seed=seed)
    params = SimParams(k=4, seed=seed + 1)
    return (SimEngine(top, params, backend="numpy"),
            SimEngine(top, params, backend="jax", **kw))


def test_policy_registry_is_covered():
    """The property sweep really does cover every registered policy."""
    assert sorted(POLICIES) == sorted(available_policies())


@settings(max_examples=8, deadline=None)
@given(n=st.integers(12, 40), m=st.integers(1, 3),
       seed=st.integers(0, 10_000),
       pol=st.integers(0, len(POLICIES) - 1),
       rng=st.integers(0, len(RNG_MODES) - 1))
def test_f64_jax_matches_numpy_bits(n, m, seed, pol, rng):
    policy, mode = POLICIES[pol], RNG_MODES[rng]
    np_eng, jx_eng = _engines(n, max(1, min(m, n - 1)), seed)
    if policy == "fd-stats":             # one origin x one trial per call
        spec = QuerySpec(origins=(0,), rng=mode)
    else:
        spec = QuerySpec(origins=(0, n // 2), n_trials=2, rng=mode)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        r_np = np_eng.run(spec, policy)
        r_jx = jx_eng.run(spec, policy)
    if policy == "fd-stats":             # no jax path: visible fallback
        assert r_jx.backend_used == "sim"
    for f in _METRIC_FIELDS:
        np.testing.assert_array_equal(
            getattr(r_np.metrics, f), getattr(r_jx.metrics, f),
            err_msg=f"{policy}/{mode}: {f}")


@settings(max_examples=6, deadline=None)
@given(n=st.integers(12, 32), seed=st.integers(0, 10_000),
       pol=st.integers(0, len(POLICIES) - 2),   # fd-stats raises: below
       rng=st.integers(0, len(RNG_MODES) - 1),
       prec=st.integers(0, 1))
def test_reduced_precision_tolerance_contract(n, seed, pol, rng, prec):
    policy, mode = POLICIES[pol], RNG_MODES[rng]
    precision = ("f32", "bf16")[prec]
    _, eng = _engines(n, 2, seed, precision=precision)
    res = eng.run(QuerySpec(origins=(0,), n_trials=2, rng=mode), policy)
    assert res.precision == precision
    tol = res.extras["tolerance"]
    assert tol["ok"], f"{policy}/{mode}/{precision}: {tol}"
    assert tol["max_rtol"] <= PRECISION_RTOL[precision]
    if tol["separated"]:
        assert tol["recall"] == 1.0


def test_fd_stats_rejects_reduced_precision():
    _, eng = _engines(16, 2, 0, precision="f32")
    with pytest.raises(ValueError, match="fd-stats"):
        eng.run(QuerySpec(origins=(0,)), "fd-stats")


def test_tolerance_recall_counts_distinct_owners():
    """Owners are peer ids and repeat when one peer holds several of
    the top k: identical answers have recall 1.0, and recall measures
    the distinct owners recovered."""
    from repro.engine.precision import check_tolerance
    vals = np.array([[0.9, 0.8, 0.7, 0.6]])
    owners = np.array([[3, 3, 5, -1]])
    rep = check_tolerance("f32", vals, owners, vals, owners)
    assert rep.recall == 1.0 and rep.ok
    rep = check_tolerance("f32", vals, np.array([[3, 3, 4, -1]]),
                          vals, owners)
    assert rep.recall == 0.5
