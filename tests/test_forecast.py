"""Pluggable harvest-forecaster correctness (repro.core.forecast).

Pins the contracts the fleet control plane depends on:

- the refactored OU model is bit-exact with the PR-3 closed forms
  (``forecast_gain`` / ``forecast_power`` / ``forecast_usable_energy``),
  through both the forecaster surface and the scheduler's
  ``plan_budget``;
- each forecaster's NumPy and jnp evaluation paths agree on shared
  deterministic inputs (the fused-scan planning budget must match the
  host reference);
- closed-form pinned values: the regime compile reproduces the two-state
  Markov conditional expectation on a synthetic chain with known
  parameters, and the AR(p) window sums equal the brute-force per-step
  recursion when the nonnegativity shrink is inactive;
- a hypothesis sweep: for all four models, forecast usable energy is
  nonnegative, bounded by the buffer ceiling, and nondecreasing in the
  lookahead (lags drawn from the fitted row's observed range).
"""
import numpy as np
import pytest

from repro.core import forecast as F
from repro.core.energy import Capacitor, get_trace, power_matrix

DT = 0.01
CAP = Capacitor()
E_CAP = 0.5 * CAP.capacitance_f * (CAP.v_max ** 2 - CAP.v_off ** 2)


def _bank(names, rows=6, duration_s=60.0, seed=0):
    return power_matrix(list(names), rows, duration_s, DT, seed=seed)


def _lags(rows, order, t, T=None):
    """(R, order) lag window sampled from the rows themselves at tick t."""
    T = rows.shape[1] if T is None else T
    return np.stack([rows[:, (t - j) % T] for j in range(order)], axis=1)


# ---------------------------------------------------------------------------
# OU refactor: bit-exact vs the PR-3 closed forms
# ---------------------------------------------------------------------------


def test_ou_refactor_bit_exact_vs_pr3_closed_forms():
    rows = _bank(["SOM", "SIR", "RF"], rows=6)
    L = 500
    theta = F.fit_ou_theta(rows)
    mu = rows.mean(axis=1)
    gain = np.asarray(F.forecast_gain(theta, L))

    f = F.OUForecaster()
    params = f.fit(rows)
    assert np.array_equal(params.theta, theta)
    assert np.array_equal(params.mu, mu)
    assert np.array_equal(f.gain(params, L), gain)

    rng = np.random.default_rng(0)
    usable = rng.uniform(0.0, E_CAP, rows.shape[0])
    lags = _lags(rows, 1, 1234)
    p_now = lags[:, 0]
    old_fp = F.forecast_power(p_now, mu, gain)
    old_ue = F.forecast_usable_energy(
        usable, p_now, L * DT, e_cap=E_CAP, booster_eff=CAP.booster_eff,
        mu=mu, gain=gain)
    rf = f.compile(params, L)
    assert np.array_equal(F.forecast_power_rows(rf, lags), old_fp)
    assert np.array_equal(
        f.usable_energy(params, L, usable, lags, DT, e_cap=E_CAP,
                        booster_eff=CAP.booster_eff), old_ue)


def test_plan_budget_ou_bit_exact_vs_pr3_formula():
    """The scheduler path: make_sched_params(forecaster='ou') +
    plan_budget must reproduce the PR-3 forecast-budget numbers
    bit-for-bit (recorded experiments stay reproducible)."""
    from repro.fleet.sched import make_sched_params, power_lags, plan_budget
    from repro.fleet.worker import FleetWorkerPool
    from repro.fleet.workloads import har_workload, lm_workload

    rows = _bank(["SOM", "RF"], rows=4)
    wls = [har_workload(), lm_workload()]
    pool = FleetWorkerPool(rows, DT, workloads=[w.costs for w in wls],
                           mode="dispatch", n_workers=16)
    p = pool.params
    sp = make_sched_params(p, wls, sched="forecast", lookahead_s=5.0,
                           forecaster="ou")
    L = sp.lookahead_ticks
    theta = F.fit_ou_theta(rows)
    mu = rows.mean(axis=1)[p.trace_index]
    gain = np.asarray(F.forecast_gain(theta, L))[p.trace_index]
    assert np.array_equal(sp.FC_MU, mu)
    assert np.array_equal(sp.FC_W[:, 0], gain)
    assert sp.fc_order == 1

    rng = np.random.default_rng(1)
    budget = rng.uniform(0.0, E_CAP, p.n)
    i = 777
    lags = power_lags(p.power, p.trace_index, i, p.T, sp.fc_order,
                      phase=p.phase)
    got = plan_budget(sp, budget, lags, p.eff)
    want = F.forecast_usable_energy(
        budget, p.power[p.trace_index, i % p.T], L * p.dt, e_cap=sp.ECAP,
        booster_eff=p.eff, mu=mu, gain=gain)
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# NumPy vs jnp evaluation paths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", F.FORECASTER_MODES)
def test_forecaster_numpy_and_jnp_paths_agree(mode):
    import jax
    import jax.numpy as jnp

    rows = _bank(["SOM", "SIM", "RF", "SIR"], rows=8)
    L = 300
    rf = F.fit_row_forecast(rows, mode, L,
                            families=["SOM", "SIM", "RF", "SIR"] * 2)
    rng = np.random.default_rng(2)
    usable = rng.uniform(0.0, E_CAP, rows.shape[0])
    for t in (3, 999, 4321):
        lags = _lags(rows, rf.order, t)
        a = F.usable_energy_rows(rf, usable, lags, L * DT, e_cap=E_CAP,
                                 booster_eff=CAP.booster_eff, xp=np)
        with jax.enable_x64(True):
            b = F.usable_energy_rows(
                rf, jnp.asarray(usable), jnp.asarray(lags), L * DT,
                e_cap=E_CAP, booster_eff=CAP.booster_eff, xp=jnp)
        # elementwise IEEE double on both paths; XLA:CPU may contract a
        # multiply-add into an FMA, so allow the last ulp
        np.testing.assert_allclose(np.asarray(b), a, rtol=1e-14, atol=0)
        # the regime branch decision itself must be identical
        fa = F.forecast_power_rows(rf, lags, xp=np)
        with jax.enable_x64(True):
            fb = F.forecast_power_rows(rf, jnp.asarray(lags), xp=jnp)
        np.testing.assert_allclose(np.asarray(fb), fa, rtol=1e-14, atol=0)
        assert np.all(fa >= 0.0)


# ---------------------------------------------------------------------------
# Pinned closed-form values
# ---------------------------------------------------------------------------


def test_regime_compile_matches_markov_closed_form():
    """A synthetic square-wave on/off chain with known dwell lengths:
    the burst fit must recover the transition structure and the compiled
    HI/LO must equal the hand-computed window-mean conditional
    expectation of the fitted chain."""
    T = 60_000
    period, duty = 100, 0.5
    x = ((np.arange(T) % period) < duty * period).astype(np.float64)
    rows = x[None, :] * 1e-3  # 1 mW bursts, exact zeros off
    f = F.BurstForecaster()
    params = f.fit(rows)
    assert bool(params.valid[0])
    assert params.m_hi[0] == pytest.approx(1e-3)
    assert params.m_lo[0] == pytest.approx(0.0)
    # square wave: one hi->lo and one lo->hi transition per period (up to
    # the truncated final period's edge effect)
    lam = 1.0 - 2.0 / (period * duty)
    assert params.lam[0] == pytest.approx(lam, rel=1e-4)
    L = 200
    g = F._geom_window_gain(params.lam, L)
    pibar = (params.pi_hi * params.m_hi
             + (1 - params.pi_hi) * params.m_lo)
    rf = f.compile(params, L)
    assert np.array_equal(rf.HI, pibar + g * (params.m_hi - pibar))
    assert np.array_equal(rf.LO, pibar + g * (params.m_lo - pibar))
    # conditioning works end-to-end: on-beam forecast exceeds off-beam
    hi = F.forecast_power_rows(rf, np.array([[1e-3]]))
    lo = F.forecast_power_rows(rf, np.array([[0.0]]))
    assert hi[0] > lo[0] > 0.0


def test_arp_window_sum_matches_bruteforce_recursion():
    """With a stable fit and lags near the mean (shrink inactive), the
    closed-form window-mean weights must equal brute-forcing the AR
    recurrence's conditional expectation step by step."""
    rng = np.random.default_rng(3)
    T, p = 40_000, 3
    a = np.array([0.55, 0.2, 0.1])  # stable AR(3)
    d = np.zeros(T)
    eps = 0.02 * rng.standard_normal(T)
    for t in range(p, T):
        d[t] = a @ d[t - p:t][::-1] + eps[t]
    rows = (1.0 + d)[None, :] * 1e-3  # mu >> |dev|: shrink never fires
    f = F.ARPForecaster(order=p)
    params = f.fit(rows)
    np.testing.assert_allclose(params.coef[0], a, atol=0.02)
    L = 50
    lags = _lags(rows, p, 12_345)
    got = F.forecast_power_rows(f.compile(params, L), lags)[0]
    # brute force: iterate the fitted recurrence on the lag window
    mu = params.mu[0]
    hist = list(lags[0] - mu)  # [d_t, d_{t-1}, d_{t-2}]
    acc = 0.0
    for _ in range(L):
        nxt = float(params.coef[0] @ np.asarray(hist))
        acc += mu + nxt
        hist = [nxt] + hist[:-1]
    assert got == pytest.approx(acc / L, rel=1e-12)


def test_arp_gain_first_step_is_the_fit():
    rows = _bank(["SOM"], rows=2)
    f = F.ARPForecaster(order=2)
    params = f.fit(rows)
    np.testing.assert_allclose(f.gain(params, 1), params.coef, rtol=1e-9)


# ---------------------------------------------------------------------------
# Auto selection
# ---------------------------------------------------------------------------


def test_auto_selection_by_family_and_by_classification():
    fams = ["SOM", "SIM", "SOR", "SIR", "RF", "KIN"]
    rows = np.concatenate([
        get_trace(n, seed=10 + i, duration_s=60.0).power_w[None, :]
        for i, n in enumerate(fams)])
    # label-driven: each row gets its family's matched model
    rf = F.fit_row_forecast(rows, "auto", 100, families=fams)
    want = [F.MODEL_CODES[F.FAMILY_FORECASTER[f]] for f in fams]
    assert list(rf.model) == want
    # label-free: the classifier separates burst / occlusion / smooth
    names = F.classify_rows(rows)
    assert names[fams.index("RF")] == "burst"
    assert names[fams.index("SIM")] == "occlusion"
    assert names[fams.index("SOR")] == "ou"
    assert names[fams.index("SIR")] == "ou"


def test_unknown_modes_rejected():
    rows = _bank(["SOM"], rows=1)
    with pytest.raises(ValueError):
        F.fit_row_forecast(rows, "kalman", 10)
    with pytest.raises(ValueError):
        F.make_forecaster("kalman")


# ---------------------------------------------------------------------------
# Property sweep (hypothesis): nonnegative + lookahead-monotone
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    @given(st.sampled_from(["SOM", "SIM", "SOR", "SIR", "RF", "KIN"]),
           st.sampled_from(F.FORECASTER_NAMES),
           st.integers(0, 10_000),
           st.integers(1, 400), st.integers(1, 400))
    @settings(max_examples=20, deadline=None)
    def test_usable_energy_nonnegative_and_lookahead_monotone(
            fam, mode, seed, la, lb):
        """INVARIANT: for every model, forecast usable energy is in
        [0, e_cap] and nondecreasing in the lookahead when the lags come
        from the fitted row's observed range."""
        rows = np.stack([
            get_trace(fam, seed=seed + r, duration_s=30.0).power_w
            for r in range(2)])
        f = F.make_forecaster(mode, arp_order=2)
        params = f.fit(rows)
        rng = np.random.default_rng(seed)
        usable = rng.uniform(0.0, E_CAP, 2)
        lags = _lags(rows, f.order, int(rng.integers(0, rows.shape[1])))
        l1, l2 = sorted((la, lb))
        u1 = f.usable_energy(params, l1, usable, lags, DT, e_cap=E_CAP,
                             booster_eff=CAP.booster_eff)
        u2 = f.usable_energy(params, l2, usable, lags, DT, e_cap=E_CAP,
                             booster_eff=CAP.booster_eff)
        assert np.all(u1 >= 0.0) and np.all(u2 >= 0.0)
        assert np.all(u1 <= E_CAP * (1 + 1e-12))
        assert np.all(u2 >= u1 - 1e-12 * E_CAP)
