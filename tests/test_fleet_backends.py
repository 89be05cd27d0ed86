"""NumPy-vs-JAX fleet backend agreement: the pluggable-backend contract.

The JAX ``lax.scan`` backend must reproduce the NumPy reference's discrete
outcomes — emitted / skipped / acquired / power-cycle counts and drawn
energies — on shared traces, across policies, worker counts, heterogeneous
capacitor banks, and both request modes. Deterministic pins cover the
acceptance grid (N in {1, 256}); a hypothesis sweep fuzzes the rest.
"""
import numpy as np
import pytest

from repro.core.budget import CostTable
from repro.core.energy import Capacitor, get_trace, power_matrix
from repro.core.policies import Fixed, Greedy, Smart
from repro.fleet.scheduler import FleetScheduler, RequestStream, run_fleet
from repro.fleet.worker import FleetWorkerPool, stack_traces
from repro.fleet.workloads import (har_workload, harris_workload,
                                   lm_workload)
from repro.launch.fleet import (build_dispatch_pool, hetero_capacitors,
                                make_power_matrix)

DT = 0.01


def _costs40():
    return CostTable(np.full(40, 2e-4), emit_cost=1.2e-4, fixed_cost=1e-4)


def _acc41():
    return np.linspace(1 / 6, 0.9, 41)


def _local_pair(power, n_workers, policy, *, duration_ticks=None, cap=None,
                capacitance_f=None, v_max=None, active_power_w=None,
                seed=0):
    rng = np.random.default_rng(seed)
    kw = dict(workloads=[_costs40()], policy=policy,
              accuracy_table=_acc41(), mode="local",
              sampling_period_s=10.0, n_workers=n_workers,
              trace_index=np.arange(n_workers) % power.shape[0],
              phase=rng.integers(0, power.shape[1], n_workers),
              cap=cap, capacitance_f=capacitance_f, v_max=v_max,
              active_power_w=active_power_w)
    a = FleetWorkerPool(power, DT, backend="numpy", **kw)
    b = FleetWorkerPool(power, DT, backend="jax", **kw)
    sa = a.run(duration_ticks)
    sb = b.run(duration_ticks)
    return a, b, sa, sb


def _assert_agreement(a, b, sa, sb):
    assert sa.emitted == sb.emitted
    assert sa.skipped == sb.skipped
    assert sa.acquired == sb.acquired
    assert sa.power_cycles == sb.power_cycles
    assert np.array_equal(a.state.cycles, b.state.cycles)
    assert np.array_equal(a.state.emit_count, b.state.emit_count)
    assert np.array_equal(a.state.emit_units_sum, b.state.emit_units_sum)
    assert np.array_equal(a.state.skipped, b.state.skipped)
    # drawn energies are sums of exact table constants + per-tick quanta:
    # identical draw sequences make them bit-equal per worker
    assert np.array_equal(a.state.e_work, b.state.e_work)
    assert np.allclose(a.state.v, b.state.v, rtol=1e-12, atol=0)


# ---------------------------------------------------------------------------
# acceptance grid: N in {1, 256}, local mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tname,policy", [
    ("RF", Greedy()),
    ("SIR", Smart(0.6)),
    ("SOM", Greedy()),
])
def test_jax_matches_numpy_single_worker(tname, policy):
    tr = get_trace(tname, duration_s=300.0)
    a, b, sa, sb = _local_pair(stack_traces([tr]), 1, policy)
    _assert_agreement(a, b, sa, sb)
    assert sa.emitted > 0 or sa.skipped > 0  # the trace actually exercises


@pytest.mark.parametrize("policy", [Greedy(), Smart(0.8), Fixed(10)])
def test_jax_matches_numpy_256_workers(policy):
    power = power_matrix(["RF", "SOM", "SIM", "SOR", "SIR"], 16, 60.0, DT,
                         seed=7)
    a, b, sa, sb = _local_pair(power, 256, policy, seed=7)
    _assert_agreement(a, b, sa, sb)
    assert sa.emitted > 0 or sa.skipped > 0  # not a vacuous agreement


def test_jax_single_worker_matches_scalar_executor():
    """Transitivity pin: jax backend == numpy backend == scalar executor,
    so the scan path inherits the original bit-exactness contract."""
    from repro.core.intermittent import IntermittentExecutor
    tr = get_trace("RF", duration_s=300.0)
    st = IntermittentExecutor(tr, _costs40(), Greedy(), _acc41(),
                              mode="approximate",
                              sampling_period_s=10.0).run()
    b = FleetWorkerPool(stack_traces([tr]), tr.dt, workloads=[_costs40()],
                        policy=Greedy(), accuracy_table=_acc41(),
                        mode="local", sampling_period_s=10.0, backend="jax")
    sb = b.run()
    assert sb.emitted == len(st.results)
    assert sb.skipped == st.samples_skipped
    assert sb.acquired == st.samples_acquired
    assert sb.power_cycles == st.power_cycles
    assert int(b.state.emit_units_sum[0]) == sum(r.units_used
                                                 for r in st.results)


# ---------------------------------------------------------------------------
# heterogeneous fleets
# ---------------------------------------------------------------------------


def test_hetero_capacitor_arrays_agree_across_backends():
    power = power_matrix(["SOM", "RF", "SIR"], 8, 90.0, DT, seed=11)
    C, vmax = hetero_capacitors(64, seed=11)
    a, b, sa, sb = _local_pair(power, 64, Greedy(), capacitance_f=C,
                               v_max=vmax, seed=11)
    _assert_agreement(a, b, sa, sb)
    assert sa.emitted > 0


def test_hetero_single_worker_reduces_to_scalar_capacitor():
    """A hetero pool whose arrays hold one worker's values must match the
    homogeneous pool built from the equivalent scalar Capacitor."""
    tr = get_trace("SOM", duration_s=120.0)
    cap = Capacitor(capacitance_f=2200e-6, v_max=3.7)
    hom = FleetWorkerPool(stack_traces([tr]), tr.dt, workloads=[_costs40()],
                          policy=Greedy(), accuracy_table=_acc41(),
                          mode="local", cap=cap)
    het = FleetWorkerPool(stack_traces([tr]), tr.dt, workloads=[_costs40()],
                          policy=Greedy(), accuracy_table=_acc41(),
                          mode="local",
                          capacitance_f=np.array([2200e-6]),
                          v_max=np.array([3.7]))
    s1, s2 = hom.run(), het.run()
    assert s1.emitted == s2.emitted and s1.power_cycles == s2.power_cycles
    assert np.array_equal(hom.state.v, het.state.v)


def test_hetero_mcu_active_power_agrees_across_backends():
    """MCU-class mixing: per-worker active power changes each worker's
    per-tick energy quantum; both backends must still agree exactly."""
    from repro.launch.fleet import hetero_mcu
    power = power_matrix(["SOM", "RF", "SIR"], 6, 90.0, DT, seed=13)
    ap = hetero_mcu(48, seed=13)
    a, b, sa, sb = _local_pair(power, 48, Greedy(), active_power_w=ap,
                               seed=13)
    _assert_agreement(a, b, sa, sb)
    assert sa.emitted > 0
    assert len(np.unique(a.params.active_power_w)) > 1  # classes mixed


def test_hetero_mcu_active_power_changes_execution():
    """Sanity on the mixed knob: active power sets the per-tick energy
    quantum of the progression loop, so different MCU classes on the
    same trace must produce different execution traces (the parameter is
    plumbed through, not ignored)."""
    tr = get_trace("SOM", duration_s=60.0)
    runs = {}
    for ap in (1.2e-3, 2.4e-3):
        pool = FleetWorkerPool(stack_traces([tr]), tr.dt,
                               workloads=[_costs40()], policy=Greedy(),
                               accuracy_table=_acc41(), mode="local",
                               active_power_w=np.array([ap]))
        pool.run()
        runs[ap] = (int(pool.state.emit_units_sum[0]),
                    float(pool.state.e_work[0]),
                    float(pool.state.v[0]))
    assert runs[1.2e-3] != runs[2.4e-3]


def test_bigger_capacitor_skips_less():
    """Sanity on the knob the hetero fleet mixes: more buffer, fewer
    SMART skips (same trace, same policy)."""
    tr = get_trace("SIR", duration_s=300.0)
    runs = {}
    for c in (735e-6, 2940e-6):
        pool = FleetWorkerPool(stack_traces([tr]), tr.dt,
                               workloads=[_costs40()], policy=Smart(0.6),
                               accuracy_table=_acc41(), mode="local",
                               capacitance_f=np.array([c]))
        runs[c] = pool.run()
    assert runs[2940e-6].skipped <= runs[735e-6].skipped


# ---------------------------------------------------------------------------
# dispatch mode: the fused control plane vs the host-tick reference
# ---------------------------------------------------------------------------

COUNT_KEYS = ("submitted", "completed", "rejected", "shed", "lost",
              "evicted", "requeued")


def _serve_pair(power, n_workers, wls, n_steps, *, rate, mix, seed,
                sched="reactive", **sched_kw):
    """Run the same stream through the NumPy per-tick driver and the
    fused JAX launch; returns (summaries, schedulers, pools)."""
    out = {}
    for backend in ("numpy", "jax"):
        pool = build_dispatch_pool(power, DT, n_workers, wls, seed,
                                   backend=backend)
        s = FleetScheduler(pool, wls, sched=sched, **sched_kw)
        stream = RequestStream(rate, mix, n_steps, DT, seed=seed + 1)
        out[backend] = (run_fleet(pool, s, stream, n_steps), s, pool)
    return out


def _assert_sched_agreement(out):
    a, b = out["numpy"][0], out["jax"][0]
    for k in COUNT_KEYS:
        assert a[k] == b[k], k
    sa, sb = out["numpy"][1].state, out["jax"][1].state
    assert np.array_equal(sa.q_len, sb.q_len)
    assert np.array_equal(sa.f_n, sb.f_n)
    assert np.array_equal(sa.lat_hist, sb.lat_hist)
    assert np.array_equal(sa.batch_hist, sb.batch_hist)
    assert np.array_equal(sa.completed_wl, sb.completed_wl)
    assert np.array_equal(sa.units_wl, sb.units_wl)
    pa, pb = out["numpy"][2], out["jax"][2]
    assert np.array_equal(pa.state.emit_count, pb.state.emit_count)
    assert np.array_equal(pa.state.cycles, pb.state.cycles)
    assert np.array_equal(pa.state.e_work, pb.state.e_work)


def test_jax_dispatch_pool_has_no_host_macro_step():
    """A dispatch-mode jax pool advances only with its scheduler, in the
    fused serve scan: the host-scheduled macro-step raises and names
    run_serve."""
    wls = [har_workload(), lm_workload()]
    power = make_power_matrix(["SOM"], 1, 10.0, DT, seed=5)
    pool = build_dispatch_pool(power, DT, 4, wls, 5, backend="jax")
    with pytest.raises(ValueError, match="run_serve"):
        pool.step_macro(0, 10)
    assert pool.steps_done == 0


@pytest.mark.parametrize("sched", ["reactive", "forecast"])
def test_fused_sched_single_worker_matches_host_ticks(sched):
    wls = [har_workload(), lm_workload()]
    power = make_power_matrix(["SOM"], 1, 60.0, DT, seed=5)
    n_steps = int(60.0 / DT)
    out = _serve_pair(power, 1, wls, n_steps, rate=0.4,
                      mix=np.array([0.6, 0.4]), seed=5, sched=sched)
    _assert_sched_agreement(out)
    assert out["numpy"][0]["completed"] > 0


@pytest.mark.parametrize("sched", ["reactive", "forecast"])
def test_fused_sched_256_workers_matches_host_ticks(sched):
    """The acceptance-grid pin: a 256-worker mixed-trace serve runs as
    one fused launch and matches the per-tick reference on every
    request-lifecycle and device counter."""
    wls = [har_workload(), lm_workload()]
    power = make_power_matrix(["SOM", "SOR", "RF", "SIR"], 8, 40.0, DT,
                              seed=6)
    n_steps = int(40.0 / DT)
    out = _serve_pair(power, 256, wls, n_steps, rate=25.6,
                      mix=np.array([0.6, 0.4]), seed=6, sched=sched)
    _assert_sched_agreement(out)
    a = out["numpy"][0]
    s = out["numpy"][1]
    accounted = (a["completed"] + a["rejected"] + a["shed"] + a["lost"]
                 + s.backlog + s.inflight_count)
    assert accounted == a["submitted"]
    assert a["energy"]["conservation_ok"]
    assert a["completed"] > 0


def test_fused_sched_agreement_under_losses_and_retries():
    """Bursty traces + tight deadlines push requests through the retry /
    requeue / loss paths; the backends must still agree exactly."""
    wls = [har_workload(), lm_workload()]
    power = make_power_matrix(["KIN", "RF"], 4, 60.0, DT, seed=21)
    n_steps = int(60.0 / DT)
    out = _serve_pair(power, 24, wls, n_steps, rate=6.0,
                      mix=np.array([0.5, 0.5]), seed=21, sched="forecast",
                      shed_after_s=10.0, grace_s=2.0, max_retries=1)
    _assert_sched_agreement(out)
    a = out["numpy"][0]
    assert a["shed"] + a["lost"] + a["requeued"] > 0  # paths exercised


@pytest.mark.parametrize("forecaster", ["occlusion", "burst", "arp",
                                        "auto"])
def test_fused_sched_agreement_pluggable_forecasters(forecaster):
    """The pluggable-forecaster contract: every forecast model (and the
    per-row auto selection) evaluates identically in the host driver and
    inside the fused scan — same ranks, same batches, same counters."""
    from repro.launch.fleet import trace_family_labels
    wls = [har_workload(), lm_workload()]
    names = ["SIM", "RF", "SOM", "SIR"]
    power = make_power_matrix(names, 8, 40.0, DT, seed=9)
    fams = trace_family_labels(names, 8)
    n_steps = int(40.0 / DT)
    out = _serve_pair(power, 96, wls, n_steps, rate=9.6,
                      mix=np.array([0.6, 0.4]), seed=9, sched="forecast",
                      forecaster=forecaster, trace_families=fams)
    _assert_sched_agreement(out)
    assert out["numpy"][0]["completed"] > 0
    if forecaster == "auto":  # regime + OU rows genuinely mixed
        sp = out["numpy"][1].params
        assert len(np.unique(sp.FC_MODEL)) > 1


def test_forecast_routing_beats_reactive_on_solar_traces():
    """The ROADMAP 'scheduler lookahead' claim at test scale: on smooth
    mean-reverting solar harvest, planning batches against the OU
    forecast completes at least as many requests as instantaneous-charge
    routing — and strictly more on at least one family."""
    wins = {}
    for fam in ("SOM", "SOR", "SIM"):
        wls = [har_workload(), harris_workload(), lm_workload()]
        power = make_power_matrix([fam], 8, 120.0, DT, seed=31)
        n_steps = int(120.0 / DT)
        done = {}
        for sched in ("reactive", "forecast"):
            pool = build_dispatch_pool(power, DT, 64, wls, 31)
            s = FleetScheduler(pool, wls, sched=sched, lookahead_s=5.0)
            stream = RequestStream(6.4, np.array([0.4, 0.3, 0.3]),
                                   n_steps, DT, seed=32)
            done[sched] = run_fleet(pool, s, stream, n_steps)["completed"]
        assert done["forecast"] >= done["reactive"], fam
        wins[fam] = done["forecast"] - done["reactive"]
    assert any(v > 0 for v in wins.values()), wins


def test_forecaster_closed_forms():
    """fit_ou_theta recovers the synthesis theta on a clean OU row, and
    the window-average gain interpolates 1 (random walk) -> 0 (white
    noise)."""
    from repro.core.forecast import fit_ou_theta, forecast_gain
    rng = np.random.default_rng(0)
    n = 200_000
    theta = 0.01
    x = np.empty(n)
    x[0] = 1.0
    eps = 0.03 * rng.standard_normal(n)
    for i in range(1, n):  # the _ou_process recurrence, un-clipped
        x[i] = x[i - 1] + theta * (1.0 - x[i - 1]) + eps[i]
    est = fit_ou_theta(x[None, :])[0]
    assert abs(est - theta) < 0.005
    g = forecast_gain(np.array([1e-9, 0.5, 1.0]), 100)
    assert g[0] > 0.99 and g[2] < 0.02
    assert 0.0 < g[1] < g[0]


# ---------------------------------------------------------------------------
# legacy attribute surface + reset
# ---------------------------------------------------------------------------


def test_pool_attribute_assignment_reaches_backends():
    """Whole-array assignment through the legacy surface must rebind the
    state field the backends read (not a shadow), frozen params must
    reject writes, and reset() keeps the compiled backend."""
    tr = get_trace("SOM", duration_s=30.0)
    pool = FleetWorkerPool(stack_traces([tr]), tr.dt,
                           workloads=[_costs40()], policy=Greedy(),
                           accuracy_table=_acc41(), mode="local",
                           n_workers=4)
    pool.v = np.full(4, pool.v_on)
    assert pool.state.v is pool.v  # rebound, not shadowed
    with pytest.raises(AttributeError):
        pool.dt = 0.02  # frozen fleet parameter
    pool.run(500)
    assert pool.steps_done == 500
    pool.reset()
    assert pool.steps_done == 0 and float(pool.state.v.sum()) == 0.0


# ---------------------------------------------------------------------------
# stack_traces dt tolerance (satellite fix)
# ---------------------------------------------------------------------------


def test_stack_traces_tolerates_float_equal_dt():
    tr = get_trace("RF", duration_s=30.0)
    resampled = type(tr)(tr.name, tr.power_w.copy(),
                         (tr.dt * 7.0) / 7.0 * (1 + 1e-13))
    power = stack_traces([tr, resampled])  # must not raise
    assert power.shape == (2, tr.power_w.shape[0])
    bad = type(tr)(tr.name, tr.power_w.copy(), tr.dt * 2)
    with pytest.raises(ValueError):
        stack_traces([tr, bad])


# ---------------------------------------------------------------------------
# property sweep (hypothesis): random traces x policies x worker counts
# (guarded import, not importorskip: the deterministic tests above must
# still run on environments without hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:
    @given(st.sampled_from(["RF", "SOM", "SIM", "SOR", "SIR", "KIN"]),
           st.sampled_from([Greedy(), Smart(0.6), Smart(0.8), Fixed(5)]),
           st.integers(1, 48),
           st.integers(0, 10_000))
    @settings(max_examples=12, deadline=None)
    def test_backend_agreement_property(tname, policy, n_workers, seed):
        """INVARIANT: on any shared trace bank, both backends emit, skip
        and power-cycle identically (the pluggable-backend contract)."""
        traces = [get_trace(tname, seed=seed + r, duration_s=60.0)
                  for r in range(min(4, n_workers))]
        a, b, sa, sb = _local_pair(stack_traces(traces), n_workers, policy,
                                   seed=seed)
        _assert_agreement(a, b, sa, sb)

    @given(st.sampled_from(["SOM", "SIR", "RF", "KIN"]),
           st.sampled_from(["reactive", "forecast"]),
           st.integers(1, 16),
           st.integers(0, 10_000))
    @settings(max_examples=6, deadline=None)
    def test_sched_agreement_property(tname, sched, n_workers, seed):
        """INVARIANT: the fused control plane and the host-tick reference
        agree on every request-lifecycle counter for any trace family,
        routing mode, fleet size and stream seed."""
        wls = [har_workload(), lm_workload()]
        power = make_power_matrix([tname], min(4, n_workers), 20.0, DT,
                                  seed=seed)
        n_steps = int(20.0 / DT)
        out = _serve_pair(power, n_workers, wls, n_steps,
                          rate=max(n_workers / 10.0, 0.5),
                          mix=np.array([0.6, 0.4]), seed=seed,
                          sched=sched, shed_after_s=8.0, grace_s=4.0,
                          max_retries=1)
        _assert_sched_agreement(out)
