"""Fleet subsystem invariants: scalar/vectorized agreement, energy
conservation, trace determinism, scheduler end-to-end behavior."""
import numpy as np
import pytest

from repro.core.budget import CostTable
from repro.core.energy import Capacitor, get_trace
from repro.core.intermittent import IntermittentExecutor
from repro.core.policies import Greedy, Smart
from repro.core.profile_tables import harris_cost_table
from repro.fleet.scheduler import (FleetScheduler, RequestStream,
                                   _HostServe, run_fleet)
from repro.fleet.worker import FleetWorkerPool, stack_traces
from repro.fleet.workloads import har_workload, harris_workload, lm_workload
from repro.launch.fleet import (build_dispatch_pool, make_power_matrix,
                                run_independent, run_scheduled)

DT = 0.01


def _costs40():
    return CostTable(np.full(40, 2e-4), emit_cost=1.2e-4, fixed_cost=1e-4)


# ---------------------------------------------------------------------------
# scalar <-> vectorized agreement (the acceptance-criterion test)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tname,policy", [
    ("RF", Greedy()),
    ("SIR", Smart(0.6)),
    ("SOM", Greedy()),
])
def test_one_worker_fleet_matches_scalar_executor(tname, policy):
    """A 1-worker vectorized fleet reproduces the scalar executor: same
    emitted sample ids and units (and times/counters, in fact)."""
    costs = _costs40()
    acc = np.linspace(1 / 6, 0.9, 41)
    tr = get_trace(tname, duration_s=300.0)
    st = IntermittentExecutor(tr, costs, policy, acc, mode="approximate",
                              sampling_period_s=10.0).run()
    pool = FleetWorkerPool(stack_traces([tr]), tr.dt, workloads=[costs],
                           policy=policy, accuracy_table=acc, mode="local",
                           sampling_period_s=10.0)
    pool.run()
    assert [(r.sample_id, r.units_used) for r in pool.results[0]] \
        == [(r.sample_id, r.units_used) for r in st.results]
    assert [r.t_emitted for r in pool.results[0]] \
        == [r.t_emitted for r in st.results]
    assert int(pool.acquired[0]) == st.samples_acquired
    assert int(pool.skipped[0]) == st.samples_skipped
    assert int(pool.cycles[0]) == st.power_cycles
    assert float(pool.e_work[0]) == st.energy_on_work_j


def test_one_worker_agreement_scarce_regime():
    """Same pinning in the Harris/partial-emission regime (emit-reserve
    fires, results carry partial tap counts)."""
    costs = harris_cost_table(25)
    acc = np.linspace(0.0, 1.0, 26)
    tr = get_trace("SIM", duration_s=300.0)
    st = IntermittentExecutor(tr, costs, Greedy(), acc, mode="approximate",
                              cap=Capacitor(v_max=3.8),
                              sampling_period_s=10.0).run()
    pool = FleetWorkerPool(stack_traces([tr]), tr.dt, workloads=[costs],
                           policy=Greedy(), accuracy_table=acc, mode="local",
                           sampling_period_s=10.0, cap=Capacitor(v_max=3.8))
    pool.run()
    assert [(r.sample_id, r.units_used, r.t_emitted)
            for r in pool.results[0]] \
        == [(r.sample_id, r.units_used, r.t_emitted) for r in st.results]
    assert len(st.results) > 0  # the regime actually emits partials
    assert any(r.units_used < 25 for r in st.results)


# ---------------------------------------------------------------------------
# energy invariants
# ---------------------------------------------------------------------------


def test_fleet_energy_conservation():
    """INVARIANT: harvested >= work + NVM + sleep, per worker and fleet-
    wide (the capacitor cannot mint energy; NVM/sleep are 0 by design for
    the approximate runtime)."""
    power = make_power_matrix(["RF", "SOM", "SIR"], 6, 60.0, DT, seed=3)
    costs = _costs40()
    acc = np.linspace(1 / 6, 0.9, 41)
    pool = FleetWorkerPool(power, DT, workloads=[costs], policy=Greedy(),
                           accuracy_table=acc, mode="local", n_workers=24,
                           sampling_period_s=5.0,
                           trace_index=np.arange(24) % 6)
    st = pool.run()
    assert np.all(pool.e_harvest + 1e-9 >= pool.e_work)
    assert st.energy_harvested_j + 1e-9 >= (
        st.energy_on_work_j + st.energy_on_nvm_j + st.energy_on_sleep_j)
    assert st.energy_on_nvm_j == 0.0


def test_scalar_executor_energy_conservation():
    costs = _costs40()
    acc = np.linspace(1 / 6, 0.9, 41)
    tr = get_trace("SOR", duration_s=120.0)
    st = IntermittentExecutor(tr, costs, Greedy(), acc,
                              sampling_period_s=5.0).run()
    assert st.energy_harvested_j + 1e-9 >= (
        st.energy_on_work_j + st.energy_on_nvm_j)


def test_trace_determinism_under_fixed_seed():
    """energy.py traces are replayable: same seed -> identical arrays."""
    for name in ("RF", "SOM", "SIM", "SOR", "SIR", "KIN"):
        a = get_trace(name, duration_s=30.0)
        b = get_trace(name, duration_s=30.0)
        assert np.array_equal(a.power_w, b.power_w), name
    a = get_trace("RF", seed=11, duration_s=30.0)
    b = get_trace("RF", seed=12, duration_s=30.0)
    assert not np.array_equal(a.power_w, b.power_w)


# ---------------------------------------------------------------------------
# scheduler end-to-end
# ---------------------------------------------------------------------------


def _small_fleet(duration_s=60.0, n_workers=32, seed=0):
    wls = [har_workload(), harris_workload(), lm_workload()]
    power = make_power_matrix(["SOM", "SOR", "SIR", "RF"], 8, duration_s,
                              DT, seed)
    pool = build_dispatch_pool(power, DT, n_workers, wls, seed)
    sched = FleetScheduler(pool, wls, max_batch=4)
    n_steps = int(duration_s / DT)
    stream = RequestStream(n_workers / 10.0, np.array([0.4, 0.3, 0.3]),
                           n_steps, DT, seed=seed + 1)
    return pool, sched, stream, n_steps, wls


def test_scheduler_serves_all_workloads_and_accounts_requests():
    pool, sched, stream, n_steps, wls = _small_fleet()
    summary = run_fleet(pool, sched, stream, n_steps)
    assert summary["completed"] > 0
    assert set(summary["per_workload"]) == {"har", "harris", "lm"}
    # request conservation: every submitted request is accounted for
    accounted = (summary["completed"] + summary["rejected"]
                 + summary["shed"] + summary["lost"] + sched.backlog
                 + sched.inflight_count)
    assert accounted == summary["submitted"]
    # every device-side assignment has a control-plane owner
    pending = int(pool.p_pending.sum() + pool.has_work.sum())
    assert int((sched.state.f_n > 0).sum()) >= pending
    # SMART admission: mean delivered accuracy sits in the floored regime
    # (partial anytime emissions may dip below a single request's floor,
    # but the mix cannot collapse to zero-knob spam)
    for name, per in summary["per_workload"].items():
        assert per["mean_units"] > 0
    assert summary["mean_expected_accuracy"] > 0.5
    assert summary["energy"]["conservation_ok"]


def test_scheduler_beats_independent_baseline():
    """The headline fleet claim at test scale: same offered load, mixed
    rich/poor traces -> routing + shedding complete more requests."""
    wls = [har_workload(), harris_workload(), lm_workload()]
    power = make_power_matrix(["RF", "SOM", "SIM", "SOR", "SIR"], 10,
                              120.0, DT, seed=5)
    n_steps = int(120.0 / DT)
    mix = np.array([0.4, 0.3, 0.3])
    sched = run_scheduled(power, DT, 64, wls, rate_rps=6.4, mix=mix,
                          n_steps=n_steps, seed=5)
    indep = run_independent(power, DT, 64, wls, mix=mix, period_s=10.0,
                            n_steps=n_steps, seed=5)
    assert sched["completed"] > indep["completed"]


def test_dispatch_batching_amortizes_overhead():
    """Several cheap requests ride one power cycle: the assignment batch
    histogram must show multi-request batches."""
    wl = lm_workload()  # cheap workload -> batching actually happens
    power = make_power_matrix(["SOM"], 2, 30.0, DT, seed=7)
    pool = build_dispatch_pool(power, DT, 4, [wl], seed=7)
    sched = FleetScheduler(pool, [wl], max_batch=4)
    n_steps = int(30.0 / DT)
    stream = RequestStream(8.0, np.array([1.0]), n_steps, DT, seed=8)
    summary = run_fleet(pool, sched, stream, n_steps)
    assert summary["completed"] > 0
    assert sum(summary["batch_hist"][2:]) > 0  # batches of >= 2 happened


def test_straggler_eviction_requeues_pending_on_dead_worker():
    """A request assigned to a worker that never turns on is evicted at
    the straggler deadline and requeued (not stuck forever)."""
    wl = lm_workload()
    power = np.zeros((1, 12000))  # no harvest at all: no recharge, ever
    pool = build_dispatch_pool(power, DT, 1, [wl], seed=0)
    # charged and dispatchable at assignment time...
    pool.on[0] = True
    pool.v[0] = pool.v_on
    sched = FleetScheduler(pool, [wl], grace_s=5.0, max_retries=0,
                           shed_after_s=1e9)
    serve = _HostServe(pool, sched, dispatch_every=10, obs=None)
    step = pool.step

    def brown_out_then_step(i):
        if i == 0:
            # ...but browns out between dispatch and acquire: the
            # assignment is stuck
            assert pool.p_pending[0]
            pool.on[0] = False
            pool.v[0] = pool.v_off
        step(i)

    pool.step = brown_out_then_step
    serve.window(np.ones((1, 1), dtype=np.int64), 0)  # tick 0 dispatches
    assert sched.inflight_count == 1
    t_fire = None
    for i0 in range(1, 12000, 10):
        serve.window(np.zeros((10, 1), dtype=np.int64), i0)
        if sched.inflight_count == 0:
            t_fire = i0 * DT
            break
    assert t_fire is not None, "assignment never evicted"
    assert int(sched.state.evicted) == 1
    assert not pool.p_pending[0]  # the device-side assignment is revoked
    assert int(sched.state.lost) == 1  # max_retries=0: loss is terminal
