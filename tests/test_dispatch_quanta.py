"""Dispatch on integer quanta: a quantized fleet decides affordability,
batch size, knob and rank from its usable quanta ``us`` and the int32
threshold tables of ``sched.quanta_thresholds``, and those decisions are
the IEEE float64 decisions on the budget ``fl(us * quantum_j)``.

Budgets are whole quanta and costs whole nanojoules, so exact ties are
common, and a device whose float64 is not IEEE (TPU v5e emulates it)
broke them otherwise than the reference. The tables are checked entry
by entry against the IEEE expressions, swept densely over a small
capacitor, pinned on the tie that exposed the fault, and run through
``dispatch`` under NumPy and under jit.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet import sched as S
from repro.fleet.worker import FleetWorkerPool
from repro.launch.fleet import WORKLOAD_FACTORIES

Q = 1e-9  # the quantized kernels' quantum, J
B = 4  # --max-batch default
I32_MAX = np.iinfo(np.int32).max
HAR_FLOOR_US = 5_956_500  # the least us affording HAR's floor knob 41


def _params(n=8, **kw):
    wls = [WORKLOAD_FACTORIES[k]() for k in ("har", "harris", "lm")]
    pool = FleetWorkerPool(np.full((1, 100), 1e-3), 0.01,
                           workloads=[w.costs for w in wls],
                           mode="dispatch", n_workers=n, kernel="q32")
    return S.make_sched_params(pool.params, wls, max_batch=B, **kw)


def _float(sp):
    """The same params without the tables: dispatch's float64 path."""
    return dataclasses.replace(sp, THR_AFF=None, THR_B=None, THR_U=None)


@pytest.fixture(scope="module")
def sp():
    return _params()


# the IEEE float64 expression each table entry decides, as dispatch
# evaluates it on the budget fl(us * q); ix is the entry's index
def _aff(sp, us, w, k):
    return sp.CU[w, k] <= np.float64(us) * Q


def _batch(sp, us, w, j, b):
    spend = np.float64(us) * Q - (sp.FIX[w] + sp.EMITC[w])
    cpb = sp.UCUM[w, j]
    fits = np.floor_divide(spend, max(cpb, 1e-300)) if cpb > 0 else B
    return fits >= b + 1


def _knob(sp, us, w, a, k):
    spend = np.float64(us) * Q - (sp.FIX[w] + sp.EMITC[w])
    return sp.UCUM[w, k] <= spend / np.int64(a + 1)


TABLES = {"THR_AFF": _aff, "THR_B": _batch, "THR_U": _knob}


@pytest.mark.parametrize("table", sorted(TABLES))
def test_every_threshold_is_where_its_ieee_comparison_turns(sp, table):
    """At ``T - 1`` the entry's float64 comparison is false and at ``T``
    it is true; ``INT32_MAX`` marks a comparison never true (a padded
    ``+inf`` cost)."""
    thr = getattr(sp, table)
    pred = TABLES[table]
    never = 0
    with np.errstate(invalid="ignore"):
        for ix in np.ndindex(thr.shape):
            t = int(thr[ix])
            if t == I32_MAX:
                never += 1
                assert not pred(sp, I32_MAX - 1, *ix), ix
                continue
            assert pred(sp, t, *ix), ix
            assert t == 0 or not pred(sp, t - 1, *ix), ix
    assert 0 < never < thr.size
    # rows are sorted where dispatch counts along them
    assert np.all(np.diff(thr.astype(np.int64), axis=-1) >= 0)
    if table == "THR_B":
        assert np.all(np.diff(thr.astype(np.int64), axis=1) >= 0)


@pytest.mark.parametrize("w", [0, 1, 2])
def test_dense_sweep_of_a_small_capacitor(sp, w):
    """Every ``us`` of a 100 uF capacitor between brown-out (1.8 V) and
    full (3.6 V), 486,000 quanta: each count of thresholds equals the
    float64 lookup it replaces, for every knob and batch."""
    us = np.arange(486_001)
    bn = us * Q
    spend = bn - (sp.FIX[w] + sp.EMITC[w])
    np.testing.assert_array_equal(
        np.searchsorted(sp.THR_AFF[w], us, side="right"),
        np.searchsorted(sp.CU[w], bn, side="right"))
    for a in range(B):
        np.testing.assert_array_equal(
            np.searchsorted(sp.THR_U[w, a], us, side="right"),
            np.searchsorted(sp.UCUM[w], spend / np.int64(a + 1),
                            side="right"))
    for j in range(sp.UCUM.shape[1]):
        cpb = sp.UCUM[w, j]
        with np.errstate(invalid="ignore"):
            fd = (np.floor_divide(spend, max(cpb, 1e-300)) if cpb > 0
                  else np.full(us.shape, float(B)))
        np.testing.assert_array_equal(
            np.searchsorted(sp.THR_B[w, j], us, side="right"),
            np.clip(fd, 0, B).astype(np.int64))


def _one_har_request(sp):
    ss = S.make_sched_state(sp)
    ss.q_len = np.array([1, 0, 0])
    return tuple(np.asarray(getattr(ss, f)) for f in S.SS._fields)


# the float budget handed to dispatch beside us = 5,956,500: IEEE's
# fl(us * 1e-9), one ulp below it (the cost itself, an exact tie), and
# what the chip's emulated float64 made of it (PERF.md, section 7)
BUDGETS = {"ieee": 0.0059565, "one_ulp_down": 0.005956499999999999,
           "chip": 0.00595649999999999}


@pytest.mark.parametrize("budget", sorted(BUDGETS))
@pytest.mark.parametrize("xp", ["numpy", "jit"])
def test_the_witness_tie_takes_the_floor_knob(budget, xp):
    """``us`` = 5,956,500 quanta affords HAR's floor knob 41, whose cost
    is one ulp under ``fl(us * 1e-9)``. One worker and one HAR request
    get knob 41 from the quanta whatever the float budget beside them
    reads. The float path ties at one ulp down and dispatches nothing at
    the chip's budget (its affordable knob, 40, is under the floor)."""
    sp = _params(n=1)
    us = np.array([HAR_FLOOR_US], np.int32)
    assert us[0] * Q == BUDGETS["ieee"]
    assert np.nextafter(BUDGETS["ieee"], 0) == BUDGETS["one_ulp_down"]
    assert sp.CU[0, 41] == BUDGETS["one_ulp_down"] and sp.P_REQ[0] == 41
    bn = np.array([BUDGETS[budget]])
    st = _one_har_request(sp)
    args = (np.ones(1, bool), bn, bn)
    if xp == "numpy":
        _, a = S.dispatch(sp, S.SS(*st), *args, 1.0, np, us=us)
    else:
        with jax.enable_x64(True):
            _, a = jax.tree.map(np.asarray, jax.jit(
                lambda s, d, bn, bp, us: S.dispatch(
                    sp, S.SS(*s), d, bn, bp, 1.0, jnp, us=us))(
                st, *args, us))
    assert (bool(a.mask[0]), int(a.units[0]), int(a.batch[0])) == (
        True, 41, 1)
    _, f = S.dispatch(_float(sp), S.SS(*st), *args, 1.0, np)
    assert bool(f.mask[0]) == (budget != "chip")


def _planted(sp, n, seed):
    """Usable quanta on table entries, one quantum either side, and
    uniform over a full capacitor."""
    rng = np.random.default_rng(seed)
    thr = np.concatenate([sp.THR_AFF.ravel(), sp.THR_B.ravel(),
                          sp.THR_U.ravel()])
    thr = thr[thr < I32_MAX].astype(np.int64)
    us = rng.choice(thr, n) + rng.integers(-1, 2, n)
    us[:n // 8] = rng.integers(0, 12_000_000, n // 8)
    return rng, np.maximum(us, 0).astype(np.int32)


@pytest.mark.parametrize("sched", ["reactive", "quality", "forecast"])
def test_numpy_and_jit_dispatch_agree_on_planted_budgets(sched):
    """4,096 workers with budgets on the thresholds: NumPy and jit give
    the same state and assignments bit for bit, and both equal the
    float64 path's decisions (forecast plans on a float budget)."""
    n = 4096
    sp = _params(n=n, sched=sched)
    rng, us = _planted(sp, n, seed=len(sched))
    bn = us * Q
    bp = bn * (1 + rng.random(n) * 0.1) if sched == "forecast" else bn
    ss = S.make_sched_state(sp)
    ss.q_len = np.array([1500, 1200, 900])
    ss.q_t[:, :1500] = np.sort(rng.uniform(0, 1, (3, 1500)), axis=1)
    ss.q_r[:, :1500] = rng.integers(0, 3, (3, 1500))
    st = tuple(np.asarray(getattr(ss, f)) for f in S.SS._fields)
    d = rng.random(n) < 0.9
    want = S.dispatch(sp, S.SS(*st), d, bn, bp, 2.0, np, us=us)
    with jax.enable_x64(True):
        got = jax.tree.map(np.asarray, jax.jit(
            lambda s, d, bn, bp, us: S.dispatch(
                sp, S.SS(*s), d, bn, bp, 2.0, jnp, us=us))(st, d, bn, bp,
                                                           us))
    old = S.dispatch(_float(sp), S.SS(*st), d, bn, bp, 2.0, np)
    assert int(want[1].batch.sum()) > 0
    for g, w_, o in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        jax.tree.leaves(old)):
        np.testing.assert_array_equal(g, w_)
        np.testing.assert_array_equal(w_, o)


def test_quantized_dispatch_needs_the_quanta(sp):
    st = _one_har_request(sp)
    bn = np.zeros(8)
    with pytest.raises(ValueError, match="usable quanta"):
        S.dispatch(sp, S.SS(*st), np.ones(8, bool), bn, bn, 1.0, np)
