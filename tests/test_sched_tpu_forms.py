"""The control plane's TPU forms against their NumPy twins, at the bounds
they claim.

Under jax, ``repro.fleet.sched`` sums bounded counts in int32, divides
one batch's units in int32, sorts on an int64 image of the float64 rank
key, looks knobs up by counting table entries instead of a binary
search, gathers/scatters (N, B) slot arrays one column at a time, and
sheds by reading the queue rings in physical order (see the docstrings
for why XLA:TPU needs each). Every form must give the NumPy twin's
integers exactly.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet import sched as S

N_MAX = 1_048_576  # the top of the fleet-size axis
B_MAX = 4  # --max-batch default


def _jit(fn, *args):
    with jax.enable_x64(True):
        return jax.tree.map(np.asarray, jax.jit(fn)(*args))


def _sched_params(n):
    """The control plane's parameters for ``n`` workers running the three
    paper workloads on a constant 1 mW harvest."""
    from repro.fleet.worker import FleetWorkerPool
    from repro.fleet.workloads import har_workload, harris_workload, \
        lm_workload
    wls = [har_workload(), harris_workload(), lm_workload()]
    pool = FleetWorkerPool(np.full((1, 100), 1e-3), 0.01,
                           workloads=[w.costs for w in wls],
                           mode="dispatch", n_workers=n)
    return S.make_sched_params(pool.params, wls, max_batch=B_MAX)


@pytest.mark.parametrize("fill", ["bound", "random"])
def test_batch_cumsum_int32_at_a_million_workers(fill):
    """Dispatch's per-worker batch prefix sum: N * B stays below 2**31."""
    rng = np.random.default_rng(0)
    b = (np.full(N_MAX, B_MAX, np.int64) if fill == "bound"
         else rng.integers(0, B_MAX + 1, N_MAX).astype(np.int64))
    got = _jit(lambda x: S._cumsum(x, jnp), b)
    want = S._cumsum(b, np)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)
    if fill == "bound":
        assert got[-1] == N_MAX * B_MAX


@pytest.mark.parametrize("fill", ["bound", "random"])
def test_slot_rank_cumsum_int32_at_a_million_workers(fill):
    """Requeue's and the quality ledger's (worker, slot)-order ranks."""
    rng = np.random.default_rng(1)
    m = (np.ones((N_MAX, B_MAX), np.int64) if fill == "bound"
         else (rng.random((N_MAX, B_MAX)) < 0.3).astype(np.int64))
    got = _jit(lambda x: S._cumsum_slots(x, jnp), m)
    np.testing.assert_array_equal(got, S._cumsum_slots(m, np))
    np.testing.assert_array_equal(got.reshape(-1),
                                  np.cumsum(m.reshape(-1)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rank_sort_matches_numpy_on_near_ties(seed):
    """The int64 order key sorts exactly as NumPy's stable float64
    argsort: one-ulp neighbours, exact ties, 0.0 against -0.0, invalid
    entries last in index order."""
    rng = np.random.default_rng(seed)
    n = N_MAX if seed == 0 else 4096
    x = rng.uniform(0, 1e-2, n) * 10.0 ** rng.integers(-8, 3, n)
    k = n // 3
    x[rng.integers(0, n, k)] = np.nextafter(
        x[rng.integers(0, n, k)], np.inf * rng.choice([-1, 1], k))
    x[rng.integers(0, n, 50)] = 0.0
    x[rng.integers(0, n, 50)] = -0.0
    x[rng.integers(0, n, 100)] = x[rng.integers(0, n, 100)]
    x = np.where(rng.random(n) < 0.5, x, -x)
    valid = rng.random(n) < 0.8
    got = _jit(lambda a, v: S._argsort(a, v, jnp), x, valid)
    np.testing.assert_array_equal(got, S._argsort(x, valid, np))


def test_knob_lookup_counts_match_numpy_binary_search():
    """Dispatch's knob lookup (a count of the table entries at or below
    each query) against NumPy's right-side binary search, on a
    ``+inf``-padded non-decreasing float64 table with repeated entries:
    queries on every entry, one ulp either side of it, below the first
    entry and above the last finite one."""
    rng = np.random.default_rng(5)
    n, k, finite = 131_072, 142, 121
    table = np.full(k, np.inf)
    steps = rng.uniform(0, 1e-4, finite - 1) * (rng.random(finite - 1) < 0.8)
    table[:finite] = np.concatenate([[0.0], np.cumsum(steps)]) + 1e-6
    ent = table[:finite]
    edge = np.concatenate([ent, np.nextafter(ent, np.inf),
                           np.nextafter(ent, -np.inf),
                           [-1.0, 0.0, -np.inf, ent[0] / 2,
                            ent[-1] * 2, 1e300]])
    v = np.concatenate([edge, rng.choice(edge, n - edge.size - n // 4),
                        rng.uniform(-1e-5, ent[-1] * 1.1, n // 4)])
    assert v.size == n
    assert np.unique(ent).size < finite  # repeated entries
    got = _jit(lambda a, q: S._searchsorted_right(a, q, jnp), table, v)
    want = S._searchsorted_right(table, v, np)
    np.testing.assert_array_equal(got, want)
    assert want.min() == 0 and want.max() == finite


def test_dispatch_lowers_without_a_while_loop():
    """The knob lookups of ``dispatch`` stay loop-free: a binary search
    would bring back a ``while`` of per-level gathers."""
    n = 256
    sp = _sched_params(n)
    ss = tuple(np.asarray(getattr(S.make_sched_state(sp), f))
               for f in S.SS._fields)
    rng = np.random.default_rng(6)
    budget = rng.uniform(0, 1e-2, n)
    with jax.enable_x64(True):
        text = jax.jit(lambda s, d, bn, bp: S.dispatch(
            sp, S.SS(*s), d, bn, bp, 1.0, jnp)).lower(
            ss, np.ones(n, bool), budget, budget).as_text()
    assert "stablehlo.while" not in text
    assert "stablehlo.sort" in text  # the rank sort, so it is dispatch


def test_slot_gather_scatter_match_numpy():
    rng = np.random.default_rng(3)
    n, b, q = 2048, B_MAX, 10000
    ring = rng.random(q)
    idx = rng.integers(0, q, (n, b))
    v = rng.random((n, b))
    # unique targets, plus a shared dump slot whose write is discarded
    perm = rng.permutation(q - 1)[:n * b].reshape(n, b)
    dump = rng.random((n, b)) < 0.3
    phys = np.where(dump, q - 1, perm)
    order = rng.permutation(n)
    hist = rng.integers(0, 33, (n, b))
    got = _jit(lambda r, i, p, vv, o, h: (
        S._take(r, i, jnp), S._scatter_set(r, p, vv, jnp)[:-1],
        S._rows(vv, S._inverse_permutation(o, jnp), jnp),
        S._bincount(h, 32, jnp)),
        ring, idx, phys, v, order, hist)
    rank = S._inverse_permutation(order, np)
    want = (S._take(ring, idx, np), S._scatter_set(ring, phys, v, np)[:-1],
            S._rows(v, rank, np), S._bincount(hist, 32, np))
    np.testing.assert_array_equal(rank, np.argsort(order))
    moved = np.zeros((n, b))
    moved[order] = v  # rank order back to worker order
    np.testing.assert_array_equal(want[2], moved)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("dtype", [np.float64, np.int64, np.int32])
def test_table_lookup_and_packed_samples_match_numpy(dtype):
    """Collect's per-worker reads of a knob row (a select chain under jax,
    no gather) and of the packed (sample, units) quality table."""
    rng = np.random.default_rng(7)
    k, n = 142, 131_072
    table = (rng.uniform(0, 1, k) if dtype is np.float64
             else rng.integers(-2**40 if dtype is np.int64 else -2**30,
                               2**30, k)).astype(dtype)
    idx = rng.integers(0, k, n)
    got = _jit(lambda i: S._lookup(table, i, jnp), idx)
    assert got.dtype == dtype
    np.testing.assert_array_equal(got, S._lookup(table, idx, np))
    np.testing.assert_array_equal(got, table[idx])
    q = rng.integers(0, 2, (144, k))  # HAR's oracle samples, 4.5 words
    words = S._sample_words(q)
    s_ = rng.integers(0, q.shape[0], n)
    bit = (words[idx, s_ // 32] >> (s_ % 32)) & 1
    np.testing.assert_array_equal(bit, q[s_, idx])


def test_collect_ledger_int32_forms_at_large_counters():
    """Collect's int32 unit division and the ledger's reduced sample
    numbering, with run-long completion counters far past 2**31."""
    rng = np.random.default_rng(4)
    n = 512
    sp = _sched_params(n)
    ss = S.make_sched_state(sp)
    ss.f_n = rng.integers(0, B_MAX + 1, n).astype(np.int64)
    ss.f_wl = rng.integers(0, sp.W, n).astype(np.int64)
    ss.f_units = rng.integers(0, int(sp.NU.max()) + 1, n).astype(np.int64)
    ss.f_arr = rng.uniform(0.0, 5.0, (n, B_MAX))
    ss.f_retry = rng.integers(0, 3, (n, B_MAX)).astype(np.int64)
    ss.completed_wl = (np.int64(1) << 40) + rng.integers(0, 1 << 20, sp.W)
    emit = rng.random(n) < 0.6
    lost = ~emit & (rng.random(n) < 0.3)
    units = rng.integers(0, B_MAX * int(sp.NU.max()) + 1, n)
    args = (emit, lost, units.astype(np.int64), 7.5)
    want = S._collect_impl(sp, S.SS(*(getattr(ss, f)
                                      for f in S.SS._fields)), *args, np)
    got = _jit(lambda s, e, lo, u: S._collect_impl(sp, S.SS(*s), e, lo, u,
                                                   7.5, jnp),
               tuple(getattr(ss, f) for f in S.SS._fields), *args[:3])
    assert int(want.completed) > 0
    for f, g, w in zip(S.SS._fields, got, want):
        w = np.asarray(w)
        if w.dtype.kind == "f":
            np.testing.assert_allclose(g, w, rtol=1e-12, err_msg=f)
        else:
            np.testing.assert_array_equal(g, w, err_msg=f)


def _shed_by_gather(q_t, q_head, q_len, t, after):
    """Shed's first form: gather each queue into logical order and take
    the first position that is not stale."""
    q = q_t.shape[1]
    j = np.arange(q)[None, :]
    log_t = np.take_along_axis(q_t, (q_head[:, None] + j) % q, axis=1)
    stale = (j < q_len[:, None]) & (t - log_t > after)
    n = np.min(np.where(stale, q, j), axis=1)
    return (q_head + n) % q, q_len - n, n


SHED_Q, SHED_T, SHED_AFTER = 64, 100.0, 30.0
OLD, NEW, EDGE = 10.0, 99.0, SHED_T - SHED_AFTER  # stale, fresh, age == 30


# each case: per queue (head, arrival times in logical order, n_shed)
SHED_CASES = {
    "wraps_past_the_last_slot": [
        (SHED_Q - 5, [OLD] * 8 + [NEW] * 4, 8),
        (SHED_Q - 1, [OLD] * 3, 3),
        (SHED_Q - 2, [NEW] + [OLD] * 6, 0)],
    "full_ring_all_stale": [
        (17, [OLD] * SHED_Q, SHED_Q), (0, [OLD] * SHED_Q, SHED_Q),
        (SHED_Q - 1, [OLD] * SHED_Q, SHED_Q)],
    "empty_queues": [(0, [], 0), (31, [], 0), (SHED_Q - 1, [], 0)],
    "fresh_head_before_stale_retries": [
        (3, [NEW] + [OLD] * 9, 0), (SHED_Q - 2, [NEW, NEW] + [OLD] * 5, 0),
        (40, [EDGE] + [OLD] * 4, 0)],
    "stale_run_ends_mid_queue": [
        (40, [OLD] * 7 + [NEW] * 13, 7),
        (SHED_Q - 3, [OLD] * 5 + [NEW] + [OLD] * 4, 5),
        (9, [OLD] * 30 + [NEW], 30)],
    "age_equal_to_the_limit_is_fresh": [
        (0, [OLD] * 3 + [EDGE] + [OLD] * 2, 3), (50, [EDGE] * 4, 0),
        (SHED_Q - 2, [np.nextafter(EDGE, 0.0), EDGE, OLD], 1)],
}


@pytest.mark.parametrize("case", sorted(SHED_CASES))
def test_shed_physical_order_matches_logical_gather(case):
    """Shed's masked min over physical slots under jit equals NumPy's
    and the logical-order gather's, integer for integer. Retries re-enter
    at a queue's front with their original arrival times, so a fresh head
    may stand before stale entries; then nothing is shed."""
    rows = SHED_CASES[case]
    sp = dataclasses.replace(_sched_params(8), Q=SHED_Q,
                             shed_after_s=SHED_AFTER)
    q_t = np.zeros((sp.W, SHED_Q))  # unqueued slots read stale
    q_head = np.array([h for h, _, _ in rows], np.int64)
    q_len = np.array([len(a) for _, a, _ in rows], np.int64)
    for w, (h, arr, _) in enumerate(rows):
        q_t[w, (h + np.arange(len(arr))) % SHED_Q] = arr
    ss = S.make_sched_state(sp)
    ss = S.SS(*(getattr(ss, f) for f in S.SS._fields))._replace(
        q_t=q_t, q_head=q_head, q_len=q_len, shed=np.int64(5))
    head, length, n = _shed_by_gather(q_t, q_head, q_len, SHED_T,
                                      SHED_AFTER)
    np.testing.assert_array_equal(n, [k for _, _, k in rows])
    want = (head, length, 5 + n.sum())
    host = S.shed(sp, ss, SHED_T, np)
    dev = _jit(lambda s, t: S.shed(sp, s, t, jnp), ss, SHED_T)
    for got in (host, dev):
        for f, w in zip(("q_head", "q_len", "shed"), want):
            g = np.asarray(getattr(got, f))
            assert g.dtype == np.int64, f
            np.testing.assert_array_equal(g, w, err_msg=f)


def test_shed_lowers_without_a_gather():
    """At the 131,072-worker ring (3 queues of 4,096 + 131,072 * 4
    slots) shed reads the ring in place: no gather of the float64
    arrival times comes back."""
    q = 4096 + 131_072 * B_MAX
    sp = _sched_params(8)
    ss = S.make_sched_state(sp)
    ss = S.SS(*(getattr(ss, f) for f in S.SS._fields))
    sp = dataclasses.replace(sp, Q=q)
    args = (jax.ShapeDtypeStruct((sp.W, q), jnp.float64),
            jax.ShapeDtypeStruct((sp.W,), jnp.int64),
            jax.ShapeDtypeStruct((sp.W,), jnp.int64),
            jax.ShapeDtypeStruct((), jnp.float64))

    def fn(q_t, q_head, q_len, t):
        out = S.shed(sp, ss._replace(q_t=q_t, q_head=q_head, q_len=q_len),
                     t, jnp)
        return out.q_head, out.q_len, out.shed

    with jax.enable_x64(True):
        text = jax.jit(fn).lower(*args).as_text()
    assert not re.search(r"stablehlo\.(dynamic_)?gather", text)
    assert "stablehlo.reduce" in text  # the row min, so it is shed
