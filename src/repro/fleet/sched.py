"""Array-native fleet control plane: scheduling as pure array ops.

The PR-1 scheduler was a per-request Python object loop (``Request``
dataclasses in deques, dict-keyed in-flight tickets). At fleet scale that
loop *is* the bottleneck: the JAX worker backend had to break its
``lax.scan`` at every scheduler macro-step for a host round-trip. This
module re-expresses every control-plane mechanism as pure, xp-parametric
(``xp`` is numpy or jax.numpy) functions over the struct-of-arrays
``SchedState`` so the same expressions serve two evaluation modes:

- the NumPy reference (the host window ``_HostServe`` in
  ``repro.fleet.scheduler`` drives them tick-by-tick on the host), and
- the fused JAX path (``backend_jax.run_serve`` traces them *inside* the
  worker scan, so an entire serve trace — workers and scheduler — runs as
  one device launch with no host interleaving).

Mechanisms (array formulation of the PR-1 semantics):

- **Admission** — per-tick arrival *counts* per workload; arrivals beyond
  the global ``max_queue`` backlog are rejected (cumulative-count clip,
  workload-index order within a tick).
- **Queues** — one fixed-capacity ring buffer per workload holding
  (arrival time, retry count); retries/evictions re-enter at the *front*
  with their original arrival time (the paper prefers fresh samples, so a
  retried old request must not leapfrog shedding).
- **Shedding** — the longest stale prefix of each queue (age beyond
  ``shed_after_s``) is dropped up to the first fresh entry.
- **Routing** — dispatchable workers are ranked by a *budget score*
  (stable argsort, richest first); queues are served oldest-head-first.
  Reactive mode scores instantaneous usable energy; forecast mode scores
  the conditional expectation of usable energy over the next
  ``lookahead`` window under the worker's *compiled harvest forecaster*
  (``repro.core.forecast``: OU mean reversion, occlusion/burst regime
  models, or a learned AR(p) fit — selected per trace row) — a
  momentarily occluded worker on a rich trace outranks a momentarily
  charged worker on a scarce one.
- **Batching** — each assigned worker takes the largest batch of
  floor-knob requests its *planning* budget affords (forecast mode plans
  with expected inflow: harvest arriving while the batch executes funds
  in-flight work; shortfalls degrade to the worker's partial-emission
  path, not losses), then refines the per-request knob greedily. Queue
  consumption across workers is a cumulative-sum slice assignment — no
  per-request loop.
- **Eviction** — assignments that outlive
  ``grace + deadline_factor * est`` (``est`` from the per-worker MCU
  active power: heterogeneous fleets straggle heterogeneously) are
  revoked and requeued, the ``runtime.straggler`` deadline rule.
- **Quality-aware service** (``sched="quality"``) — queues are served in
  descending *marginal accuracy-per-joule* order (``SchedParams.QVALUE``,
  computed from the workload accuracy tables — measured oracle tables
  under ``repro.quality``) instead of oldest-head-first: when harvested
  energy cannot serve the whole backlog, the joules go to the requests
  that buy the most measured accuracy, and the starved low-value queues
  age out through the ordinary stale-prefix shed — value-ranked shedding
  without a second drop mechanism. Reactive and forecast modes are
  untouched (the rank key is the only difference, guarded by
  ``value_order``).
- **Quality ledger** — on every completion, ``collect`` reads the
  request's *measured* quality from the precomputed
  ``(workload, sample, units)`` oracle table (``repro.quality.oracles``)
  and its table-priced spend in integer nanojoules, accumulating both
  into per-workload ``SchedState`` counters. Sample ids are assigned
  deterministically (the per-workload completion counter, cycling mod
  the oracle set size), so the fused scan needs no per-request records
  and both backends ledger identically.

Agreement contract: every *decision* (ranking, admission, batch sizes,
knob units, shed/evict counts) is integer arithmetic or elementwise IEEE
float ops evaluated identically by numpy and jax.numpy under
``enable_x64``, with stable sorts on both sides — the NumPy and fused-JAX
control planes agree exactly on emitted/skipped/power-cycle/completion
counts (pinned by tests/test_fleet_backends.py). A quantized fleet's
dispatch decisions are integer counts of thresholds on its usable
quanta (:func:`quanta_thresholds`), equal to the IEEE float64 ones on a
device whose float64 is not IEEE too. Float *metric*
accumulators (latency sums, accuracy sums) may differ by reduction order
ulps and are compared with tolerances.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Sequence

import numpy as np

from repro.core.forecast import (FORECASTER_MODES, RowForecast,
                                 fit_row_forecast, usable_energy_rows,
                                 zero_row_forecast)
from repro.fleet.state import (SCHED_FIELDS, FleetParams, SchedParams,
                               SchedState, init_sched_state)

SS = collections.namedtuple("SS", SCHED_FIELDS)

Assignment = collections.namedtuple("Assignment",
                                    ["mask", "wl", "units", "batch"])

SCHED_MODES = ("reactive", "forecast", "quality")

_BIG = np.int64(1) << 40  # sentinel: floor unattainable -> never afford

_S_PROXY = 64  # synthetic oracle rows for workloads without a measured
# per-sample table: row s of the quantized table scores "correct" at u
# units iff s < round(accuracy[u] * _S_PROXY), so the ledgered mean
# reproduces the proxy accuracy curve to 1/64 without any randomness.


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------


def make_sched_params(p: FleetParams, workloads: Sequence, *,
                      max_queue: int = 4096, shed_after_s: float = 30.0,
                      max_batch: int = 4, max_retries: int = 2,
                      grace_s: float = 20.0, deadline_factor: float = 1.5,
                      sched: str = "reactive", lookahead_s: float = 5.0,
                      forecaster: str = "ou",
                      trace_families: Sequence[str] | None = None,
                      arp_order: int = 3,
                      forecaster_fit: str = "full",
                      lat_bins: int = 64, shards: int = 1,
                      rebalance_every: int = 0,
                      rebalance_max: int = 8,
                      persist: str = "none",
                      fram_write_j_per_byte: float = 18e-9,
                      fram_read_j_per_byte: float = 7e-9) -> SchedParams:
    """Compile the control-plane constants for one fleet.

    Stacks the workload cost/accuracy tables (joules / dimensionless),
    then fits + compiles the pluggable harvest forecaster
    (``repro.core.forecast``) per power-matrix row and gathers it per
    worker via ``p.trace_index``.

    Args:
        p: the fleet's static device configuration.
        workloads: ``FleetWorkload`` sequence (cost tables in J).
        max_queue: global admission bound, requests.
        shed_after_s / grace_s: staleness / straggler windows, seconds.
        max_batch: per-assignment batch cap, requests.
        max_retries: retry budget before a request counts as lost.
        deadline_factor: straggler deadline multiplier (dimensionless).
        sched: "reactive" (instantaneous budget), "forecast", or
            "quality" (reactive budget, queues served by marginal
            measured-accuracy-per-joule instead of age).
        lookahead_s: forecast window, seconds (rounded to >= 1 tick).
        forecaster: one of ``repro.core.forecast.FORECASTER_MODES``;
            "auto" picks a model per trace row (by ``trace_families``
            labels when given, else label-free classification).
        trace_families: optional per-power-row family names ("SOM", ...).
        arp_order: lag order p of the "arp" model (ticks).
        forecaster_fit: "full" fits the forecaster on the whole (R, T)
            bank (the historical offline behavior — it reads harvest
            samples the run has not produced yet); "causal" starts from
            the zero-inflow prior and leaves fitting to prefix-only
            refits (``FleetScheduler.refit_forecast``). Both compile to
            the same ``fc_order`` so refits never re-trace the scan.
        shards: hierarchical control planes (``--mesh-fleet K``): the
            worker axis splits into K contiguous blocks, each running an
            independent plane over ``n/K`` workers and a ``max_queue/K``
            admission slice. Must divide ``n`` evenly.
        rebalance_every: cross-shard work-stealing cadence in ticks
            (0 = off; when on, must be a positive multiple of the run's
            ``dispatch_every`` — checked at serve time).
        rebalance_max: per-workload cap on requests moved to the ring
            successor per rebalance event (the ppermute buffer width).
        persist: execution discipline ("none" | "ckpt" | "undolog") —
            must match the fleet's ``FleetParams.persist``. Exact
            disciplines pin the dispatch knob at NU and relax admission
            to the fixed+emit overhead (docs/persistence_plane.md).
        fram_write_j_per_byte / fram_read_j_per_byte: the NVM per-byte
            energies pricing the persistence plane (provenance record;
            the device-side joule tables live in ``FleetParams``).
    Returns:
        a frozen :class:`SchedParams`. Its ``quality`` provenance label
        is inferred: "measured" when any workload carries a per-sample
        oracle table (``qtab``), "proxy" otherwise.
    """
    if sched not in SCHED_MODES:
        raise ValueError(f"unknown sched mode {sched!r}; "
                         f"choose from {SCHED_MODES}")
    if forecaster not in FORECASTER_MODES:
        raise ValueError(f"unknown forecaster {forecaster!r}; "
                         f"choose from {FORECASTER_MODES}")
    shards = int(shards)
    if shards < 1 or p.n % shards:
        raise ValueError(
            f"--mesh-fleet {shards} does not divide the fleet: n={p.n} "
            f"workers must split into equal contiguous shards "
            f"(n % shards == {p.n % max(shards, 1)})")
    if rebalance_every < 0:
        raise ValueError(f"rebalance_every must be >= 0 ticks, got "
                         f"{rebalance_every}")
    if rebalance_max < 1:
        raise ValueError(f"rebalance_max must be >= 1, got "
                         f"{rebalance_max}")
    if forecaster_fit not in ("full", "causal"):
        raise ValueError(f"unknown forecaster_fit {forecaster_fit!r}; "
                         "choose from ('full', 'causal')")
    from repro.persist import PERSIST_MODES
    if persist not in PERSIST_MODES:
        raise ValueError(f"unknown persist mode {persist!r}; "
                         f"choose from {PERSIST_MODES}")
    if persist != getattr(p, "persist", "none"):
        raise ValueError(
            f"control-plane persist={persist!r} does not match the "
            f"fleet's FleetParams.persist={p.persist!r}")
    W = len(workloads)
    u_max = max(w.costs.n_units for w in workloads)
    CU = np.full((W, u_max + 2), np.inf)
    UCUM = np.full((W, u_max + 2), np.inf)
    ACC = np.zeros((W, u_max + 1))
    FIX = np.zeros(W)
    EMITC = np.zeros(W)
    NU = np.zeros(W, dtype=np.int64)
    FULL = np.zeros(W)
    P_REQ = np.zeros(W, dtype=np.int64)
    IS_SMART = np.zeros(W, dtype=bool)
    qtabs = [getattr(wk, "qtab", None) for wk in workloads]
    S_Q = np.array([_S_PROXY if q is None else q.shape[0] for q in qtabs],
                   dtype=np.int64)
    QTAB = np.zeros((W, int(S_Q.max()), u_max + 1), dtype=np.int64)
    QJ_NJ = np.zeros((W, u_max + 1), dtype=np.int64)
    QVALUE = np.zeros(W)
    QTARGET = np.zeros(W, dtype=np.int64)
    for w, wk in enumerate(workloads):
        nu = wk.costs.n_units
        NU[w] = nu
        CU[w, :nu + 1] = wk.costs.cumulative()
        UCUM[w, :nu + 1] = np.concatenate(
            [[0.0], np.cumsum(wk.costs.unit_costs)])
        FULL[w] = UCUM[w, nu]
        ACC[w, :nu + 1] = wk.accuracy
        FIX[w] = wk.costs.fixed_cost
        EMITC[w] = wk.costs.emit_cost
        if wk.floor > 0:
            IS_SMART[w] = True
            ok = np.nonzero(wk.accuracy >= wk.floor)[0]
            P_REQ[w] = int(ok[0]) if ok.size else _BIG
        # quality tables: measured per-sample oracle rows when the
        # workload carries them, the deterministic quantized proxy rows
        # otherwise; spend is priced from the cumulative cost table and
        # quantized to integer nanojoules (bit-exact ledger sums)
        if qtabs[w] is not None:
            QTAB[w, :S_Q[w], :nu + 1] = np.asarray(qtabs[w], np.int64)
        else:
            QTAB[w, :_S_PROXY, :nu + 1] = (
                np.arange(_S_PROXY)[:, None]
                < np.round(wk.accuracy[None, :] * _S_PROXY))
        QJ_NJ[w, :nu + 1] = np.round(CU[w, :nu + 1] * 1e9)
        u_eff = int(min(P_REQ[w] if IS_SMART[w] else nu, nu))
        QVALUE[w] = ((ACC[w, u_eff] - ACC[w, 0])
                     / max(CU[w, u_eff], 1e-300))
        QTARGET[w] = int(np.argmax(wk.accuracy))  # first knob at the max
    thr = {}
    if p.quantum_j is not None:
        from repro.obs.profile import span
        with span("fleet.sched.thresholds",
                  entries=W * (u_max + 2) * (1 + 2 * int(max_batch))):
            thr = quanta_thresholds(CU, UCUM, FIX + EMITC, int(max_batch),
                                    p.quantum_j)
    L = max(int(round(lookahead_s / p.dt)), 1)
    if sched == "forecast" and forecaster_fit == "causal":
        # honest start: nothing observed yet, forecast nothing. The
        # streaming loop (FleetScheduler.refit_forecast) swaps in
        # prefix-only fits at the same fixed fc_order.
        rf = zero_row_forecast(
            p.n, arp_order if forecaster == "arp" else 1)
    elif sched == "forecast":
        rf = fit_row_forecast(p.power, forecaster, L,
                              families=trace_families,
                              arp_order=arp_order).take(p.trace_index)
    else:
        # reactive planning never reads the forecast: skip the fit and
        # carry a trivial zero-forecast table (keeps params uniform and
        # the lag gather at order 1)
        z = np.zeros(p.n)
        rf = RowForecast(order=1, MU=z, W=z[:, None],
                         THRESH=np.full(p.n, np.inf), HI=z, LO=z,
                         model=np.zeros(p.n, dtype=np.int8))
    return SchedParams(
        n=p.n, W=W, Q=int(max_queue + p.n * max_batch), B=int(max_batch),
        max_queue=int(max_queue), max_retries=int(max_retries),
        shed_after_s=float(shed_after_s), grace_s=float(grace_s),
        deadline_factor=float(deadline_factor), dt=float(p.dt),
        CU=CU, UCUM=UCUM, FIX=FIX, EMITC=EMITC, NU=NU, FULL=FULL, ACC=ACC,
        P_REQ=P_REQ, IS_SMART=IS_SMART,
        forecast=(sched == "forecast"), lookahead_ticks=L,
        forecaster=str(forecaster), fc_order=int(rf.order),
        FC_MU=rf.MU, FC_W=rf.W, FC_THRESH=rf.THRESH, FC_HI=rf.HI,
        FC_LO=rf.LO, FC_MODEL=rf.model,
        ECAP=0.5 * p.C * (p.v_max * p.v_max - p.v_off * p.v_off),
        ACTIVE_P=np.asarray(p.active_power_w, dtype=np.float64),
        lat_bins=int(lat_bins),
        lat_max_s=2.0 * (float(shed_after_s) + float(grace_s)),
        quality=("measured" if any(q is not None for q in qtabs)
                 else "proxy"),
        value_order=(sched == "quality"),
        S_Q=S_Q, QTAB=QTAB, QJ_NJ=QJ_NJ, QVALUE=QVALUE,
        WL_RANK=np.argsort(-QVALUE, kind="stable").astype(np.int64),
        QTARGET=QTARGET, shards=shards,
        rebalance_every=int(rebalance_every),
        rebalance_max=int(rebalance_max),
        forecaster_fit=str(forecaster_fit),
        persist=str(persist),
        fram_write_j_per_byte=float(fram_write_j_per_byte),
        fram_read_j_per_byte=float(fram_read_j_per_byte), **thr)


_I32_MAX = np.iinfo(np.int32).max


def _first_true(pred, shape) -> np.ndarray:
    """Elementwise over a table of ``shape``: the smallest ``us`` in
    ``[0, INT32_MAX)`` at which the monotone (false, then true) ``pred``
    is true, or ``INT32_MAX`` where it never is. Integer bisection, 31
    rounds of ``pred`` on the whole table at once."""
    lo = np.zeros(shape, np.int64)
    hi = np.full(shape, _I32_MAX, np.int64)
    while np.any(lo < hi):
        mid = (lo + hi) // 2
        t = pred(mid)
        hi, lo = (np.where((lo < hi) & t, mid, hi),
                  np.where((lo < hi) & ~t, mid + 1, lo))
    return lo.astype(np.int32)


def quanta_thresholds(CU, UCUM, overhead, B: int, quantum_j: float) -> dict:
    """Dispatch's float64 decisions as int32 thresholds on the usable
    quanta ``us`` of a quantized fleet.

    Each decision ``dispatch`` takes from the budget ``fl(us * q)`` is a
    comparison of IEEE operations that are each monotone in ``us``
    (multiply, subtract, divide, ``floor_divide``), so it turns true at
    one smallest ``us``, found here by bisection in NumPy float64, and
    a lookup becomes a count of thresholds at or below ``us``. Budgets
    are whole quanta and costs whole nanojoules, so exact ties are
    common; an integer count decides them as IEEE float64 does on any
    device. Padded ``+inf`` costs are never affordable (``INT32_MAX``).

    Args:
        CU / UCUM: (W, K) cumulative cost tables, J (``SchedParams``).
        overhead: (W,) fixed plus emission cost, J.
        B: the batch cap.
        quantum_j: joules per quantum.
    Returns:
        ``THR_AFF`` (W, K): ``fl(us*q) >= CU[w, k]`` (the affordable
        knob); ``THR_B`` (W, K, B): the batch of ``b + 1`` requests
        priced at knob ``j`` fits, ``floor_divide(fl(us*q) - overhead,
        UCUM[w, j]) >= b + 1``; ``THR_U`` (W, B, K): knob ``k`` fits one
        request of a batch of ``a + 1``, ``(fl(us*q) - overhead) / (a + 1)
        >= UCUM[w, k]``. Each row is non-decreasing along its last axis,
        and ``THR_B`` also along ``j``.
    """
    ovh = np.asarray(overhead, np.float64)[:, None]
    bsz = np.arange(1, B + 1)

    def spend(us):
        return us.astype(np.float64) * quantum_j - ovh[..., None]

    def fits(us):  # us: (W, K, B)
        cpb = UCUM[:, :, None]
        with np.errstate(invalid="ignore"):
            b = np.where(cpb > 0, np.floor_divide(
                spend(us), np.maximum(cpb, 1e-300)), B)
        return b >= bsz

    W, K = CU.shape
    return dict(
        THR_AFF=_first_true(
            lambda us: CU <= us.astype(np.float64) * quantum_j, (W, K)),
        THR_B=_first_true(fits, (W, K, B)),
        THR_U=_first_true(
            lambda us: UCUM[:, None, :] <= spend(us) / bsz[:, None],
            (W, B, K)))


def make_sched_state(sp: SchedParams) -> SchedState:
    """Empty :class:`SchedState` sized for ``sp`` (see
    ``state.init_sched_state``). Sharded params (``sp.shards > 1``) get
    the stacked per-shard form: every field carries a leading shard axis
    over per-shard shapes (``shard_sched_params``)."""
    if sp.shards > 1:
        base = init_sched_state(shard_sched_params(sp, 0))
        return SchedState(**{
            f: np.broadcast_to(
                getattr(base, f),
                (sp.shards,) + getattr(base, f).shape).copy()
            for f in SCHED_FIELDS})
    return init_sched_state(sp)


def power_lags(power, trace_index, i, T, order: int, phase=None, xp=np):
    """Gather the (N, P) power lag window the forecast planners read.

    Column j holds each worker's harvested power (watts) at trace tick
    ``i - j`` (column 0 is the current tick), indexed modulo the trace
    length ``T`` — traces are cyclic, matching the tick transition's own
    column arithmetic. ``phase`` is the optional (N,) per-worker tick
    offset. ``order`` (= ``SchedParams.fc_order``) is a static small int,
    so the gather unrolls identically under numpy and jax tracing.
    """
    cols = []
    for j in range(order):
        c = ((i - j) % T) if phase is None else (i + phase - j) % T
        cols.append(power[trace_index, c])
    return xp.stack(cols, axis=1)


# ---------------------------------------------------------------------------
# xp-generic primitives
# ---------------------------------------------------------------------------


def _argsort(key, valid, xp):
    """Rank -> index: the stable ascending argsort of
    ``where(valid, key, +inf)`` (ties break by index), identical on both
    array namespaces.

    Under jax the sort runs on :func:`_order_key`'s int64 image of the
    float64 key: XLA:TPU emulates float64, and a sort on an emulated
    float64 comparator takes minutes to compile at fleet sizes (as does a
    sort with several key operands); one int64 key compiles in well under
    a minute. An integer key (negated usable quanta) sorts as it is.
    ``key`` must be finite where ``valid``, and an integer key below its
    dtype's maximum."""
    if xp is np:
        return np.argsort(np.where(valid, key, np.inf), kind="stable")
    from jax import lax
    if np.issubdtype(key.dtype, np.integer):
        k = xp.where(valid, key, np.iinfo(key.dtype).max)
    else:
        k = xp.where(valid, _order_key(xp.where(valid, key, 0.0), xp),
                     np.iinfo(np.int64).max)
    idx = lax.iota(xp.int32, k.shape[0])
    return lax.sort((k, idx), num_keys=1, is_stable=True)[1]


def _order_key(x, xp):
    """An int64 key with the order of the finite float64 ``x`` (equal
    values, -0.0 and 0.0 included, get equal keys), built without a
    64-bit bitcast, which XLA:TPU does not implement.

    ``hi`` is the float32 rounding of ``x``; the remainder ``r = x - hi``
    is exact, at most half a float32 ulp of ``hi`` and a multiple of
    ``x``'s float64 ulp, so ``r * 2**(53 - e)`` (``e`` the exponent of
    ``hi``) is an integer in [-2**29, 2**29]. The key is the order-
    preserving int32 image of ``hi``'s bits, times 2**31, plus that
    integer offset by 2**30: ``hi`` orders first, and equal ``hi`` means
    equal ``e``, where the scaled remainders order exactly. Magnitudes
    in float32's normal range (2**-126 to 2**128) order exactly; smaller
    ones order by their float32 rounding alone."""
    from jax import lax
    y = xp.where(x == 0, 0.0, x)  # -0.0 -> 0.0
    hi = y.astype(xp.float32)
    bits = lax.bitcast_convert_type(hi, xp.int32)
    ord32 = xp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    e = ((bits >> 23) & 0xFF) - 127
    # 2**(53 - e) as two exact float32 powers of two
    s = 53 - e
    s1 = xp.clip(s, -126, 127)
    p1 = lax.bitcast_convert_type((s1 + 127) << 23, xp.float32)
    p2 = lax.bitcast_convert_type((xp.clip(s - s1, -126, 127) + 127) << 23,
                                  xp.float32)
    r = (y - hi.astype(y.dtype)) * p1.astype(y.dtype) * p2.astype(y.dtype)
    r = xp.where(e > -127, r, 0.0)  # hi == 0: tie with 0.0
    return (ord32.astype(xp.int64) << 31) + (r.astype(xp.int64) + (1 << 30))


def _cumsum(x, xp):
    """Inclusive prefix sum along axis 0 of non-negative int64 counts
    whose total the caller bounds below 2**31 (batch sizes: at most
    ``n * B`` request slots, which the (N, B) state arrays cap; capped
    per-workload counts). Under jax it is summed in int32: XLA:TPU lowers ``cumsum`` to a
    ``reduce_window`` whose emulated-int64 form runs out of scoped VMEM
    once fused into the serve scan body. Exact, so bit-identical to
    NumPy's int64 sums."""
    if xp is np:
        return np.cumsum(x, axis=0)
    return xp.cumsum(x.astype(xp.int32), axis=0).astype(x.dtype)


def _searchsorted_right(table, v, xp):
    """``np.searchsorted(table, v, side="right")`` for a short 1-D
    non-decreasing ``table`` (a knob row of ``CU``/``UCUM``, ``+inf``
    padded) and an (N,) query ``v``.

    Under jax it counts the table entries at or below each query
    (``method="compare_all"``): one fused (K, N) compare and reduce. The
    default binary search is a ``while`` loop of ceil(log2(K + 1)) levels,
    each a gather of N indices from the table, and on XLA:TPU each such
    gather costs about a millisecond at N = 131,072, where the whole
    compare costs microseconds for K in the hundreds. Both compare in
    jax's same total order on the same stored values, so the counts are
    the insertion indices exactly, ties included."""
    if xp is np:
        return np.searchsorted(table, v, side="right")
    return xp.searchsorted(table, v, side="right", method="compare_all")


# (N, B) per-slot arrays under jax: XLA:TPU lays out the narrow slot axis
# B minor, and a gather or scatter indexed by such an array, or a reshape
# between (N, B) and the flat (N*B,) order, costs it about a minute of
# compile per call site at fleet sizes. The helpers below work one slot
# column at a time instead ((N,) vectors: about a second each) and keep
# the flat row-major (worker, slot) semantics of the NumPy forms.


def _cols(x):
    return [x[:, j] for j in range(x.shape[1])]


def _take(a, idx, xp):
    """``xp.take(a, idx)`` for a 1-D table ``a`` and an (N,) or (N, B)
    index array."""
    if xp is np or idx.ndim == 1:
        return xp.take(a, idx)
    return xp.stack([xp.take(a, c) for c in _cols(idx)], axis=1)


def _rows(a, idx, xp):
    """``a[idx]`` for an (N,) or (N, B) ``a`` and an (N,) ``idx``: a row
    gather, one slot column at a time under jax."""
    if xp is np:
        return a[idx]
    if a.ndim == 2:
        return xp.stack([xp.take(c, idx) for c in _cols(a)], axis=1)
    return xp.take(a, idx)


def _inverse_permutation(perm, xp):
    """``inv`` with ``inv[perm[r]] == r``, for a permutation ``perm`` of
    ``0..N-1``. Under jax a sort of ``perm`` carrying the ranks: on
    XLA:TPU a scatter of N = 131,072 indices takes about 10 ms, more as
    the permutation scrambles, where the sort takes about 2 ms and each
    array it lets :func:`_rows` gather about 2 ms."""
    if xp is np:
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.shape[0])
        return inv
    from jax import lax
    n = perm.shape[0]
    return lax.sort((perm.astype(xp.int32), lax.iota(xp.int32, n)),
                    num_keys=1)[1]


def _scatter_set(a, idx, v, xp):
    """``a[idx] = v`` on a copy, for a 1-D ``a`` and an (N,) or (N, B)
    ``idx`` with ``v`` of its shape. Where indices repeat, the NumPy order
    decides (the last write wins), and the callers send only discarded
    writes to a repeated index."""
    if xp is np:
        out = a.copy()
        out[idx] = v
        return out
    if idx.ndim == 2:
        for c, vc in zip(_cols(idx), _cols(v)):
            a = a.at[c].set(vc)
        return a
    return a.at[idx].set(v)


def _bincount(idx, n: int, xp):
    """How many entries of the int array ``idx`` hold each value
    ``0..n-1`` (other values are not counted), as (n,) int64. Under jax a
    compare with each value and a reduce, no scatter: at N = 131,072
    XLA:TPU takes about 10 ms to scatter N indices and 1-2 ms to gather
    them, where a compare and reduce over a few hundred values takes
    microseconds."""
    if xp is np:
        v = idx.ravel()
        return np.bincount(v[(v >= 0) & (v < n)], minlength=n)
    hit = idx[..., None] == xp.arange(n)
    return xp.sum(hit.astype(xp.int32),
                  axis=tuple(range(idx.ndim))).astype(xp.int64)


def _lookup(table, idx, xp):
    """``table[idx]`` for a short 1-D host ``table`` and an index array
    in its range. Under jax each index is compared with every entry's and
    the one match kept by a max reduce, no gather (see
    :func:`_bincount`)."""
    table = np.asarray(table)
    if xp is np:
        return table[idx]
    low = (-np.inf if table.dtype.kind == "f"
           else np.iinfo(table.dtype).min)
    hit = idx[..., None] == xp.arange(table.shape[0])
    return xp.max(xp.where(hit, table, low), axis=-1)


def _sample_words(q) -> np.ndarray:
    """A (samples, K) 0/1 table packed along samples: (K, ceil(S / 32))
    int32 words, bit ``s % 32`` of word ``s // 32`` holding ``q[s]``."""
    q = np.asarray(q, np.int64)
    n_words = max(-(-q.shape[0] // 32), 1)
    bits = np.zeros((n_words * 32, q.shape[1]), np.int64)
    bits[:q.shape[0]] = q
    words = (bits.reshape(n_words, 32, -1)
             << np.arange(32)[None, :, None]).sum(axis=1)
    return words.astype(np.uint32).view(np.int32).T


def _cumsum_slots(m, xp):
    """Inclusive prefix sum of an (N, B) int64 count array in flat
    row-major (worker, slot) order, returned as (N, B): the row totals'
    prefix plus the prefix within each row. Same bound as
    :func:`_cumsum`."""
    if xp is np:
        return np.cumsum(m.reshape(-1)).reshape(m.shape)
    rows = xp.sum(m, axis=1)
    within = xp.cumsum(m.astype(xp.int32), axis=1).astype(m.dtype)
    return (_cumsum(rows, xp) - rows)[:, None] + within


# ---------------------------------------------------------------------------
# intake
# ---------------------------------------------------------------------------


def admit(sp: SchedParams, ss, counts, t, xp=np):
    """Admit this tick's arrivals up to the global backlog bound; reject
    the remainder.

    Args:
        counts: (W,) per-workload arrival counts this tick.
        t: arrival time stamped on admitted requests, seconds.
    Returns:
        the updated ``SchedState`` namedtuple view.

    All arrivals of one tick share the arrival time ``t``, so a push is a
    masked fill of the ring segment past each queue's tail."""
    if xp is np and int(np.sum(counts)) == 0:
        return ss  # pure no-op (identical to the masked write below)
    if xp is not np:
        # traced twin of the host fast path: skip the ring write on
        # zero-arrival ticks (the result is identical either way)
        from jax import lax
        return lax.cond(xp.sum(counts) > 0,
                        lambda s: _admit_impl(sp, s, counts, t, xp),
                        lambda s: s, ss)
    return _admit_impl(sp, ss, counts, t, xp)


def _admit_impl(sp: SchedParams, ss, counts, t, xp):
    counts = xp.asarray(counts).astype(xp.int64)
    backlog = xp.sum(ss.q_len)
    space = xp.maximum(sp.max_queue - backlog, 0)
    # arrivals ahead of each workload's in this tick; each count is capped
    # at max_queue + 1 first (a larger prefix admits nothing either way),
    # which bounds the sum for _cumsum
    capped = xp.minimum(counts, sp.max_queue + 1)
    adm = xp.clip(space - (_cumsum(capped, xp) - capped), 0, counts)
    if xp is np:
        # reference-driver fast path: write exactly the admitted slots
        # (same values the masked whole-ring write below produces — the
        # admission *decision* above is shared, the store is sparse)
        q_t, q_r = ss.q_t.copy(), ss.q_r.copy()
        for w in range(sp.W):
            k = int(adm[w])
            if k:
                idx = (int(ss.q_head[w]) + int(ss.q_len[w])
                       + np.arange(k)) % sp.Q
                q_t[w, idx] = t
                q_r[w, idx] = 0
    else:
        slot = xp.arange(sp.Q)[None, :]
        pos = (slot - ss.q_head[:, None]) % sp.Q  # logical idx per slot
        new = ((pos >= ss.q_len[:, None])
               & (pos < (ss.q_len + adm)[:, None]))
        q_t = xp.where(new, t, ss.q_t)
        q_r = xp.where(new, 0, ss.q_r)
    return ss._replace(
        q_t=q_t, q_r=q_r,
        q_len=ss.q_len + adm,
        submitted=ss.submitted + xp.sum(counts),
        rejected=ss.rejected + xp.sum(counts - adm))


def shed(sp: SchedParams, ss, t, xp=np):
    """Drop the stale prefix of each queue (age ``t - arrival`` beyond
    ``shed_after_s`` seconds): a stale approximate answer is worth less
    than no answer. Prefix, not filter — ring contiguity is preserved
    and matches the PR-1 head-pop loop. Returns the updated state.

    The ring is read in physical order: each slot's logical position is
    ``(slot - q_head) % Q``, a bijection onto ``0..Q-1``, so the first
    non-stale logical position is a masked min over the physical slots,
    the same integer a gather into logical order gives. XLA:TPU runs such
    a ring-sized gather of the emulated float64 times as two 32-bit
    gathers of W * Q indices, about 67 ms per dispatch tick at 131,072
    workers; this is one elementwise pass and a row min."""
    slot = xp.arange(sp.Q, dtype=xp.int32)[None, :]
    pos = (slot - ss.q_head.astype(xp.int32)[:, None]) % sp.Q
    stale = ((pos < ss.q_len.astype(xp.int32)[:, None])
             & (t - ss.q_t > sp.shed_after_s))
    # the stale prefix ends at the first fresh (or empty) position
    n_shed = xp.min(xp.where(stale, sp.Q, pos), axis=1).astype(xp.int64)
    return ss._replace(
        q_head=(ss.q_head + n_shed) % sp.Q,
        q_len=ss.q_len - n_shed,
        shed=ss.shed + xp.sum(n_shed))


# ---------------------------------------------------------------------------
# routing / batching
# ---------------------------------------------------------------------------


def plan_budget(sp: SchedParams, budget_now, pw_lags, eff, xp=np):
    """The budget (joules) routing and batching plan against.

    Reactive: the instantaneous usable energy. Forecast: usable energy
    plus the expected harvest over the lookahead window under each
    worker's compiled forecaster, capped at the buffer's storable
    ceiling (``repro.core.forecast.usable_energy_rows`` — one expression
    for all four models).

    Args:
        budget_now: (N,) instantaneous usable energy, J.
        pw_lags: (N, fc_order) power lag window from :func:`power_lags`,
            watts (ignored in reactive mode).
        eff: booster conversion efficiency (dimensionless).
    Returns:
        (N,) planning budget, J.
    """
    if not sp.forecast:
        return budget_now
    rf = RowForecast(order=sp.fc_order, MU=sp.FC_MU, W=sp.FC_W,
                     THRESH=sp.FC_THRESH, HI=sp.FC_HI, LO=sp.FC_LO,
                     model=sp.FC_MODEL)
    return usable_energy_rows(
        rf, budget_now, pw_lags, sp.lookahead_ticks * sp.dt,
        e_cap=sp.ECAP, booster_eff=eff, xp=xp)


def dispatch(sp: SchedParams, ss, dispatchable, budget_now, budget_plan,
             t, xp=np, us=None):
    """Route queued requests to capable workers.

    Args:
        dispatchable: (N,) bool — on, idle, nothing pending.
        budget_now: (N,) instantaneous usable energy, J.
        budget_plan: (N,) planning budget from :func:`plan_budget`, J.
        t: assignment time, seconds.
        us: (N,) int usable quanta above brown-out, of which
            ``budget_now`` is ``fl(us * quantum_j)``; required for a
            quantized fleet (``sp.THR_AFF`` set), unused otherwise.
    Returns:
        ``(ss, a)`` — the updated state and an :class:`Assignment` of
        per-worker arrays (mask, workload id, per-request knob units,
        batch size) the caller writes into the device state
        (``p_pending`` and friends).

    Workers are ranked richest-first by ``budget_plan`` (stable sort);
    queues are served oldest-head-first (or, under ``sp.value_order``,
    best marginal-accuracy-per-joule first). Per worker: SMART admission at
    the workload floor on the *instantaneous* budget (never start work
    whose fixed cost is unfunded today), batch size and greedy knob
    refinement on the *planning* budget (forecast inflow funds in-flight
    units). Queue consumption is a cumulative-sum slice per workload.

    A quantized fleet takes every decision that reads the instantaneous
    budget (the rank, the affordable knob, the batch size, the knob per
    request) as a count of :func:`quanta_thresholds` at or below ``us``:
    the IEEE float64 decisions, in integers on any device. Forecast mode
    still ranks and sizes batches on its float planning budget, which is
    no whole number of quanta."""
    from repro.obs.profile import scope
    i64 = xp.int64
    quant = sp.THR_AFF is not None
    if quant and us is None:
        raise ValueError("a quantized fleet dispatches on its usable "
                         "quanta: pass us")
    with scope("fleet.dispatch.rank", xp):
        # rank -> worker id: dispatchable workers richest first (fl(us * q)
        # rises strictly with us, so us ranks as the reactive budget does)
        key = us if quant and not sp.forecast else budget_plan
        order = _argsort(-key, dispatchable, xp)
        elig = xp.take(dispatchable, order)
        if quant:
            us_r = xp.take(us, order)
        else:
            bn = xp.take(budget_now, order)
        bp = xp.take(budget_plan, order)
    with scope("fleet.dispatch.queues", xp):
        if sp.value_order:
            # sched="quality": serve queues richest-in-accuracy-per-joule
            # first (a params constant, so the order is static under tracing)
            wl_order = xp.asarray(sp.WL_RANK)
        else:
            head_t = xp.take_along_axis(ss.q_t, ss.q_head[:, None],
                                        axis=1)[:, 0]
            wl_order = _argsort(head_t, ss.q_len > 0, xp)  # empty queues last
        q_head, q_len = ss.q_head, ss.q_len
        taken = xp.zeros(sp.n, dtype=bool)
        a_wl = xp.zeros(sp.n, dtype=i64)
        a_units = xp.zeros(sp.n, dtype=i64)
        a_batch = xp.zeros(sp.n, dtype=i64)
        g_arr = xp.zeros((sp.n, sp.B))
        g_retry = xp.zeros((sp.n, sp.B), dtype=i64)
        jB = xp.arange(sp.B)[None, :]
        for k in range(sp.W):  # static: one pass per workload queue
            wl = wl_order[k]
            ucum = xp.take(xp.asarray(sp.UCUM), wl, axis=0)
            nu = xp.take(xp.asarray(sp.NU), wl)
            overhead = (xp.take(xp.asarray(sp.FIX), wl)
                        + xp.take(xp.asarray(sp.EMITC), wl))
            qrem = xp.take(q_len, wl)
            head = xp.take(q_head, wl)
            # admission: largest knob the instantaneous budget affords (-1:
            # even fixed+emit does not fit), SMART floor for floored workloads
            if quant:
                with scope("fleet.dispatch.afford", xp):
                    def count(thr):
                        return _searchsorted_right(thr, us_r,
                                                   xp).astype(i64)
                    k_aff = count(xp.take(xp.asarray(sp.THR_AFF), wl,
                                          axis=0)) - 1
                    # b_fit[b]: the knobs below it price b + 1 requests
                    # within budget (THR_B rises along the knob axis)
                    thr_b = xp.take(xp.asarray(sp.THR_B), wl, axis=0)
                    b_fit = ([] if sp.forecast else
                             [count(thr_b[:, b]) for b in range(sp.B)])
                    # k_fit[a]: the largest knob per request of a + 1
                    thr_u = xp.take(xp.asarray(sp.THR_U), wl, axis=0)
                    k_fit = [count(thr_u[a]) - 1 for a in range(sp.B)]
            else:
                k_aff = _searchsorted_right(
                    xp.take(xp.asarray(sp.CU), wl, axis=0), bn,
                    xp).astype(i64) - 1
                b_fit = []
            if sp.persist != "none":
                # exact disciplines (docs/persistence_plane.md): the knob is
                # pinned at NU — every unit runs — and admission only needs
                # the fixed+emit overhead funded now; the persisted request
                # survives power failure and spans recharge cycles
                p_req = xp.zeros(sp.n, dtype=i64) + nu
                afford = k_aff >= 0
            else:
                p_req = xp.where(xp.take(xp.asarray(sp.IS_SMART), wl),
                                 xp.take(xp.asarray(sp.P_REQ), wl),
                                 xp.maximum(k_aff, 0))
                afford = (k_aff >= p_req) & (k_aff >= 0)
            # batch sizing on the *planning* budget (forecast inflow lets more
            # floor-knob requests ride one power cycle, amortizing fixed+emit
            # overhead); greedy knob refinement on the *instantaneous* budget
            # (spend expected inflow on throughput, never on slower service).
            # Quality mode sizes batches at the max-measured-accuracy knob
            # instead of the floor knob: fewer requests ride one power cycle,
            # each affording the knob where the oracle says accuracy peaks —
            # under scarcity the target degrades back to the floor (b_want
            # clips to >= 1 and refinement still bounds at p_req).
            if sp.value_order and sp.persist == "none":
                # quality mode also CAPS refinement at the target knob:
                # measured tables are non-monotonic, so units past the peak
                # cost strictly more joules for no more (often less)
                # measured accuracy
                u_cap = xp.maximum(xp.take(xp.asarray(sp.QTARGET), wl), p_req)
                j_b = u_cap  # never below the admission knob
            else:
                u_cap = nu
                j_b = p_req
            j_b = xp.clip(j_b, 0, ucum.shape[0] - 1)  # the knob pricing b
            if b_fit:
                b_want = xp.zeros(sp.n, dtype=i64)
                for f in b_fit:
                    b_want = b_want + (j_b < f)
            else:
                cpb = xp.take(ucum, j_b)
                b_want = xp.where(
                    cpb > 0, xp.floor_divide(bp - overhead,
                                             xp.maximum(cpb, 1e-300)), sp.B)
            b_want = xp.clip(b_want, 1, sp.B).astype(i64)

            def knob(batch):
                """The largest knob one request of a ``batch`` (1..B)
                affords on the instantaneous budget, within
                ``[p_req, u_cap]``."""
                if quant:
                    kb = k_fit[0]
                    for a in range(1, sp.B):
                        kb = xp.where(batch == a + 1, k_fit[a], kb)
                else:
                    kb = _searchsorted_right(ucum, (bn - overhead) / batch,
                                             xp).astype(i64) - 1
                return xp.clip(kb, p_req, u_cap)
            u_want = knob(b_want)
            ok = elig & ~taken & afford & (u_want > 0)
            b = xp.where(ok, b_want, 0)
            c = _cumsum(b, xp)  # b <= B per worker
            start = c - b
            actual = xp.clip(qrem - start, 0, b)
            got = ok & (actual > 0)
            u = knob(xp.maximum(actual, 1))
            # consume the queue front: gather each worker's request slice
            phys = (head + start[:, None] + jB) % sp.Q
            row_t = xp.take(ss.q_t, wl, axis=0)
            row_r = xp.take(ss.q_r, wl, axis=0)
            take_mask = got[:, None] & (jB < actual[:, None])
            g_arr = xp.where(take_mask, _take(row_t, phys, xp), g_arr)
            g_retry = xp.where(take_mask, _take(row_r, phys, xp), g_retry)
            consumed = xp.sum(actual)
            onehot = xp.arange(sp.W) == wl
            q_head = xp.where(onehot, (q_head + consumed) % sp.Q, q_head)
            q_len = xp.where(onehot, q_len - consumed, q_len)
            taken = taken | got
            a_wl = xp.where(got, wl, a_wl)
            a_units = xp.where(got, u, a_units)
            a_batch = xp.where(got, actual, a_batch)
    with scope("fleet.dispatch.scatter", xp):
        # rank space -> worker space: order is a permutation, so each
        # worker's entry sits at its rank
        rank = _inverse_permutation(order, xp)
        batch_w = _rows(a_batch, rank, xp)
        mask_w = batch_w > 0
        wl_w = _rows(a_wl, rank, xp)
        units_w = _rows(a_units, rank, xp)
        arr_w = _rows(g_arr, rank, xp)
        retry_w = _rows(g_retry, rank, xp)
        ss = ss._replace(
            q_head=q_head, q_len=q_len,
            f_n=xp.where(mask_w, batch_w, ss.f_n),
            f_wl=xp.where(mask_w, wl_w, ss.f_wl),
            f_units=xp.where(mask_w, units_w, ss.f_units),
            f_t0=xp.where(mask_w, t, ss.f_t0),
            f_arr=xp.where(mask_w[:, None], arr_w, ss.f_arr),
            f_retry=xp.where(mask_w[:, None], retry_w, ss.f_retry),
            batch_hist=ss.batch_hist + xp.sum(
                (batch_w[:, None] == xp.arange(sp.B + 1)[None, :])
                & mask_w[:, None], axis=0))
    return ss, Assignment(mask_w, wl_w, units_w, batch_w)


# ---------------------------------------------------------------------------
# completion / loss / eviction
# ---------------------------------------------------------------------------


def _requeue(sp: SchedParams, ss, slots, xp=np):
    """Grant retries to the in-flight request ``slots`` ((N, B) mask):
    retry budget exceeded -> lost; otherwise re-enter the owning workload
    queue at the *front*, preserving (worker, slot) order, with original
    arrival times (so shedding still sees their true age)."""
    if xp is np:
        if not slots.any():
            return ss  # pure no-op fast path for the reference driver
        return _requeue_impl(sp, ss, slots, xp)
    # traced twin: retries are rare relative to ticks — skip the ring
    # scatter entirely on clean ticks (identical result either way)
    from jax import lax
    return lax.cond(xp.any(slots),
                    lambda s: _requeue_impl(sp, s, slots, xp),
                    lambda s: s, ss)


def _requeue_impl(sp: SchedParams, ss, slots, xp):
    newr = ss.f_retry + 1
    give_up = slots & (newr > sp.max_retries)
    keep = slots & ~give_up
    q_t, q_r, q_head, q_len = ss.q_t, ss.q_r, ss.q_head, ss.q_len
    for w in range(sp.W):  # static: one front-insert pass per queue
        # (N, B) slots kept for queue w, ranked in (worker, slot) order
        m = keep & (ss.f_wl == w)[:, None]
        kcount = xp.sum(m.astype(xp.int64))
        rank = _cumsum_slots(m.astype(xp.int64), xp) - 1
        headnew = (q_head[w] - kcount) % sp.Q
        phys = xp.where(m, (headnew + rank) % sp.Q, sp.Q)  # Q: dump slot
        ext_t = xp.concatenate([q_t[w], xp.zeros(1)])
        ext_t = _scatter_set(ext_t, phys, xp.where(m, ss.f_arr, 0.0), xp)
        ext_r = xp.concatenate([q_r[w], xp.zeros(1, dtype=xp.int64)])
        ext_r = _scatter_set(ext_r, phys, xp.where(m, newr, 0), xp)
        onehot = xp.arange(sp.W) == w
        q_t = xp.where(onehot[:, None], ext_t[None, :sp.Q], q_t)
        q_r = xp.where(onehot[:, None], ext_r[None, :sp.Q], q_r)
        q_head = xp.where(onehot, headnew, q_head)
        q_len = xp.where(onehot, q_len + kcount, q_len)
    return ss._replace(
        q_t=q_t, q_r=q_r, q_head=q_head, q_len=q_len,
        lost=ss.lost + xp.sum(give_up),
        requeued=ss.requeued + xp.sum(keep))


def collect(sp: SchedParams, ss, emit, lost, units_done, t, xp=np):
    """Retire this tick's device outcomes.

    Args:
        emit / lost: (N,) bool — workers that emitted / browned out.
        units_done: (N,) int64 units finished by emitting workers.
        t: completion time, seconds (drives the latency histogram).
    Returns:
        the updated state.

    An emitting worker completes ``units_done // u`` full requests of its
    batch (plus one partial: anytime semantics — a truncated result is
    still a result); the unfinished tail and all requests of browned-out
    workers go through the retry path."""
    if xp is np:
        if not (emit.any() or lost.any()):
            return ss
        return _collect_impl(sp, ss, emit, lost, units_done, t, xp)
    from jax import lax
    return lax.cond(
        xp.any(emit | lost),
        lambda s: _collect_impl(sp, s, emit, lost, units_done, t, xp),
        lambda s: s, ss)


def _collect_impl(sp: SchedParams, ss, emit, lost, units_done, t, xp):
    act = ss.f_n > 0
    em = emit & act
    lo = lost & act
    b = ss.f_n
    u = ss.f_units
    # int32 division (one batch's units are far below 2**31): XLA:TPU's
    # emulated int64 division costs ~20 s of compile per call site
    i32 = xp.int32
    ud, su = units_done.astype(i32), xp.maximum(u, 1).astype(i32)
    full = xp.where(u > 0, (ud // su).astype(b.dtype), b)
    part = xp.where(u > 0, (ud % su).astype(b.dtype), 0)
    nfull = xp.minimum(full, b)
    haspart = (part > 0) & (full < b)
    jB = xp.arange(sp.B)[None, :]
    slotv = jB < b[:, None]
    compfull = em[:, None] & slotv & (jB < nfull[:, None])
    comppart = (em[:, None] & slotv & (jB == nfull[:, None])
                & haspart[:, None])
    comp = compfull | comppart
    unfinished = (em[:, None] & slotv & ~comp) | (lo[:, None] & slotv)
    units_slot = xp.where(compfull, u[:, None],
                          xp.where(comppart, part[:, None], 0))
    lat = t - ss.f_arr
    # fixed-bin latency histogram: integer counts agree exactly across
    # backends; percentiles come from the bins (metrics.py)
    binw = sp.lat_max_s / sp.lat_bins
    idx = xp.clip((lat / binw).astype(xp.int64), 0, sp.lat_bins - 1)
    hist = _bincount(xp.where(comp, idx, sp.lat_bins), sp.lat_bins, xp)
    # per-workload aggregates, one static pass per workload over the
    # (N, B) slots (no (N, B, W) one-hot tensors). A worker's completions
    # are its first slots: full ones at its knob u, then at most one
    # partial, so each table is read at two knobs per worker.
    K = sp.ACC.shape[1]
    uq, pq = xp.clip(u, 0, K - 1), xp.clip(part, 0, K - 1)

    def at(row):
        """``row[knob]`` of each slot's completion, (N, B)."""
        return xp.where(comppart, _lookup(row, pq, xp)[:, None],
                        _lookup(row, uq, xp)[:, None])
    # quality ledger: each completion is scored against a deterministic
    # oracle sample — per workload, this tick's completions are numbered
    # in flat (worker, slot) order continuing the run-long completed_wl
    # counter, cycling mod the oracle set size — then measured
    # correctness (0/1, a bit of the packed (sample, units) table) and
    # the table-priced spend (integer nanojoules) are read at the slot's
    # knob. Integer arithmetic only: both backends ledger bit-exactly.
    wl = ss.f_wl[:, None]
    agg = {k: [] for k in ("n", "units", "acc", "meas", "nj")}
    for w in range(sp.W):  # static: one pass per workload
        m = comp & (wl == w)
        mi = m.astype(xp.int64)
        # the run-long counter is reduced first, so the per-slot modulo
        # runs in int32 (ranks are below n * B)
        s_q = sp.S_Q[w].astype(i32)
        sample = ((ss.completed_wl[w] % sp.S_Q[w]).astype(i32)
                  + (_cumsum_slots(mi, xp) - mi).astype(i32)) % s_q
        words = _sample_words(sp.QTAB[w, :sp.S_Q[w]])
        word = at(words[:, 0])
        for c in range(1, words.shape[1]):
            word = xp.where(sample // 32 == c, at(words[:, c]), word)
        qv = (word >> (sample % 32)) & 1
        agg["n"].append(xp.sum(mi))
        agg["units"].append(xp.sum(xp.where(m, units_slot, 0)))
        agg["acc"].append(xp.sum(xp.where(m, at(sp.ACC[w]), 0.0)))
        agg["meas"].append(xp.sum(xp.where(m, qv, 0).astype(xp.int64)))
        agg["nj"].append(xp.sum(xp.where(m, at(sp.QJ_NJ[w]), 0)))
    agg = {k: xp.stack(v) for k, v in agg.items()}
    # the latency sum counts whole ticks (arrivals and completions are
    # stamped on the tick grid): an integer is the same in every
    # reduction order and under XLA:TPU's emulated float64
    lat_ticks = xp.rint(lat / sp.dt).astype(xp.int64)
    ss = ss._replace(
        completed=ss.completed + xp.sum(comp),
        completed_wl=ss.completed_wl + agg["n"],
        units_wl=ss.units_wl + agg["units"],
        acc_wl=ss.acc_wl + agg["acc"],
        meas_wl=ss.meas_wl + agg["meas"],
        joules_nj_wl=ss.joules_nj_wl + agg["nj"],
        lat_sum=ss.lat_sum + xp.sum(xp.where(comp, lat_ticks, 0)),
        lat_hist=ss.lat_hist + hist)
    ss = _requeue(sp, ss, unfinished, xp)
    return ss._replace(f_n=xp.where(em | lo, 0, ss.f_n))


def evict(sp: SchedParams, ss, t, xp=np):
    """Straggler pass: revoke assignments older than the service
    deadline ``grace_s + deadline_factor * est`` (seconds), where
    ``est`` prices the batch at the worker's own MCU active power (the
    device browned out before acquiring, or recharges too slowly).
    Returns ``(ss, ev)`` with ``ev`` the (N,) evicted mask; the caller
    clears the device's pending/in-flight flags for ``ev``."""
    act = ss.f_n > 0
    est = (xp.take(xp.asarray(sp.FIX), ss.f_wl)
           + xp.take(xp.asarray(sp.EMITC), ss.f_wl)
           + ss.f_n * xp.take(xp.asarray(sp.FULL), ss.f_wl)) / sp.ACTIVE_P
    ev = act & (t - ss.f_t0 > sp.grace_s + sp.deadline_factor * est)
    slots = ev[:, None] & (xp.arange(sp.B)[None, :] < ss.f_n[:, None])
    ss = ss._replace(evicted=ss.evicted + xp.sum(xp.where(ev, ss.f_n, 0)))
    ss = _requeue(sp, ss, slots, xp)
    return ss._replace(f_n=xp.where(ev, 0, ss.f_n)), ev


# ---------------------------------------------------------------------------
# sharded control plane (--mesh-fleet K): per-shard params/state + the
# cross-shard work-stealing rebalance, xp-generic so the fused JAX path
# (psum/ppermute collectives) and the NumPy host twin (axis-0 sums +
# np.roll) evaluate the same queue moves bit-exactly
# ---------------------------------------------------------------------------

# compiled forecast tables — the SchedParams arrays a causal refit
# replaces between chunks. The fused scan passes them as *runtime*
# inputs (not trace constants) so a refit never forces a re-trace;
# sched_params_compatible is the matching cache-invalidation rule.
FC_FIELDS = ("FC_MU", "FC_W", "FC_THRESH", "FC_HI", "FC_LO", "FC_MODEL")

# SchedParams fields indexed by worker (N,...) — the ones a per-shard
# view must slice to its contiguous worker block
PER_WORKER_FIELDS = FC_FIELDS + ("ECAP", "ACTIVE_P")


def sched_params_compatible(old: SchedParams | None,
                            new: SchedParams) -> bool:
    """True iff a scan compiled against ``old`` stays valid for ``new``.

    A causal refit rebinds only the ``FC_FIELDS`` tables (same shapes,
    same dtypes — ``fc_order`` is fixed per session), which the compiled
    serve functions take as runtime arguments; everything else in
    :class:`SchedParams` is baked into the trace, so any *other* change
    — a different table object, a different scalar — invalidates the
    compile cache exactly like the old identity check did."""
    if old is None:
        return False
    if old is new:
        return True
    for f in dataclasses.fields(SchedParams):
        a, b = getattr(old, f.name), getattr(new, f.name)
        if f.name in FC_FIELDS:
            a, b = np.asarray(a), np.asarray(b)
            if a.shape != b.shape or a.dtype != b.dtype:
                return False
        elif isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
            if a is not b:
                return False
        elif a != b:
            return False
    return True


def shard_sched_params(sp: SchedParams, shard: int | None = None,
                       per_worker: dict | None = None) -> SchedParams:
    """The single-shard view of a sharded :class:`SchedParams`.

    Shard ``s`` owns workers ``[s*n/K, (s+1)*n/K)``, an admission slice
    of ``max_queue // K`` requests, and a private ring sized
    ``max_queue//K + n_shard*B + rebalance_max`` — the last term is
    headroom so a rebalance push landing on a full queue (admission
    slice + every in-flight retry requeued at once) cannot overflow the
    ring. Pass ``shard`` on the host (NumPy slices of the per-worker
    fields) or ``per_worker`` inside a trace (the shard's tracer slices,
    e.g. under ``shard_map``/``vmap``)."""
    K = sp.shards
    ns = sp.n // K
    if per_worker is None:
        sl = slice(shard * ns, (shard + 1) * ns)
        per_worker = {f: getattr(sp, f)[sl] for f in PER_WORKER_FIELDS}
    return dataclasses.replace(
        sp, n=ns, shards=1,
        max_queue=sp.max_queue // K,
        Q=int(sp.max_queue // K + ns * sp.B + sp.rebalance_max),
        **per_worker)


def split_counts(counts, shards: int) -> np.ndarray:
    """Deterministic arrival split: shard ``s`` of ``K`` receives
    ``counts // K + (s < counts % K)`` requests — elementwise over any
    counts shape ((W,) per tick or (T, W) whole-run), so the shards'
    admissions sum exactly to the global stream. Host-side NumPy; both
    serve paths consume the same precomputed split."""
    counts = np.asarray(counts).astype(np.int64)
    s = np.arange(int(shards), dtype=np.int64).reshape(
        (int(shards),) + (1,) * counts.ndim)
    return counts // shards + (s < counts % shards)


# accounting fields summed over the shard axis by merged_sched_view
# (all order-free sums), split by their per-shard rank; the remaining
# fields (rings, in-flight slots) keep their stacked form
MERGED_SCALAR_FIELDS = ("submitted", "rejected", "shed", "lost",
                        "evicted", "requeued", "completed", "lat_sum",
                        "rebalanced")  # 0-d per shard
MERGED_ARRAY_FIELDS = ("completed_wl", "units_wl", "acc_wl", "lat_hist",
                       "batch_hist", "meas_wl", "joules_nj_wl")  # 1-d


def merged_sched_view(st) -> SS:
    """Aggregate a stacked (K, ...) sharded :class:`SchedState` into the
    global counter view ``metrics.sched_summary`` reads: every
    accounting field summed over the shard axis (all are order-free
    sums), structural fields (queues, in-flight slots) passed through
    stacked. Works on the unsharded state too (identity)."""
    vals = {}
    for f in SCHED_FIELDS:
        a = np.asarray(getattr(st, f))
        if f in MERGED_SCALAR_FIELDS and a.ndim > 0:
            vals[f] = a.sum()
        elif f in MERGED_ARRAY_FIELDS and a.ndim > 1:
            vals[f] = a.sum(axis=0)
        else:
            vals[f] = getattr(st, f)
    return SS(**vals)


def rebalance_capacity(budget_plan, xp=np):
    """One shard's energy capacity for the rebalance targets: the
    order-free int64 sum of its workers' planning budgets quantized
    elementwise to microjoules. µJ (not nJ) keeps the ``b_tot * cap``
    product well inside int64 at million-worker fleets."""
    return xp.sum(xp.round(budget_plan * 1e6).astype(xp.int64))


def rebalance_targets(backlog, cap, b_tot, c_tot, xp=np):
    """Forecast-weighted backlog targets: shard ``s`` should hold
    ``b_tot * cap_s // c_tot`` queued requests (energy-proportional
    share of the global backlog, integer floor). Returns
    ``(surplus, deficit)`` — requests above / below target. Scalars per
    shard under the collectives; (K,) arrays on the host twin."""
    target = (b_tot * cap) // xp.maximum(c_tot, 1)
    surplus = xp.maximum(backlog - target, 0)
    deficit = xp.maximum(target - backlog, 0)
    return surplus, deficit


def rebalance_moves(sp: SchedParams, q_len, give, xp=np):
    """Split one shard's total give-count into per-workload tail-pops:
    fixed workload order 0..W-1, each queue contributing at most
    ``min(q_len[w], rebalance_max)`` (vectorized greedy fill via the
    availability cumsum). ``give`` is an int64 scalar."""
    capw = xp.minimum(q_len, sp.rebalance_max)
    c = _cumsum(capw, xp)  # capw <= rebalance_max per workload
    return xp.clip(give - (c - capw), 0, capw).astype(xp.int64)


def queue_pop_tail(sp: SchedParams, ss, move, xp=np):
    """Pop ``move[w]`` requests from the TAIL of each workload ring
    (the youngest entries — stealing ships fresh work and leaves the
    oldest requests where shedding can still see their age) into fixed
    (W, rebalance_max) buffers, oldest-of-the-moved first. Pure value
    transfer: the (arrival time, retry count) payloads are copied
    bit-for-bit, no float arithmetic. Returns ``(ss, buf_t, buf_r)``."""
    R = sp.rebalance_max
    jR = xp.arange(R)[None, :]
    take = jR < move[:, None]
    pos = ss.q_len[:, None] - move[:, None] + jR  # logical, >= 0
    phys = (ss.q_head[:, None] + pos) % sp.Q
    buf_t = xp.where(take, xp.take_along_axis(ss.q_t, phys, axis=1), 0.0)
    buf_r = xp.where(take, xp.take_along_axis(ss.q_r, phys, axis=1), 0)
    return ss._replace(q_len=ss.q_len - move), buf_t, buf_r


def queue_push_tail(sp: SchedParams, ss, move, buf_t, buf_r, xp=np):
    """Push received rebalance buffers at each workload ring's tail,
    preserving buffer order (slot j of ``buf_*`` lands j-th). Unused
    buffer lanes scatter into a dump slot that is sliced off, mirroring
    ``_requeue_impl``'s ring-write idiom. Also counts the arrivals into
    ``ss.rebalanced``."""
    R = sp.rebalance_max
    jR = xp.arange(R)[None, :]
    put = jR < move[:, None]
    phys = xp.where(put, (ss.q_head[:, None] + ss.q_len[:, None] + jR)
                    % sp.Q, sp.Q)  # Q: per-row dump slot
    flat = (xp.arange(sp.W)[:, None] * (sp.Q + 1) + phys).reshape(-1)
    ext_t = xp.concatenate(
        [ss.q_t, xp.zeros((sp.W, 1))], axis=1).reshape(-1)
    ext_r = xp.concatenate(
        [ss.q_r, xp.zeros((sp.W, 1), dtype=xp.int64)], axis=1).reshape(-1)
    ext_t = _scatter_set(ext_t, flat,
                         xp.where(put, buf_t, 0.0).reshape(-1), xp)
    ext_r = _scatter_set(ext_r, flat,
                         xp.where(put, buf_r, 0).reshape(-1), xp)
    return ss._replace(
        q_t=ext_t.reshape(sp.W, sp.Q + 1)[:, :sp.Q],
        q_r=ext_r.reshape(sp.W, sp.Q + 1)[:, :sp.Q],
        q_len=ss.q_len + move,
        rebalanced=ss.rebalanced + xp.sum(move))


def rebalance_host(sps_list: Sequence[SchedParams], sss: list,
                   plans: Sequence) -> list:
    """The NumPy host twin of one cross-shard rebalance event.

    Mirrors the collective protocol exactly: ``psum`` totals become
    axis-0 sums, the ``ppermute`` ring shifts become ``np.roll`` —
    shard ``s`` learns its successor's deficit (roll -1), gives
    ``min(surplus_s, deficit_{s+1})`` requests popped from its queue
    tails, and receives its predecessor's send buffers (roll +1). Same
    helper functions as the traced path, so the queue contents agree
    bit-for-bit. Args are per-shard lists: params views, ``SS`` states,
    (n_shard,) planning budgets. Returns the updated states."""
    K = len(sss)
    backlog = np.array([int(np.sum(s.q_len)) for s in sss],
                       dtype=np.int64)
    cap = np.array([int(rebalance_capacity(pl, np)) for pl in plans],
                   dtype=np.int64)
    surplus, deficit = rebalance_targets(
        backlog, cap, backlog.sum(), cap.sum(), np)
    give = np.minimum(surplus, np.roll(deficit, -1))
    sent = []
    out = []
    for s in range(K):
        move = rebalance_moves(sps_list[s], sss[s].q_len, give[s], np)
        ss2, bt, br = queue_pop_tail(sps_list[s], sss[s], move, np)
        out.append(ss2)
        sent.append((move, bt, br))
    for s in range(K):
        move, bt, br = sent[(s - 1) % K]  # ppermute s -> s+1
        out[s] = queue_push_tail(sps_list[s], out[s], move, bt, br, np)
    return out
