"""Shared xp-generic telemetry expressions + the host-side recorder.

One tick-update function (:func:`obs_tick`) evaluated by both serve
paths: the NumPy host window (``repro.fleet.scheduler._HostServe``)
calls it with ``xp=numpy`` every tick, and
``backend_jax._build_serve`` traces the identical expressions inside the
fused ``lax.scan`` (telemetry and ring arrays ride the scan carry).
Everything it accumulates is an int64 sum of per-worker integer
quantities — float energies/powers are quantized *elementwise*
(``round(x * 1e12)`` picojoules, ``round(x * 1e9)`` nanowatts) before
the reduction, so reduction order cannot matter and every channel
agrees bit-exactly across backends (the per-worker floats themselves
are bit-equal under the existing agreement contract).

Zero perturbation by construction: :func:`obs_tick` is a pure function
of *snapshots* of the fleet/scheduler transition — it never writes any
``FleetState``/``SchedState`` field, so instrumented runs produce
bit-identical serve and quality counters (tests/test_obs.py gates this).
"""
from __future__ import annotations

import collections

import numpy as np

from repro.core.forecast import RowForecast, forecast_power_rows
from repro.fleet.sched import _scatter_set, power_lags
from repro.obs.state import (EV_ACQUIRE, EV_ADMIT, EV_ASSIGN, EV_BROWN,
                             EV_COMPLETE, EV_EMIT, EV_EVICT, EV_LOST,
                             EV_REJECT, EV_REQUEUE, EV_SHED, EV_WAKE,
                             TELE_FIELDS, ObsParams, init_ring,
                             init_tele)

# tick-start snapshots: the before-side of every delta obs_tick takes.
# DevSnap copies the handful of device arrays a tick mutates; SchedSnap
# is nine integer scalars (the lifecycle counters + the two quality
# ledger sums).
DevSnap = collections.namedtuple(
    "DevSnap", ["on", "cycles", "acquired", "skipped", "emit_count",
                "e_work", "p_pending"])

SchedSnap = collections.namedtuple(
    "SchedSnap", ["submitted", "rejected", "shed", "lost", "evicted",
                  "requeued", "completed", "meas", "ledger_nj"])


def dev_snap(fs, copy: bool = False) -> DevSnap:
    """Snapshot the device arrays :func:`obs_tick` deltas against.
    ``copy=True`` for the in-place NumPy driver; the JAX carry is
    immutable so the traced path snapshots by reference."""
    g = (lambda a: a.copy()) if copy else (lambda a: a)
    return DevSnap(on=g(fs.on), cycles=g(fs.cycles),
                   acquired=g(fs.acquired), skipped=g(fs.skipped),
                   emit_count=g(fs.emit_count), e_work=g(fs.e_work),
                   p_pending=g(fs.p_pending))


def sched_snap(ss, xp=np) -> SchedSnap:
    """Snapshot the scheduler's scalar counters (+ ledger sums)."""
    return SchedSnap(submitted=ss.submitted, rejected=ss.rejected,
                     shed=ss.shed, lost=ss.lost, evicted=ss.evicted,
                     requeued=ss.requeued, completed=ss.completed,
                     meas=xp.sum(ss.meas_wl),
                     ledger_nj=xp.sum(ss.joules_nj_wl))


def power_cumsum(power: np.ndarray) -> np.ndarray:
    """(R, T+1) prefix-sum table of the power matrix, computed once in
    NumPy and shared by both backends (the JAX path ``jnp.asarray``s
    this exact array), so the realized-window gathers read bit-identical
    float64 values on either side."""
    R, T = power.shape
    cs = np.zeros((R, T + 1), dtype=np.float64)
    np.cumsum(power, axis=1, out=cs[:, 1:])
    return cs


def forecast_error_nw(sp, power, cs, trace_index, phase, T: int, i,
                      xp=np):
    """Per-tick forecast-quality increment, integer nanowatts:
    ``sum_w round(1e9 * |E[mean power over the lookahead | lags at i]
    - realized window mean|)``.

    The prediction is exactly what the dispatch planner computes
    (``forecast_power_rows`` on the same ``power_lags`` gather); the
    realized side is the mean of ticks ``i+1 .. i+L`` of each worker's
    cyclic trace row, read from the shared :func:`power_cumsum` table as
    at most two gathers (plus whole-cycle multiples when ``L > T``).
    """
    rf = RowForecast(order=sp.fc_order, MU=sp.FC_MU, W=sp.FC_W,
                     THRESH=sp.FC_THRESH, HI=sp.FC_HI, LO=sp.FC_LO,
                     model=sp.FC_MODEL)
    lags = power_lags(power, trace_index, i, T, sp.fc_order,
                      phase=phase, xp=xp)
    pred = forecast_power_rows(rf, lags, xp=xp)
    L = sp.lookahead_ticks
    full, m = divmod(L, T)  # static python ints: L, T are params
    a = ((i + 1) % T) if phase is None else (i + 1 + phase) % T
    a = xp.zeros_like(trace_index) + a  # broadcast scalar start -> (N,)
    b = a + m
    wrap = b > T
    b_safe = xp.where(wrap, b - T, b)
    # paired (row, col) gathers — never materialize an (N, T+1) table
    tot = cs[trace_index, T]
    ga = cs[trace_index, a]
    gb = cs[trace_index, b_safe]
    seg = xp.where(wrap, tot - ga + gb, gb - ga)
    realized = (full * tot + seg) / L
    err = xp.abs(pred - realized)
    return xp.sum(xp.round(err * 1e9).astype(xp.int64))


# ---------------------------------------------------------------------------
# telemetry accumulation
# ---------------------------------------------------------------------------


def _acc(ch, w, inc, xp):
    """Pure scalar scatter-add ``ch[w] += inc`` on either namespace."""
    if xp is np:
        out = ch.copy()
        out[w] += inc
        return out
    return ch.at[w].add(inc)


def _quantize_sum(x, scale, xp):
    """Elementwise ``round(x * scale)`` -> int64 sum (order-free)."""
    return xp.sum(xp.round(x * scale).astype(xp.int64))


def tele_tick(op: ObsParams, tele: tuple, *, j, is_close, pw, eff, dt,
              b: DevSnap, sb: SchedSnap, fs, ss, fe_nw, v_bin_idx, xp):
    """Accumulate one tick into the telemetry channels.

    Args:
        tele: ``TELE_FIELDS``-ordered channel tuple (the carry form).
        j: run-relative tick index (0-based), int scalar (traced ok).
        is_close: bool scalar — this tick closes the current window
            (the sampled channels fire exactly once per window).
        pw: (N,) harvested power this tick, watts.
        b / sb: tick-start snapshots (:func:`dev_snap`,
            :func:`sched_snap`).
        fs / ss: end-of-tick fleet / scheduler state views (attribute
            access; ``FleetState`` or the scan's ``_S``/``SS`` tuples).
        fe_nw: int64 scalar forecast-error increment (0 off dispatch
            ticks / in reactive mode).
        v_bin_idx: (N,) int64 voltage histogram bin per worker.
    Returns:
        the updated channel tuple.
    """
    t = dict(zip(TELE_FIELDS, tele))
    w = xp.minimum(j // op.window, op.n_windows - 1)
    i64 = xp.int64
    wake = fs.cycles > b.cycles
    brown = (b.on | wake) & ~fs.on
    incs = {
        "harvest_pj": _quantize_sum(eff * pw * dt, 1e12, xp),
        "spent_pj": _quantize_sum(fs.e_work - b.e_work, 1e12, xp),
        "wakes": xp.sum(wake.astype(i64)),
        "brownouts": xp.sum(brown.astype(i64)),
        "acquired": xp.sum(fs.acquired - b.acquired),
        "emitted": xp.sum(fs.emit_count - b.emit_count),
        "skipped": xp.sum(fs.skipped - b.skipped),
        "admitted": ((ss.submitted - sb.submitted)
                     - (ss.rejected - sb.rejected)),
        "rejected": ss.rejected - sb.rejected,
        "shed": ss.shed - sb.shed,
        "completed": ss.completed - sb.completed,
        "lost": ss.lost - sb.lost,
        "evicted": ss.evicted - sb.evicted,
        "requeued": ss.requeued - sb.requeued,
        "meas_correct": xp.sum(ss.meas_wl) - sb.meas,
        "ledger_nj": xp.sum(ss.joules_nj_wl) - sb.ledger_nj,
        "forecast_err_nw": fe_nw,
    }
    for name, inc in incs.items():
        t[name] = _acc(t[name], w, inc, xp)
    # sampled channels (queue/inflight/on snapshots + the (N,) voltage
    # histogram scatter) fire once per window, at its closing tick —
    # skipped entirely on the ~window-1 other ticks (host branch /
    # lax.cond), which keeps warm telemetry overhead in budget
    flat = w * op.v_bins + v_bin_idx

    def _close_sample(args):
        qd, infl, onw, vh = args
        qd = _acc(qd, w, xp.sum(ss.q_len), xp)
        infl = _acc(infl, w, xp.sum(ss.f_n), xp)
        onw = _acc(onw, w, xp.sum(fs.on.astype(i64)), xp)
        if xp is np:
            vh = vh.copy().reshape(-1)
            np.add.at(vh, flat, 1)
            vh = vh.reshape(op.n_windows, op.v_bins)
        else:
            vh = (vh.reshape(-1).at[flat].add(1)
                  .reshape(op.n_windows, op.v_bins))
        return qd, infl, onw, vh

    sampled = (t["queue_depth"], t["inflight"], t["on_workers"],
               t["v_hist"])
    if xp is np:
        if is_close:
            sampled = _close_sample(sampled)
    else:
        from jax import lax
        sampled = lax.cond(is_close, _close_sample, lambda a: a, sampled)
    (t["queue_depth"], t["inflight"], t["on_workers"],
     t["v_hist"]) = sampled
    return tuple(t[f] for f in TELE_FIELDS)


def v_bins_of(op: ObsParams, v, xp):
    """(N,) histogram bin per worker: ``floor(v * v_bins / v_hi)``,
    clipped into range (int64)."""
    idx = (v * (op.v_bins / op.v_hi)).astype(xp.int64)
    return xp.clip(idx, 0, op.v_bins - 1)


# ---------------------------------------------------------------------------
# event rings
# ---------------------------------------------------------------------------


def _ring_push(op: ObsParams, ring: tuple, mask, kind: int, i, arg, xp):
    """Push one event kind into every ring row flagged by ``mask``
    ((N+1,) bool). Writes land at slot ``n_ev % ring`` (oldest records
    are overwritten: drop-oldest semantics with the drop count derived
    as ``max(0, n_ev - ring)``). Host fast path / ``lax.cond`` twin on
    event-free ticks, mirroring ``fleet.sched.admit``."""
    if xp is np:
        if not mask.any():
            return ring
        return _ring_push_impl(op, ring, mask, kind, i, arg, xp)
    from jax import lax
    return lax.cond(xp.any(mask),
                    lambda r: _ring_push_impl(op, r, mask, kind, i, arg,
                                              xp),
                    lambda r: r, ring)


def _ring_push_impl(op: ObsParams, ring: tuple, mask, kind, i, arg, xp):
    rt, rk, ra, n_ev = ring
    R = op.ring
    rows = xp.arange(op.n + 1, dtype=xp.int64)
    dump = (op.n + 1) * R  # scatter sink for unflagged rows
    flat = xp.where(mask, rows * R + n_ev % R, dump)

    def setv(a, v):
        if xp is np:
            ext = xp.concatenate([a.reshape(-1),
                                  xp.zeros(1, dtype=xp.int64)])
            ext = _scatter_set(ext, flat, xp.where(mask, v, 0), xp)
            return ext[:dump].reshape(op.n + 1, R)
        # jax: unflagged rows target the out-of-bounds dump slot, which
        # mode="drop" discards — no concat/slice per push
        return (a.reshape(-1).at[flat].set(v, mode="drop")
                .reshape(op.n + 1, R))

    z = xp.zeros(op.n + 1, dtype=xp.int64)
    return (setv(rt, z + i), setv(rk, z + kind), setv(ra, arg),
            n_ev + mask)


def _pad_row(x, fill, xp):
    """(N,) worker array -> (N+1,) with the scheduler row appended."""
    return xp.concatenate([x, xp.asarray([fill]).astype(x.dtype)])


def ring_tick(op: ObsParams, sp, ring: tuple, *, i, b: DevSnap,
              sb: SchedSnap, assign_mask, assign_wl, evict_mask, fs, ss,
              xp):
    """Push this tick's events: six per-worker kinds (wake, brownout,
    assign, acquire, emit, evict) and six scheduler-track kinds at row
    ``n`` (admit/reject/shed/complete/lost/requeue, ``arg`` = count).
    Push order is fixed (lifecycle order within the tick), so both
    backends fill identical rings."""
    i64 = xp.int64
    wake = fs.cycles > b.cycles
    brown = (b.on | wake) & ~fs.on
    acq = fs.acquired > b.acquired
    emit = fs.emit_count > b.emit_count
    zi = xp.zeros(op.n, dtype=i64)
    per_worker = (
        (assign_mask, EV_ASSIGN, assign_wl),
        (wake, EV_WAKE, fs.cycles),
        (acq, EV_ACQUIRE, fs.w_wl),
        (emit, EV_EMIT, fs.w_units_done),
        (brown, EV_BROWN, zi),
        (evict_mask, EV_EVICT, zi),
    )
    for mask, kind, arg in per_worker:
        ring = _ring_push(op, ring, _pad_row(mask, False, xp), kind, i,
                          _pad_row(arg.astype(i64), 0, xp), xp)
    sched_row = _pad_row(xp.zeros(op.n, dtype=bool), True, xp)
    counts = (
        (EV_ADMIT, (ss.submitted - sb.submitted)
         - (ss.rejected - sb.rejected)),
        (EV_REJECT, ss.rejected - sb.rejected),
        (EV_SHED, ss.shed - sb.shed),
        (EV_COMPLETE, ss.completed - sb.completed),
        (EV_LOST, ss.lost - sb.lost),
        (EV_REQUEUE, ss.requeued - sb.requeued),
    )
    zarg = xp.zeros(op.n + 1, dtype=i64)
    for kind, count in counts:
        ring = _ring_push(op, ring, sched_row & (count > 0), kind, i,
                          zarg + count, xp)
    return ring


# ---------------------------------------------------------------------------
# the one shared tick entry point
# ---------------------------------------------------------------------------


def obs_tick(op: ObsParams, sp, tele: tuple, ring: tuple | None, *, i, j,
             is_tick, pw, eff, dt, b: DevSnap, sb: SchedSnap,
             assign_mask, assign_wl, evict_mask, fs, ss, power, cs,
             trace_index, phase, T: int, xp):
    """Advance telemetry (+ rings in trace mode) by one serve tick.

    Args:
        i / j: absolute trace tick / run-relative tick.
        is_tick: bool — this is a dispatch-cadence tick (gates the
            forecast-error channel, matching when the planner runs).
        assign_mask / assign_wl: (N,) post-dispatch assignment mask and
            workload ids (``p_pending`` rising edge this tick).
        evict_mask: (N,) assignments revoked by the straggler pass.
        fs / ss: end-of-tick state views.
        power / cs / trace_index / phase / T: harvest-matrix context for
            the forecast-error gathers (``cs`` from
            :func:`power_cumsum`; both backends pass bit-identical
            tables).
    Returns:
        ``(tele, ring)`` updated tuples (``ring`` passed through
        untouched unless ``op.mode == "trace"``).
    """
    if sp.forecast and xp is np:
        # host fast path: the channel only accrues on dispatch ticks
        fe = (forecast_error_nw(sp, power, cs, trace_index, phase, T, i,
                                xp=xp) if is_tick else np.int64(0))
    elif sp.forecast:
        # lax.cond, not where: the gathers + forecast math only execute
        # on dispatch ticks (1 in dispatch_every), same as the planner
        from jax import lax
        fe = lax.cond(
            is_tick,
            lambda: forecast_error_nw(sp, power, cs, trace_index,
                                      phase, T, i, xp=xp),
            lambda: xp.asarray(0, dtype=xp.int64))
    else:
        fe = xp.asarray(0, dtype=xp.int64)
    is_close = ((j + 1) % op.window == 0) | (j == op.n_ticks - 1)
    tele = tele_tick(op, tele, j=j, is_close=is_close, pw=pw, eff=eff,
                     dt=dt, b=b, sb=sb, fs=fs, ss=ss, fe_nw=fe,
                     v_bin_idx=v_bins_of(op, fs.v, xp), xp=xp)
    if op.mode == "trace":
        ring = ring_tick(op, sp, ring, i=i, b=b, sb=sb,
                         assign_mask=assign_mask, assign_wl=assign_wl,
                         evict_mask=evict_mask, fs=fs, ss=ss, xp=xp)
    return tele, ring


# ---------------------------------------------------------------------------
# host recorder
# ---------------------------------------------------------------------------


class FleetObs:
    """Host handle over one instrumented serve run.

    Owns the telemetry/ring arrays that both serve paths advance with
    :func:`obs_tick`: the NumPy host window
    (``repro.fleet.scheduler._HostServe``) evaluates it per tick and
    writes the arrays back here at each window's end; the fused JAX
    path threads them through the scan carry (``backend_jax.run_serve``
    writes them back here). ``summary()`` is the JSON-able channel dump
    the CLIs attach to their run summaries — two runs' summaries compare
    bit-exactly with ``==``.
    """

    def __init__(self, op: ObsParams, params, sp):
        if op.mode == "off":
            raise ValueError("FleetObs is for mode 'tele' or 'trace'; "
                             "pass obs=None for uninstrumented runs")
        self.op = op
        self.p = params  # FleetParams (power matrix context)
        self.sp = sp
        self.tele = init_tele(op)
        self.ring = init_ring(op) if op.mode == "trace" else None
        self.cs = power_cumsum(params.power) if sp.forecast else None

    # -- reporting ----------------------------------------------------------

    def events_recorded(self) -> tuple[int, int]:
        """(recorded, dropped) totals across all ring rows."""
        if self.ring is None:
            return 0, 0
        n_ev = np.asarray(self.ring.n_ev)
        return (int(np.minimum(n_ev, self.op.ring).sum()),
                int(np.maximum(n_ev - self.op.ring, 0).sum()))

    def summary(self) -> dict:
        """JSON-able dump: config, every channel as a plain int list,
        and the ring fill/drop ledger."""
        rec, dropped = self.events_recorded()
        op = self.op
        return {
            "mode": op.mode,
            "window_ticks": op.window,
            "window_s": op.window * self.p.dt,
            "n_windows": op.n_windows,
            "v_bins": op.v_bins,
            "v_hi": op.v_hi,
            "ring": op.ring,
            "channels": {f: np.asarray(getattr(self.tele, f))
                         .reshape(-1).tolist()
                         for f in TELE_FIELDS},
            "events": {"recorded": rec, "dropped": dropped},
        }


def make_fleet_obs(mode: str, params, sp, n_ticks: int, *,
                   window: int = 100, v_bins: int = 32,
                   v_hi: float | None = None, ring: int = 256):
    """Build a :class:`FleetObs` for one run (or ``None`` for "off").
    ``v_hi`` defaults to the fleet's largest ``v_max`` (the histogram
    covers the whole reachable voltage range)."""
    if mode == "off":
        return None
    from repro.obs.state import make_obs_params
    if v_hi is None:
        v_hi = float(np.max(params.v_max)) * 1.0001  # v=v_max in-range
    op = make_obs_params(mode, params.n, n_ticks, window=window,
                         v_bins=v_bins, v_hi=v_hi, ring=ring)
    return FleetObs(op, params, sp)
