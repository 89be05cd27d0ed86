"""NumPy reference backend: the per-tick fleet transition, in place.

This is the original ``FleetWorkerPool.step`` lifted out of the class into
pure struct-of-arrays functions over ``(FleetParams, FleetState)`` — the
arithmetic mirrors the scalar ``core.intermittent`` executor expression-
for-expression (pinned at N=1 by tests/test_fleet.py), and is in turn the
reference the JAX scan backend is pinned against. Python-side outputs
(``results`` per-worker EmittedResult lists in local mode, ``events``
tuples in dispatch mode) are appended to caller-owned lists; the JAX
backend replaces them with fixed-capacity arrays.

Event tuples pushed to ``events`` in dispatch mode:
  ("emit", t, worker, ticket, units_done, req_units, batch)
  ("lost", t, worker, ticket)   -- brown-out or failed emission

Under the persistence plane (``params.persist`` != "none" — see
docs/persistence_plane.md) a brown-out mid-request is a power-down, not
a loss: the worker keeps ``has_work``, pays a FRAM restore on its next
productive wake, and re-executes to *exact* completion. No "lost"
events are emitted in the exact disciplines.
"""
from __future__ import annotations

import numpy as np

from repro.core.energy import (capacitor_draw, capacitor_harvest,
                               capacitor_usable_energy)
from repro.core.intermittent import EmittedResult
from repro.core.policies import SKIP
from repro.fleet.state import STATE_FIELDS, FleetParams, FleetState

EMIT = "emit"
LOST = "lost"


def usable_quanta(p: FleetParams, s: FleetState) -> np.ndarray | None:
    """Per-worker usable energy quanta above brown-out of a quantized
    fleet (``p.quantum_j`` set; ``None`` for a float fleet): what its
    dispatch decides on (``sched.dispatch``'s ``us``)."""
    if p.quantum_j is None:
        return None
    from repro.core.energy import capacitor_usable_q
    from repro.fleet.qtick import quantize_fleet_cached
    return capacitor_usable_q(s.v, quantize_fleet_cached(p).E_OFF, np)


def usable_energy(p: FleetParams, s: FleetState) -> np.ndarray:
    """Per-worker usable joules — the budget the host scheduler reads.

    Quantized states (``p.quantum_j`` set) hold energy quanta in ``v``;
    the quanta -> joules conversion here is the exact float64 expression
    the fused jax serve build uses, so forecast planning agrees
    bit-for-bit across backends in both precisions."""
    us = usable_quanta(p, s)
    if us is not None:
        return us.astype(np.float64) * p.quantum_j
    return capacitor_usable_energy(s.v, capacitance_f=p.C, v_off=p.v_off)


def _draw_at(p: FleetParams, s: FleetState, idx: np.ndarray,
             amount: np.ndarray) -> np.ndarray:
    """Draw ``amount`` at workers ``idx``; brown-outs get v_off and False,
    exactly like ``Capacitor.draw``."""
    new_v, ok = capacitor_draw(s.v[idx], amount, capacitance_f=p.C[idx],
                               v_off=p.v_off)
    s.v[idx] = new_v
    return ok


def tick(p: FleetParams, s: FleetState, i: int,
         results: list[list[EmittedResult]] | None,
         events: list[tuple] | None) -> None:
    """Advance all N workers by one dt (trace index ``i``)."""
    if p.quantum_j is not None:
        return _tick_quantized(p, s, i, events)
    t = i * p.dt
    dt = p.dt

    # 1. harvest (mirrors Capacitor.harvest)
    if p.phase is None:
        pw = p.power[p.trace_index, i % p.T]
    else:
        pw = p.power[p.trace_index, (i + p.phase) % p.T]
    s.e_harvest += p.eff * pw * dt
    s.v = capacitor_harvest(s.v, pw, dt, capacitance_f=p.C,
                            booster_eff=p.eff, v_max=p.v_max)

    # 2. turn on at v_on
    waking = ~s.on & (s.v >= p.v_on)
    s.on |= waking
    s.cycles += waking
    active = s.on.copy()

    # workers holding work from a previous tick progress it; workers
    # acquiring this tick spend the whole dt on acquisition (scalar
    # semantics: the acquisition branch ends the step)
    working = active & s.has_work
    idle = active & ~s.has_work

    # persistence plane: a worker that powered down mid-request pays the
    # FRAM restore read before it may progress again (the restore
    # consumes its tick)
    if p.persist != "none":
        working = _restore(p, s, working)

    # 3. acquisition
    if p.mode == "local":
        _acquire_local(p, s, idle, t)
    else:
        _acquire_dispatch(p, s, idle, t, events)

    # 4. progress in-flight work by one dt of active execution
    emit_now = np.zeros(p.n, dtype=bool)
    if working.any():
        emit_now = _progress(p, s, working, t, events)

    # 5. emission (BLE packet / host transfer)
    finish = (working & s.has_work & s.on
              & ((s.w_units_done >= s.w_target) | emit_now))
    if finish.any():
        _emit(p, s, np.nonzero(finish)[0], t, results, events)


def _tick_quantized(p: FleetParams, s: FleetState, i: int,
                    events: list[tuple] | None) -> None:
    """Quantized (int32 quanta) dispatch tick: the NumPy reference
    driver for the serve-tick megakernel path. Runs the exact
    xp-generic integer expressions of ``repro.fleet.qtick`` (the same
    function body the ``kernel="q32"`` scan traces) and decodes the
    fixed-capacity event log back into the host tuple protocol."""
    from repro.fleet import qtick as Q
    qp = Q.quantize_fleet_cached(p)
    qh = Q.harvest_row(p, qp, p.trace_index, p.phase, i, np)
    st = tuple(getattr(s, f) for f in STATE_FIELDS)
    z = lambda: np.zeros(p.n, dtype=np.int32)  # noqa: E731
    ev = (z(), z(), z(), z())
    st, ev = Q.tick_q(p, qp, st, ev, qh, i, np, Q.np_while)
    for f, x in zip(STATE_FIELDS, st):
        setattr(s, f, x)
    if events is None:
        return
    t = i * p.dt
    evc, _, evtk, evu = ev
    for w in np.nonzero(evc != Q.EV_NONE)[0]:
        w = int(w)
        if evc[w] == Q.EV_EMIT:
            events.append((EMIT, t, w, int(evtk[w]), int(evu[w]),
                           int(s.w_tile[w]), int(s.w_batch[w])))
        else:
            events.append((LOST, t, w, int(evtk[w])))


def _acquire_local(p: FleetParams, s: FleetState, idle: np.ndarray,
                   t: float) -> None:
    due = idle & (t >= s.next_sample_t)
    if not due.any():
        return
    d_idx = np.nonzero(due)[0]
    delta = t - s.next_sample_t[d_idx]
    k = delta // p.P
    s.sample_counter[d_idx] += k.astype(np.int64) + 1
    s.next_sample_t[d_idx] += p.P * (k + 1.0)
    # decide BEFORE spending anything (SMART skips the whole round)
    us = usable_energy(p, s)[d_idx]
    init, refine = p.policy.decide_batch(us, p.tables[0], p.acc)
    skip = init == SKIP
    s.skipped[d_idx[skip]] += 1
    go = d_idx[~skip]
    if go.size == 0:
        return
    fixed = p.FIX[0]
    ok = _draw_at(p, s, go, np.minimum(fixed, us[~skip]))
    s.on[go[~ok]] = False
    succ = go[ok]
    s.e_work[succ] += fixed
    s.acquired[succ] += 1
    s.has_work[succ] = True
    s.w_ticket[succ] = s.sample_counter[succ] - 1
    s.w_t_acq[succ] = t
    s.w_cycle_acq[succ] = s.cycles[succ]
    s.w_units_done[succ] = 0
    s.w_left[succ] = 0.0
    s.w_target[succ] = np.where(refine, p.NU[0], init)[~skip][ok]
    s.w_tile[succ] = 0
    s.w_wl[succ] = 0
    s.w_batch[succ] = 1


def _restore(p: FleetParams, s: FleetState, working: np.ndarray
             ) -> np.ndarray:
    """Persistence-plane restore (persist != "none"): pay the FRAM read
    that reloads the progress image (ckpt) or log header (undolog).
    Returns ``working`` minus the restoring lanes — a restore consumes
    the worker's tick before it can progress again."""
    rest = working & s.need_restore
    if not rest.any():
        return working
    r_idx = np.nonzero(rest)[0]
    rj = p.REST_J[s.w_wl[r_idx]]
    ok = _draw_at(p, s, r_idx, rj)
    # not enough banked for the read yet: recharge more (defensive — a
    # freshly woken worker holds a full cycle of charge)
    s.on[r_idx[~ok]] = False
    succ = r_idx[ok]
    s.need_restore[succ] = False
    s.restores[succ] += 1
    s.e_persist[succ] += rj[ok]
    if p.persist == "ckpt":
        # Mementos semantics: rewind to the checkpointed unit counter;
        # progress past the last image is lost and re-executes
        s.w_units_done[succ] = s.ck_units[succ]
    # either way the partial unit in flight restarts idempotently
    s.w_left[succ] = 0.0
    return working & ~rest


def _acquire_dispatch(p: FleetParams, s: FleetState, idle: np.ndarray,
                      t: float, events: list[tuple]) -> None:
    due = idle & s.p_pending
    if not due.any():
        return
    d_idx = np.nonzero(due)[0]
    wl = s.p_wl[d_idx]
    us = usable_energy(p, s)[d_idx]
    fixed = p.FIX[wl]
    ok = _draw_at(p, s, d_idx, np.minimum(fixed, us))
    fail = d_idx[~ok]
    s.on[fail] = False
    if p.persist == "none":
        s.p_pending[d_idx] = False
        for w in fail:
            events.append((LOST, t, int(w), int(s.p_ticket[w])))
    else:
        # exact disciplines never drop an accepted request: a failed
        # acquisition keeps the assignment pending across the recharge
        s.p_pending[d_idx[ok]] = False
    succ = d_idx[ok]
    if succ.size == 0:
        return
    if p.persist != "none":
        # fresh request: clear any stale persistence carried from an
        # evicted or completed predecessor
        s.need_restore[succ] = False
        s.ck_units[succ] = 0
    s.e_work[succ] += fixed[ok]
    s.acquired[succ] += 1
    s.has_work[succ] = True
    s.w_ticket[succ] = s.p_ticket[succ]
    s.w_t_acq[succ] = t
    s.w_cycle_acq[succ] = s.cycles[succ]
    s.w_units_done[succ] = 0
    s.w_left[succ] = 0.0
    s.w_tile[succ] = s.p_units[succ]
    s.w_batch[succ] = s.p_batch[succ]
    s.w_target[succ] = s.p_units[succ] * s.p_batch[succ]
    s.w_wl[succ] = s.p_wl[succ]


def _progress(p: FleetParams, s: FleetState, working: np.ndarray, t: float,
              events: list[tuple] | None) -> np.ndarray:
    """One dt of active execution for every working device; returns the
    emit_now mask (budget died at a unit boundary -> emit what we have)."""
    emit_now = np.zeros(p.n, dtype=bool)
    e_step = np.zeros(p.n)
    e_step[working] = p.active_power_w[working] * p.dt
    # scalar loop guard: `while e_step > 0 and units_done < target` —
    # a target-0 work item skips straight to emission
    run = working & (s.w_units_done < s.w_target)
    while True:
        r_idx = np.nonzero(run)[0]
        if r_idx.size == 0:
            break
        # unit boundary: start the next unit only if unit + reserve are
        # affordable now. Approximate: reserve = the BLE emit packet and
        # "cant" emits the partial result. Exact (persist != "none"):
        # reserve additionally covers the checkpoint image / unit commit
        # write, and "cant" is a forced power-down — the request is
        # persisted, never truncated.
        starting = s.w_left[r_idx] <= 0
        if starting.any():
            s_idx = r_idx[starting]
            ud = s.w_units_done[s_idx]
            tile = s.w_tile[s_idx]
            gidx = np.where(tile > 0, ud % np.maximum(tile, 1), ud)
            nc = p.UC[s.w_wl[s_idx], gidx]
            us = usable_energy(p, s)[s_idx]
            if p.persist == "none":
                cant = us < nc + p.EMITC[s.w_wl[s_idx]]
                emit_now[s_idx[cant]] = True
            else:
                rsv = p.CKPT_J if p.persist == "ckpt" else p.COMMIT_J
                cant = us < (nc + rsv[s.w_wl[s_idx]]
                             + p.EMITC[s.w_wl[s_idx]])
                if p.persist == "ckpt":
                    # the voltage trigger fired: serialize dirty
                    # progress to FRAM before dying (the reserve at the
                    # previous boundary guarantees this write is funded)
                    dirty = s_idx[cant & (s.w_units_done[s_idx]
                                          != s.ck_units[s_idx])]
                    if dirty.size:
                        cj = p.CKPT_J[s.w_wl[dirty]]
                        okc = _draw_at(p, s, dirty, cj)
                        wrote = dirty[okc]
                        s.ck_units[wrote] = s.w_units_done[wrote]
                        s.persists[wrote] += 1
                        s.e_persist[wrote] += cj[okc]
                down = s_idx[cant]
                s.on[down] = False
                s.need_restore[down] = True
            run[s_idx[cant]] = False
            go = s_idx[~cant]
            s.w_left[go] = nc[~cant]
            r_idx = np.nonzero(run)[0]
            if r_idx.size == 0:
                break
        take = np.minimum(e_step[r_idx], s.w_left[r_idx])
        ok = _draw_at(p, s, r_idx, take)
        fail = r_idx[~ok]
        if fail.size:
            s.on[fail] = False
            run[fail] = False
            if p.persist == "none":
                # power failure mid-work: volatile by design; work lost
                s.has_work[fail] = False
                if p.mode == "dispatch":
                    for w in fail:
                        events.append(
                            (LOST, t, int(w), int(s.w_ticket[w])))
            else:
                # the persisted request survives; restore re-runs the
                # partial unit
                s.need_restore[fail] = True
        succ = r_idx[ok]
        tk = take[ok]
        s.e_work[succ] += tk
        s.w_left[succ] -= tk
        e_step[succ] -= tk
        fin = succ[s.w_left[succ] <= 1e-18]
        halted = np.empty(0, dtype=np.int64)
        if p.persist == "undolog" and fin.size:
            # Alpaca task commit: the completed unit's undo-buffer write
            # makes w_units_done durable (funded by the boundary reserve)
            cj = p.COMMIT_J[s.w_wl[fin]]
            okc = _draw_at(p, s, fin, cj)
            halted = fin[~okc]
            s.on[halted] = False
            s.need_restore[halted] = True
            fin = fin[okc]
            s.persists[fin] += 1
            s.e_persist[fin] += cj[okc]
        s.w_units_done[fin] += 1
        s.w_left[fin] = 0.0
        run[succ] = ((e_step[succ] > 0)
                     & (s.w_units_done[succ] < s.w_target[succ]))
        run[halted] = False
    return emit_now


def _emit(p: FleetParams, s: FleetState, f_idx: np.ndarray, t: float,
          results: list[list[EmittedResult]] | None,
          events: list[tuple] | None) -> None:
    ec = p.EMITC[s.w_wl[f_idx]]
    ok = _draw_at(p, s, f_idx, ec)
    fail = f_idx[~ok]
    s.on[fail] = False
    if p.persist == "none":
        s.has_work[fail] = False  # volatile: failed emission loses it
        if p.mode == "dispatch":
            for w in fail:
                events.append((LOST, t, int(w), int(s.w_ticket[w])))
    else:
        # persisted work retries the emission after the next restore
        s.need_restore[fail] = True
    succ = f_idx[ok]
    s.e_work[succ] += ec[ok]
    s.has_work[succ] = False
    s.emit_count[succ] += 1
    s.emit_units_sum[succ] += s.w_units_done[succ]
    if p.mode == "local":
        s.emit_acc_sum[succ] += p.acc[np.minimum(s.w_units_done[succ],
                                                 p.NU[0])]
    for w in succ:  # emissions are rare relative to ticks
        w = int(w)
        if p.mode == "local":
            results[w].append(EmittedResult(
                int(s.w_ticket[w]), int(s.w_units_done[w]),
                float(s.w_t_acq[w]), t,
                int(s.cycles[w] - s.w_cycle_acq[w])))
        else:
            events.append(
                (EMIT, t, w, int(s.w_ticket[w]),
                 int(s.w_units_done[w]), int(s.w_tile[w]),
                 int(s.w_batch[w])))
