"""The plain reference of the streaming fleet serve: the same semantics as
the program, written again from its specification, and independent of
the program's code (it imports nothing of ``repro``).

What one tick does, in order (``t = i * dt`` seconds, ``i`` the global
tick):

1. admission: this tick's arrivals join the tail of their workload's
   FIFO queue, in workload order, while the total backlog is under
   ``max_queue``; the rest are rejected;
2. every ``dispatch_every`` ticks, before the device tick:
   - shed: each queue drops its front while the front request is older
     than ``shed_after_s``;
   - dispatch: the idle workers (on, no work, nothing pending) are
     ranked by usable energy, richest first (ties by worker index); the
     non-empty queues are served oldest front first (ties by workload
     index). For one queue, each ranked worker not yet given work takes
     the largest batch of floor-knob requests its budget affords, at the
     largest knob the budget affords per request, while requests remain
     (the SMART rule of the paper: never start below the accuracy floor);
3. the device tick of every worker, in stored-energy quanta: harvest,
   wake at the turn-on level, acquire a pending assignment, progress the
   work by one tick of active draw a knob unit at a time (a unit starts
   only if the unit and the result's emission are affordable; else the
   partial result is emitted), emit; a draw that would cross the
   brown-out level powers the worker off and loses its work;
4. collection: an emitting worker completes ``units // knob`` requests
   of its batch, plus one partial request; its unfinished requests and
   all requests of a worker that lost its work are retried at the front
   of their queue (in worker, then slot order), until a request has
   been retried ``max_retries`` times and is lost;
5. every ``dispatch_every`` ticks, after collection: an assignment older
   than ``grace_s + deadline_factor * est`` (``est``: the batch's full
   cost at the worker's active power) is evicted and its requests are
   retried.

The queues are lists of requests, the control plane loops over requests
and workers; the device tick is written over arrays of workers, since
it is the same elementwise rule for each.

``ft`` is the float type of every float the control plane computes
(budgets, cost tables, times, accuracy sums): float64, as the
configuration states, or float32 for the control.
"""
from __future__ import annotations

import bisect
import collections

import numpy as np

import deploy

EMIT, LOST = 1, 2

# device-state fields as the program names them, and those the
# quantized dispatch tick never writes (they stay zero)
DEVICE_FIELDS = ("v", "on", "cycles", "acquired", "e_work", "e_harvest",
                 "has_work", "w_t_acq", "w_cycle_acq", "w_units_done",
                 "w_left", "w_target", "w_tile", "w_wl", "w_batch",
                 "p_pending", "p_wl", "p_units", "p_batch", "p_t_assigned",
                 "emit_count", "emit_units_sum")
ZERO_FIELDS = ("skipped", "next_sample_t", "sample_counter", "w_ticket",
               "p_ticket", "emit_acc_sum", "need_restore", "ck_units",
               "e_persist", "persists", "restores")
COUNTERS = ("submitted", "rejected", "shed", "lost", "evicted", "requeued",
            "completed", "lat_sum")
# the per-chunk record's counters (a record holds their deltas)
RECORD_COUNTERS = ("submitted", "completed", "shed", "rejected", "lost",
                   "evicted", "requeued")


def _q(x, quantum: float) -> np.ndarray:
    """Joules to whole quanta, rounding half to even."""
    return np.rint(np.asarray(x, np.float64) / quantum).astype(np.int64)


class Workload:
    """One workload's tables in joules (control plane) and quanta
    (device)."""

    def __init__(self, tab: dict, quantum: float, ft):
        self.name = tab["name"]
        units = tab["units"]
        self.nu = units.shape[0]
        ucum = np.concatenate([[0.0], np.cumsum(units)])
        cu = ucum + tab["fixed"] + tab["emit"]
        self.ucum = [float(x) for x in ucum.astype(ft)]
        self.cu = [float(x) for x in cu.astype(ft)]
        self.full = ft(ucum[self.nu])
        self.fixed, self.emit = ft(tab["fixed"]), ft(tab["emit"])
        self.overhead = float(ft(self.fixed + self.emit))
        self.acc = tab["acc"].astype(ft)
        ok = np.nonzero(tab["acc"] >= tab["floor"])[0]
        self.smart = tab["floor"] > 0
        self.p_req = int(ok[0]) if ok.size else None  # None: unattainable
        # the quality ledger: sample s of 64 is right at k units iff
        # s < round(acc[k] * 64); a completion is priced at its
        # cumulative cost in whole nanojoules
        self.right = np.round(tab["acc"] * 64).astype(np.int64)
        self.nj = np.round(cu * 1e9).astype(np.int64)
        # device quanta
        self.unit_q = _q(units, quantum)
        self.fixed_q = int(_q(tab["fixed"], quantum))
        self.emit_q = int(_q(tab["emit"], quantum))


class PlainFleet:
    """The reference fleet and control plane of one configuration."""

    def __init__(self, config: dict, power: np.ndarray, phase: np.ndarray,
                 e0: np.ndarray, ft=np.float64):
        d = config["device"]
        self.ft = ft
        self.n = n = int(config["workers"])
        self.dt = float(config["dt_s"])
        q = self.quantum = float(d["quantum_j"])
        self.every = int(config["dispatch_every"])
        self.B = int(config["max_batch"])
        self.max_queue = int(config["max_queue"])
        self.max_retries = int(config["max_retries"])
        self.shed_after = float(config["shed_after_s"])
        self.grace = float(config["grace_s"])
        self.deadline_factor = float(config["deadline_factor"])
        self.bins = int(config["lat_bins"])
        self.lat_max = 2.0 * (self.shed_after + self.grace)
        self.binw = self.lat_max / self.bins
        if config["sched"] != "reactive":
            raise ValueError("the plain reference plans on the "
                             "instantaneous budget (sched reactive) only")
        self.wls = [Workload(t, q, ft) for t in deploy.workload_tables(config)]
        W = self.W = len(self.wls)
        u_max = max(w.nu for w in self.wls)
        # device constants in quanta (one capacitor class per fleet)
        c = d["capacitance_f"]
        self.e_on = int(_q(0.5 * c * d["v_on"] ** 2, q))
        self.e_off = int(_q(0.5 * c * d["v_off"] ** 2, q))
        self.e_max = int(_q(0.5 * c * d["v_max"] ** 2, q))
        self.e_step = int(_q(d["active_power_w"] * self.dt, q))
        self.active_p = ft(d["active_power_w"])
        self.harvest_q = _q(d["booster_eff"] * power * self.dt, q)
        self.row = deploy.trace_rows(config)
        self.phase = np.asarray(phase, np.int64)
        self.T = power.shape[1]
        big = 2 ** 30  # a unit past a workload's last: never affordable
        self.unit_q = np.full((W, u_max), big, np.int64)
        for k, w in enumerate(self.wls):
            self.unit_q[k, :w.nu] = w.unit_q
        self.fixed_q = np.array([w.fixed_q for w in self.wls], np.int64)
        self.emit_q = np.array([w.emit_q for w in self.wls], np.int64)
        # device state, by the program's field names
        i64 = lambda: np.zeros(n, np.int64)  # noqa: E731
        b = lambda: np.zeros(n, bool)  # noqa: E731
        self.dev = {f: i64() for f in DEVICE_FIELDS}
        for f in ("on", "has_work", "p_pending"):
            self.dev[f] = b()
        self.dev["w_batch"] = np.ones(n, np.int64)
        self.dev["p_batch"] = np.ones(n, np.int64)
        self.dev["v"] = np.asarray(e0, np.int64).copy()
        # control plane: a queue of [arrival tick, retries] per workload,
        # each worker's assignment, and the counters
        self.queues = [collections.deque() for _ in range(W)]
        self.f_n = np.zeros(n, np.int64)
        self.f_wl = np.zeros(n, np.int64)
        self.f_units = np.zeros(n, np.int64)
        self.f_t0 = np.zeros(n, np.int64)  # tick
        # each worker's last batch of [arrival tick, retries], as given
        self.f_req = [[] for _ in range(n)]
        self.count = dict.fromkeys(COUNTERS, 0)
        self.completed_wl = [0] * W
        self.units_wl = [0] * W
        self.acc_wl = [ft(0.0)] * W
        self.meas_wl = [0] * W
        self.nj_wl = [0] * W
        self.lat_hist = [0] * self.bins
        self.batch_hist = [0] * (self.B + 1)

    def time(self, i: int):
        return self.ft(i) * self.ft(self.dt)

    # -- control plane -----------------------------------------------------

    def admit(self, counts, i: int) -> None:
        space = max(self.max_queue - sum(len(q) for q in self.queues), 0)
        for w, c in enumerate(counts):
            c = int(c)
            k = min(c, space)
            space = max(space - c, 0)
            self.queues[w].extend([i, 0] for _ in range(k))
            self.count["submitted"] += c
            self.count["rejected"] += c - k

    def shed(self, i: int) -> None:
        t = self.time(i)
        for q in self.queues:
            while q and t - self.time(q[0][0]) > self.shed_after:
                q.popleft()
                self.count["shed"] += 1

    def budgets(self) -> np.ndarray:
        """Usable joules above the brown-out level, per worker."""
        ft = self.ft
        us = np.maximum(self.dev["v"] - self.e_off, 0)
        return us.astype(ft) * ft(self.quantum)

    def dispatch(self, i: int) -> None:
        d = self.dev
        idle = np.nonzero(d["on"] & ~d["has_work"] & ~d["p_pending"])[0]
        budget = self.budgets()
        ranked = idle[np.argsort(-budget[idle], kind="stable")]
        heads = sorted((self.time(q[0][0]), w)
                       for w, q in enumerate(self.queues) if q)
        taken = set()
        for _, w in heads:
            wl = self.wls[w]
            q = self.queues[w]
            for r in ranked:
                if not q:
                    break
                r = int(r)
                if r in taken:
                    continue
                bn = float(budget[r])
                k_aff = bisect.bisect_right(wl.cu, bn) - 1
                p_req = wl.p_req if wl.smart else max(k_aff, 0)
                if p_req is None or k_aff < p_req or k_aff < 0:
                    if wl.smart:
                        break  # ranked by budget: no poorer worker affords
                    continue
                spend = float(self.ft(bn - wl.overhead))
                per_req = wl.ucum[p_req] if p_req <= wl.nu else np.inf
                if per_req > 0:
                    b = int(np.floor_divide(
                        self.ft(spend), self.ft(max(per_req, 1e-300))))
                else:
                    b = self.B
                b = min(max(b, 1), self.B)
                u_want = self._knob(wl, spend, b, p_req)
                if u_want <= 0:
                    continue
                actual = min(b, len(q))
                u = self._knob(wl, spend, actual, p_req)
                reqs = [q.popleft() for _ in range(actual)]
                taken.add(r)
                self._assign(r, w, u, reqs, i)

    def _knob(self, wl: Workload, spend: float, batch: int, p_req: int):
        per = float(self.ft(spend) / self.ft(batch))
        k = bisect.bisect_right(wl.ucum, per) - 1
        return min(max(k, p_req), wl.nu)

    def _assign(self, r: int, w: int, u: int, reqs: list, i: int) -> None:
        d = self.dev
        d["p_pending"][r] = True
        d["p_wl"][r] = w
        d["p_units"][r] = u
        d["p_batch"][r] = len(reqs)
        d["p_t_assigned"][r] = i
        self.f_n[r] = len(reqs)
        self.f_wl[r] = w
        self.f_units[r] = u
        self.f_t0[r] = i
        self.f_req[r] = reqs
        self.batch_hist[len(reqs)] += 1

    def collect(self, events: dict, i: int) -> None:
        """``events``: worker -> (EMIT, units done) or (LOST, 0)."""
        t = self.time(i)
        retry = [[] for _ in range(self.W)]
        for r in sorted(events):
            b = int(self.f_n[r])
            if b == 0:
                continue
            code, done = events[r]
            w, u, reqs = self.f_wl[r], self.f_units[r], self.f_req[r]
            wl = self.wls[w]
            if code == EMIT:
                full = done // u if u > 0 else b
                part = done % u if u > 0 else 0
                units = [u] * min(full, b)
                if part > 0 and full < b:
                    units.append(part)
            else:
                units = []
            for j, req in enumerate(reqs):
                if j >= len(units):
                    retry[w].append(req)
                    continue
                k = units[j]
                lat = t - self.time(req[0])
                self.lat_hist[min(max(int(lat / self.ft(self.binw)), 0),
                                  self.bins - 1)] += 1
                self.count["lat_sum"] += int(np.rint(lat / self.ft(self.dt)))
                sample = self.completed_wl[w] % 64
                self.meas_wl[w] += int(sample < wl.right[k])
                self.nj_wl[w] += int(wl.nj[k])
                self.acc_wl[w] = self.ft(self.acc_wl[w] + wl.acc[k])
                self.units_wl[w] += k
                self.completed_wl[w] += 1
                self.count["completed"] += 1
            self.f_n[r] = 0
        self._retry(retry)

    def _retry(self, retry: list) -> None:
        for w, reqs in enumerate(retry):
            back = []
            for arrival, tries in reqs:
                if tries + 1 > self.max_retries:
                    self.count["lost"] += 1
                else:
                    back.append([arrival, tries + 1])
            self.queues[w].extendleft(reversed(back))
            self.count["requeued"] += len(back)

    def evict(self, i: int) -> None:
        ft = self.ft
        busy = np.nonzero(self.f_n)[0]
        wl = self.f_wl[busy]
        fixed = np.array([w.fixed for w in self.wls], ft)[wl]
        emit = np.array([w.emit for w in self.wls], ft)[wl]
        full = np.array([w.full for w in self.wls], ft)[wl]
        est = (fixed + emit + self.f_n[busy].astype(ft) * full) \
            / self.active_p
        age = self.time(i) - self.f_t0[busy].astype(ft) * ft(self.dt)
        late = busy[age > self.grace + self.deadline_factor * est]
        retry = [[] for _ in range(self.W)]
        for r in late:  # in worker order
            r = int(r)
            self.count["evicted"] += int(self.f_n[r])
            retry[int(self.f_wl[r])] += self.f_req[r]
            self.f_n[r] = 0
            self.dev["p_pending"][r] = False
            self.dev["has_work"][r] = False
        self._retry(retry)

    # -- device ------------------------------------------------------------

    def device_tick(self, i: int) -> dict:
        """One tick of every worker; returns this tick's events. The
        rule is elementwise: harvest and wake apply to all workers,
        acquisition to the idle workers with an assignment pending, and
        progress and emission to the workers holding work."""
        d = self.dev
        h = self.harvest_q[self.row, (i + self.phase) % self.T]
        d["e_harvest"] += h
        E = np.minimum(d["v"] + h, self.e_max)
        waking = ~d["on"] & (E >= self.e_on)
        d["on"] |= waking
        d["cycles"] += waking
        working = np.nonzero(d["on"] & d["has_work"])[0]
        due = np.nonzero(d["on"] & ~d["has_work"] & d["p_pending"])[0]
        events = {}

        # acquisition: the fixed cost, or what is left above brown-out
        if due.size:
            e = E[due]
            fixed = self.fixed_q[d["p_wl"][due]]
            draw = np.minimum(fixed, np.maximum(e - self.e_off, 0))
            ok = e - draw >= self.e_off
            E[due] = np.where(ok, e - draw, self.e_off)
            d["p_pending"][due] = False
            d["on"][due[~ok]] = False
            events.update((int(r), (LOST, 0)) for r in due[~ok])
            got = due[ok]
            d["e_work"][got] += fixed[ok]
            d["acquired"][got] += 1
            d["has_work"][got] = True
            d["w_t_acq"][got] = i
            d["w_cycle_acq"][got] = d["cycles"][got]
            d["w_units_done"][got] = 0
            d["w_left"][got] = 0
            d["w_tile"][got] = d["p_units"][got]
            d["w_batch"][got] = d["p_batch"][got]
            d["w_target"][got] = d["p_units"][got] * d["p_batch"][got]
            d["w_wl"][got] = d["p_wl"][got]
        if not working.size:
            d["v"] = E
            return events

        # progress: one tick of active draw, a knob unit at a time
        w = working
        e, wl, tile = E[w], d["w_wl"][w], d["w_tile"][w]
        done, left, target = (d["w_units_done"][w], d["w_left"][w],
                              d["w_target"][w])
        alive = np.ones(w.size, bool)  # still on and holding the work
        spent = np.zeros(w.size, np.int64)
        step = np.full(w.size, self.e_step)
        run = done < target
        stop = np.zeros(w.size, bool)  # out of budget at a unit boundary
        emit_q = self.emit_q[wl]
        u_max = self.unit_q.shape[1]
        while run.any():
            start = run & (left <= 0)
            nxt = np.where(tile > 0, done % np.maximum(tile, 1), done)
            cost = self.unit_q[wl, np.clip(nxt, 0, u_max - 1)]
            cant = start & (np.maximum(e - self.e_off, 0) < cost + emit_q)
            stop |= cant
            run &= ~cant
            left = np.where(start & ~cant, cost, left)
            take = np.minimum(step, left)
            ok = e - take >= self.e_off
            e = np.where(run, np.where(ok, e - take, self.e_off), e)
            dead = run & ~ok
            alive &= ~dead
            events.update((int(r), (LOST, 0)) for r in w[dead])
            run &= ok
            spent += np.where(run, take, 0)
            left = np.where(run, left - take, left)
            step = np.where(run, step - take, step)
            done = done + (run & (left <= 0))
            run &= (step > 0) & (done < target)

        # emission of a finished or stopped request
        fin = alive & ((done >= target) | stop)
        ok = e - emit_q >= self.e_off
        e = np.where(fin, np.where(ok, e - emit_q, self.e_off), e)
        sent = fin & ok
        events.update((int(r), (LOST, 0)) for r in w[fin & ~ok])
        events.update((int(r), (EMIT, int(k)))
                      for r, k in zip(w[sent], done[sent]))
        d["on"][w] = alive & ~(fin & ~ok)
        d["has_work"][w] = alive & ~fin
        d["e_work"][w] += spent + np.where(sent, emit_q, 0)
        d["emit_count"][w] += sent
        d["emit_units_sum"][w] += np.where(sent, done, 0)
        d["w_units_done"][w] = done
        d["w_left"][w] = left
        E[w] = e
        d["v"] = E
        return events

    # -- the serve ---------------------------------------------------------

    def tick(self, counts, i: int) -> None:
        if np.any(counts):
            self.admit(counts, i)
        dispatch = i % self.every == 0
        if dispatch:
            self.shed(i)
            self.dispatch(i)
        self.collect(self.device_tick(i), i)
        if dispatch:
            self.evict(i)

    def serve(self, rows: np.ndarray, n_ticks: int,
              chunk_ticks: int) -> list[dict]:
        """Serves ticks ``[0, n_ticks)`` with arrival rows ``rows``;
        returns one record per chunk of ``chunk_ticks`` ticks."""
        records = []
        for i0 in range(0, n_ticks, chunk_ticks):
            k = min(chunk_ticks, n_ticks - i0)
            before = self.snapshot()
            for i in range(i0, i0 + k):
                self.tick(rows[i], i)
            records.append(self.record(before, self.snapshot(), i0, k))
        return records

    # -- what is compared --------------------------------------------------

    def snapshot(self) -> dict:
        return dict(self.count, lat_hist=list(self.lat_hist))

    def record(self, a: dict, b: dict, i0: int, k: int) -> dict:
        """A chunk's record: its counters, throughput, mean latency and
        latency quantiles from the histogram of its completions (the
        centre of the bin in which the quantile falls)."""
        hist = [y - x for x, y in zip(a["lat_hist"], b["lat_hist"])]
        done = b["completed"] - a["completed"]
        lat_ticks = b["lat_sum"] - a["lat_sum"]
        rec = {"tick0": i0, "ticks": k,
               "throughput_rps": done / (k * self.dt),
               "mean_latency_s": lat_ticks * self.dt / done if done else 0.0}
        for name, qq in (("p50_s", 0.50), ("p95_s", 0.95), ("p99_s", 0.99)):
            rec[name] = self._quantile(hist, qq)
        for f in RECORD_COUNTERS:
            rec[f] = b[f] - a[f]
        return rec

    def _quantile(self, hist: list, qq: float) -> float:
        total = sum(hist)
        if total == 0:
            return 0.0
        rank, seen = max(qq * total, np.finfo(np.float64).tiny), 0
        for b, c in enumerate(hist):
            seen += c
            if seen >= rank:
                return (b + 0.5) * self.lat_max / self.bins
        return (len(hist) - 0.5) * self.lat_max / self.bins

    def state(self) -> dict:
        """Everything the window leaves behind, by name: the device state,
        the queues (arrival seconds and retries, front first), each
        worker's assignment and the counters."""
        ft = self.ft
        n, B = self.n, self.B
        out = {f"fleet.{f}": v.copy() for f, v in self.dev.items()}
        for f in ZERO_FIELDS:
            out[f"fleet.{f}"] = np.zeros(n, np.int64)
        for w, q in enumerate(self.queues):
            out[f"queue[{w}].t"] = np.array(
                [self.time(a) for a, _ in q], np.float64)
            out[f"queue[{w}].retries"] = np.array([r for _, r in q],
                                                  np.int64)
        f_arr = np.zeros((n, B))
        f_retry = np.zeros((n, B), np.int64)
        for r, reqs in enumerate(self.f_req):
            for j, (a, tries) in enumerate(reqs):
                f_arr[r, j] = self.time(a)
                f_retry[r, j] = tries
        out.update({
            "sched.f_n": self.f_n.copy(),
            "sched.f_wl": np.array(self.f_wl, np.int64),
            "sched.f_units": np.array(self.f_units, np.int64),
            "sched.f_t0": np.array([self.time(x) for x in self.f_t0],
                                   np.float64),
            "sched.f_arr": f_arr, "sched.f_retry": f_retry,
            "sched.completed_wl": np.array(self.completed_wl, np.int64),
            "sched.units_wl": np.array(self.units_wl, np.int64),
            "sched.acc_wl": np.array([float(ft(x)) for x in self.acc_wl]),
            "sched.meas_wl": np.array(self.meas_wl, np.int64),
            "sched.joules_nj_wl": np.array(self.nj_wl, np.int64),
            "sched.lat_hist": np.array(self.lat_hist, np.int64),
            "sched.batch_hist": np.array(self.batch_hist, np.int64),
            "sched.rebalanced": np.zeros((), np.int64)})
        for f in COUNTERS:
            out[f"sched.{f}"] = np.array(self.count[f], np.int64)
        return out
