"""Forecast-aware fleet dispatcher: a thin host frontend over the
array-native control plane (``repro.fleet.sched``).

The scheduler owns the global request stream and answers, every dispatch
tick, the fleet version of the paper's per-sample question: *which worker
should run this request, at which knob setting, so the result is emitted
within the worker's current power cycle?* Since PR 3 the answer is
computed by pure struct-of-arrays ops (queue ring-buffers, cumulative-sum
batching, stable-sort routing) instead of a per-request Python object
loop, so the same expressions run in two modes, both driven chunk by
chunk by :func:`run_fleet_stream` (:func:`run_fleet` is its one-chunk
form):

- ``backend="numpy"`` pools: the host window (:class:`_HostServe`)
  evaluates the array ops tick by tick on the host, for one shard or K —
  the bit-exact reference cadence;
- ``backend="jax"`` pools: each chunk is one ``backend_jax.run_serve``
  launch — workers **and** scheduler fused into a single ``lax.scan``
  with no per-tick host round-trips.

Routing is *forecast-aware* (``sched="forecast"``): workers are ranked —
and batches sized — by the conditional expectation of usable energy over
the next ``lookahead_s`` window instead of instantaneous charge, under a
*pluggable* harvest forecaster (``repro.core.forecast``): the closed-form
OU mean reversion, the occlusion/burst regime models, a learned AR(p)
fit, or per-row automatic selection (``forecaster="auto"``, matched to
each row's trace family). ``sched="reactive"`` is the PR-1 behavior.
"""
from __future__ import annotations

import collections

import numpy as np

from repro.fleet import backend_numpy, sched as _sched
from repro.fleet.metrics import _hist_percentile, sched_summary
from repro.fleet.state import (STATE_FIELDS, sched_state_as_tuple,
                               sched_state_from_tuple)
from repro.fleet.worker import EMIT, FleetWorkerPool
from repro.fleet.workloads import FleetWorkload
from repro.runtime.straggler import StragglerPolicy


class RequestStream:
    """Deterministic Poisson arrivals with a workload mix."""

    def __init__(self, rate_rps: float, mix: np.ndarray, n_steps: int,
                 dt: float, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.counts = rng.poisson(rate_rps * dt, size=n_steps)
        total = int(self.counts.sum())
        mix = np.asarray(mix, dtype=np.float64)
        self.wl = rng.choice(mix.shape[0], size=total, p=mix / mix.sum())

    def counts_matrix(self, n_workloads: int) -> np.ndarray:
        """(n_steps, W) per-tick arrival counts — the array-native form
        the fused serve scan consumes as its ``lax.scan`` input."""
        n_steps = self.counts.shape[0]
        out = np.zeros((n_steps, n_workloads), dtype=np.int64)
        step = np.repeat(np.arange(n_steps), self.counts)
        np.add.at(out, (step, self.wl), 1)
        return out


class FleetScheduler:
    """Host handle over (``SchedParams``, ``SchedState``) for one pool.

    Construction compiles the workload tables into stacked arrays and
    fits the per-trace-row harvest forecaster. The two serve paths
    (:class:`_HostServe` on the host, ``backend_jax.run_serve`` on the
    device) advance ``state`` with the shared control-plane expressions
    of ``repro.fleet.sched``.
    """

    def __init__(self, pool: FleetWorkerPool,
                 workloads: list[FleetWorkload], *,
                 max_queue: int = 4096,
                 shed_after_s: float = 30.0,
                 max_batch: int = 4,
                 max_retries: int = 2,
                 grace_s: float = 20.0,
                 straggler: StragglerPolicy | None = None,
                 sched: str = "reactive",
                 lookahead_s: float = 5.0,
                 forecaster: str = "ou",
                 trace_families: list[str] | None = None,
                 arp_order: int = 3,
                 forecaster_fit: str = "full",
                 lat_bins: int = 64,
                 shards: int = 1,
                 rebalance_every: int = 0,
                 rebalance_max: int = 8):
        if pool.mode != "dispatch":
            raise ValueError("scheduler needs a dispatch-mode pool")
        self.pool = pool
        self.workloads = workloads
        straggler = straggler or StragglerPolicy()
        self.params = _sched.make_sched_params(
            pool.params, workloads, max_queue=max_queue,
            shed_after_s=shed_after_s, max_batch=max_batch,
            max_retries=max_retries, grace_s=grace_s,
            deadline_factor=straggler.deadline_factor, sched=sched,
            lookahead_s=lookahead_s, forecaster=forecaster,
            trace_families=trace_families, arp_order=arp_order,
            forecaster_fit=forecaster_fit,
            lat_bins=lat_bins, shards=shards,
            rebalance_every=rebalance_every,
            rebalance_max=rebalance_max,
            persist=pool.params.persist,
            fram_write_j_per_byte=pool.mcu.fram_write_j_per_byte,
            fram_read_j_per_byte=pool.mcu.fram_read_j_per_byte)
        self.state = _sched.make_sched_state(self.params)
        # causal refit machinery: windowed sufficient statistics over the
        # observed harvest prefix (repro.core.forecast.CausalFitState),
        # refreshed by refit_forecast at streaming chunk boundaries
        self.fit_state = None
        self.observed_ticks = 0
        if forecaster_fit == "causal" and sched == "forecast":
            from repro.core.forecast import CausalFitState
            self.fit_state = CausalFitState(
                forecaster, pool.params.power.shape[0],
                arp_order=arp_order, families=trace_families)

    # -- state plumbing ------------------------------------------------------

    def _ss(self) -> _sched.SS:
        return _sched.SS(*sched_state_as_tuple(self.state))

    def _store(self, ss) -> None:
        self.state = sched_state_from_tuple(tuple(ss))

    @property
    def backlog(self) -> int:
        """Requests currently queued (all workloads)."""
        return int(self.state.q_len.sum())

    @property
    def inflight_count(self) -> int:
        """Requests currently assigned to (pending or running on) workers."""
        return int(self.state.f_n.sum())

    def refit_forecast(self, upto_tick: int) -> bool:
        """Causal refit: absorb harvest columns ``[observed, upto_tick)``
        into the sufficient statistics and swap the compiled forecast
        tables in ``self.params`` for a fit on exactly that prefix.

        Prefix-only by construction — samples at trace tick
        ``>= upto_tick`` are never read (pinned by the future-mutation
        test in tests/test_streaming.py). The replacement keeps every
        non-``FC_*`` field identical (``sched_params_compatible``), so
        the fused scan's compiled functions stay valid and the new
        tables flow in as runtime arguments. Returns True iff the
        tables changed (i.e. the scheduler was built with
        ``forecaster_fit="causal"`` and ``sched="forecast"``)."""
        if self.fit_state is None:
            return False
        import dataclasses
        p = self.pool.params
        upto = min(int(upto_tick), p.T)
        if upto > self.observed_ticks:
            self.fit_state.update(p.power[:, self.observed_ticks:upto])
            self.observed_ticks = upto
        rf = self.fit_state.compile(
            self.params.lookahead_ticks).take(p.trace_index)
        self.params = dataclasses.replace(
            self.params, FC_MU=rf.MU, FC_W=rf.W, FC_THRESH=rf.THRESH,
            FC_HI=rf.HI, FC_LO=rf.LO, FC_MODEL=rf.model)
        return True

    def summary(self, duration_s: float) -> dict:
        # merged_sched_view sums sharded (K, ...) accounting fields over
        # the shard axis (identity for the unsharded state)
        return sched_summary(self.params,
                             _sched.merged_sched_view(self.state),
                             duration_s, self.pool,
                             [w.name for w in self.workloads])


def run_fleet(pool: FleetWorkerPool, sched: FleetScheduler,
              stream: RequestStream, n_steps: int, *,
              dispatch_every: int = 10, obs=None) -> dict:
    """Drive arrivals -> control plane -> device physics -> collection
    over ``n_steps`` ticks: :func:`run_fleet_stream` as one chunk, with
    its ``"stream"`` block removed from the summary.

    With a JAX pool that chunk is one fused ``backend_jax.run_serve``
    launch over the whole trace; with a NumPy pool it is one
    :class:`_HostServe` window, the per-tick reference. Both evaluate the
    same control-plane expressions and agree exactly on all discrete
    counts. ``obs`` (a ``repro.obs.FleetObs``, or None) instruments the
    run without perturbing its results.
    """
    summary = run_fleet_stream(pool, sched, stream, n_steps,
                               chunk_ticks=n_steps,
                               dispatch_every=dispatch_every, obs=obs)
    del summary["stream"]
    return summary


class _HostServe:
    """The NumPy reference serve, the one host serve loop of a NumPy pool:
    :meth:`window` serves a chunk of ticks, mutating pool and scheduler
    state in place, so the streaming loop carries full state across
    chunk boundaries. It is the reference the fused scan (and, for K > 1,
    the traced ``shard_map``/``vmap`` path) is gated against bit for bit.

    The control plane loops over ``K = sched.params.shards`` contiguous
    worker slices. At K = 1 the one slice is the whole fleet under
    ``sched.params`` itself (the unsharded ring, the unstacked state) and
    no rebalance runs. At K > 1 (``--mesh-fleet K``) each shard gets
    its own admission (the deterministic ``split_counts`` arrival split:
    elementwise, so splitting each chunk equals slicing the full split),
    shed/plan/dispatch/collect/evict against its params view, and the
    all-integer work-stealing exchange via
    :func:`repro.fleet.sched.rebalance_host`; the per-shard scheduler
    states are restacked into the (K, ...) form the fused scan uses. The
    device physics stays full-fleet: the tick is embarrassingly parallel
    over workers, so one ``pool.step`` per tick is bit-identical to K
    shard-local ticks.

    With ``obs``, every tick evaluates the shared
    :func:`repro.obs.telemetry.obs_tick` per shard. Each window's shard
    telemetry starts at zero and is summed into ``obs.tele`` at its end:
    every channel is an int64 scatter-add, so the sums equal the
    whole-trace counters. The event ring (``--obs trace``, K = 1 only)
    is carried in place.
    """

    def __init__(self, pool: FleetWorkerPool, sched: FleetScheduler,
                 dispatch_every: int, obs):
        sp = sched.params
        self.K = sp.shards
        if self.K > 1 and sp.rebalance_every % dispatch_every:
            raise ValueError(
                f"rebalance_every={sp.rebalance_every} ticks must be a "
                f"positive multiple of dispatch_every={dispatch_every}:"
                " the work-stealing exchange runs inside the dispatch "
                "pass")
        if self.K > 1 and obs is not None and obs.op.mode != "tele":
            raise ValueError(
                "--obs trace keeps a global per-worker event ring and "
                "is not supported under --mesh-fleet > 1; use --obs "
                "tele (windowed counters reduce exactly across shards)")
        self.pool = pool
        self.sched = sched
        self.dispatch_every = dispatch_every
        self.obs = obs
        ns = pool.params.n // self.K
        self.sls = [slice(s * ns, (s + 1) * ns) for s in range(self.K)]

    def window(self, counts: np.ndarray, i0: int) -> None:
        """Serve ticks ``[i0, i0 + counts.shape[0])`` with per-tick
        arrival counts ``counts`` ((k, W) int64). The tick index is
        global, so harvest columns, dispatch/evict phase and shed
        deadlines are chunk-invariant."""
        pool, sched, obs = self.pool, self.sched, self.obs
        K, sls = self.K, self.sls
        sp = sched.params  # re-read: causal refits swap the FC_* tables
        p = pool.params
        dev = pool.state
        if K == 1:
            sps = [sp]
            sss = [sched._ss()]
        else:
            sps = [_sched.shard_sched_params(sp, s) for s in range(K)]
            sss = [_sched.SS(*(np.asarray(getattr(sched.state, f))[s]
                               for f in _sched.SCHED_FIELDS))
                   for s in range(K)]
        split = _sched.split_counts(np.asarray(counts, np.int64), K)
        if obs is not None:
            from repro.obs import telemetry as O
            from repro.obs.state import (init_tele, ring_as_tuple,
                                         ring_from_tuple, tele_as_tuple,
                                         tele_from_tuple)
            base = tele_as_tuple(init_tele(obs.op))
            teles = [tuple(np.zeros_like(np.asarray(x)) for x in base)
                     for _ in range(K)]
            ring = None if obs.ring is None else ring_as_tuple(obs.ring)
        for j in range(split.shape[1]):
            i = i0 + j
            t = i * p.dt
            is_tick = i % self.dispatch_every == 0
            if obs is not None:
                b = O.dev_snap(dev, copy=True)
                sbs = [O.sched_snap(ss, np) for ss in sss]
                assign = np.zeros(p.n, dtype=bool)
                assign_wl = np.zeros(p.n, dtype=np.int64)
            for s in range(K):
                sss[s] = _sched.admit(sps[s], sss[s], split[s, j], t, np)
            if is_tick:
                budget_now = backend_numpy.usable_energy(p, dev)
                us = backend_numpy.usable_quanta(p, dev)
                plans = []
                for s, sl in enumerate(sls):
                    sss[s] = _sched.shed(sps[s], sss[s], t, np)
                    pw_lags = _sched.power_lags(
                        p.power, p.trace_index[sl], i, p.T, sp.fc_order,
                        phase=None if p.phase is None else p.phase[sl],
                        xp=np)
                    plans.append(_sched.plan_budget(
                        sps[s], budget_now[sl], pw_lags, p.eff, np))
                if K > 1 and sp.rebalance_every and (
                        i % sp.rebalance_every == 0):
                    sss = _sched.rebalance_host(sps, sss, plans)
                dispatchable = dev.on & ~dev.has_work & ~dev.p_pending
                mask = np.zeros(p.n, dtype=bool)
                wl = np.zeros(p.n, dtype=np.int64)
                units = np.zeros(p.n, dtype=np.int64)
                batch = np.zeros(p.n, dtype=np.int64)
                for s, sl in enumerate(sls):
                    sss[s], a = _sched.dispatch(
                        sps[s], sss[s], dispatchable[sl], budget_now[sl],
                        plans[s], t, np,
                        us=None if us is None else us[sl])
                    mask[sl] = a.mask
                    wl[sl] = a.wl
                    units[sl] = a.units
                    batch[sl] = a.batch
                # the one full-width write round of the assignments
                dev.p_pending = dev.p_pending | mask
                dev.p_wl = np.where(mask, wl, dev.p_wl)
                dev.p_units = np.where(mask, units, dev.p_units)
                dev.p_batch = np.where(mask, np.maximum(batch, 1),
                                       dev.p_batch)
                dev.p_t_assigned = np.where(mask, float(t),
                                            dev.p_t_assigned)
                if obs is not None:
                    assign = dev.p_pending & ~b.p_pending
                    assign_wl = dev.p_wl.copy()
            pool.step(i)
            if obs is not None:
                # who holds an assignment before the evict pass
                held = dev.p_pending | dev.has_work
            emit = np.zeros(p.n, dtype=bool)
            lost = np.zeros(p.n, dtype=bool)
            done_units = np.zeros(p.n, dtype=np.int64)
            for ev in pool.pop_events():
                w = int(ev[2])
                if ev[0] == EMIT:
                    emit[w] = True
                    done_units[w] = int(ev[4])
                else:
                    lost[w] = True
            for s, sl in enumerate(sls):
                sss[s] = _sched.collect(sps[s], sss[s], emit[sl],
                                        lost[sl], done_units[sl], t, np)
            if is_tick:
                evm = np.zeros(p.n, dtype=bool)
                for s, sl in enumerate(sls):
                    sss[s], evm[sl] = _sched.evict(sps[s], sss[s], t, np)
                dev.p_pending = dev.p_pending & ~evm
                dev.has_work = dev.has_work & ~evm
            if obs is not None:
                col = ((i % p.T) if p.phase is None
                       else (i + p.phase) % p.T)
                pw = p.power[p.trace_index, col]
                evict_mask = held & ~(dev.p_pending | dev.has_work)
                for s, sl in enumerate(sls):
                    teles[s], ring = O.obs_tick(
                        obs.op, sps[s], teles[s], ring, i=i, j=i,
                        is_tick=is_tick, pw=pw[sl], eff=p.eff, dt=p.dt,
                        b=O.DevSnap(*(x[sl] for x in b)), sb=sbs[s],
                        assign_mask=assign[sl], assign_wl=assign_wl[sl],
                        evict_mask=evict_mask[sl],
                        fs=_slice_state(dev, sl), ss=sss[s],
                        power=p.power, cs=obs.cs,
                        trace_index=p.trace_index[sl],
                        phase=None if p.phase is None else p.phase[sl],
                        T=p.T, xp=np)
        if K == 1:
            sched._store(sss[0])
        else:
            sched.state = sched_state_from_tuple(tuple(
                np.stack([np.asarray(getattr(ss, f)) for ss in sss])
                for f in _sched.SCHED_FIELDS))
        if obs is not None:
            obs.tele = tele_from_tuple(tuple(
                np.asarray(o) + sum(np.asarray(tl[k]) for tl in teles)
                for k, o in enumerate(tele_as_tuple(obs.tele))))
            if ring is not None:
                obs.ring = ring_from_tuple(ring)


_FS = collections.namedtuple("_FS", STATE_FIELDS)


def _slice_state(s, sl: slice) -> _FS:
    """One shard's view of the (N,) struct-of-arrays device state."""
    return _FS(*(getattr(s, f)[sl] for f in STATE_FIELDS))


class StreamClient:
    """Live request generator: a background producer thread feeds
    per-tick ``(W,)`` arrival-count rows into a bounded queue, and the
    serve loop's :meth:`take` blocks for the next chunk — the MaxText
    offline-inference pattern of a host-side arrival queue decoupling
    request generation from the compiled serve launches.

    Rows come from the same deterministic ``RequestStream`` counts
    matrix the offline path consumes, in order, so a streamed run is
    row-for-row identical to the offline arrivals — that determinism is
    what lets the differential suite pin chunked == whole-trace
    bit-equality through the live client too.
    """

    def __init__(self, stream: RequestStream, n_workloads: int,
                 n_steps: int | None = None, max_buffer: int = 4096):
        import queue
        import threading
        counts = stream.counts_matrix(n_workloads)
        if n_steps is not None:
            counts = counts[:n_steps]
        self.n_steps = counts.shape[0]
        self.n_workloads = int(n_workloads)
        self._q = queue.Queue(maxsize=max_buffer)
        self._thread = threading.Thread(
            target=self._feed, args=(counts,), daemon=True)
        self._thread.start()

    def _feed(self, counts: np.ndarray) -> None:
        for row in counts:
            self._q.put(row)

    def take(self, k: int) -> np.ndarray:
        """Block until the next ``k`` arrival rows are available and
        return them stacked as a (k, W) int64 matrix."""
        return np.stack([self._q.get() for _ in range(k)]).astype(
            np.int64)


_CHUNK_COUNTERS = ("submitted", "completed", "shed", "rejected",
                   "lost", "evicted", "requeued", "lat_sum")


def _chunk_snapshot(state) -> dict:
    v = _sched.merged_sched_view(state)
    snap = {f: int(getattr(v, f)) for f in _CHUNK_COUNTERS}
    snap["lat_hist"] = np.asarray(v.lat_hist).copy()
    return snap


def run_fleet_stream(pool: FleetWorkerPool, sched: FleetScheduler,
                     source, n_steps: int, *, chunk_ticks: int,
                     dispatch_every: int = 10, refit_every: int = 0,
                     obs=None, slo_p95_s: float = 0.0) -> dict:
    """Streaming online serve: the chunked steady-state loop.

    Scans a fixed window of ``chunk_ticks`` ticks per launch, carrying
    the full (FleetState, SchedState, TeleState) across chunk
    boundaries, and injects host-submitted arrivals between chunks —
    ``source`` is either a live :class:`StreamClient` (its ``take``
    blocks on the producer thread) or an offline :class:`RequestStream`
    (rows sliced from the counts matrix). The final, possibly shorter,
    chunk covers the trace remainder, so ``chunk_ticks`` need not
    divide ``n_steps``.

    With a JAX pool each chunk is one fused ``run_serve`` launch
    (``i0 = pool.steps_done`` keeps harvest columns and obs indices
    global); equal-size chunks reuse a single compiled function, and a
    causal refit between chunks swaps only the runtime ``FC_*``
    tables — no re-trace. With a NumPy pool each chunk is one
    :class:`_HostServe` window, the per-tick reference for one or K
    shards. When the arrival rows are identical and ``refit_every`` is
    0, the chunked run is **bit-exact** with the one-chunk run
    (:func:`run_fleet`) on every summary field — the differential suite
    in tests/test_streaming.py pins this.

    ``refit_every`` (ticks; 0 = off) triggers
    :meth:`FleetScheduler.refit_forecast` at the first chunk boundary
    at least that many ticks after the previous refit — the causal,
    prefix-only re-estimation of the forecaster tables from the harvest
    actually observed so far.

    The returned summary carries a ``"stream"`` block: per-chunk
    latency/throughput records (p50/p95/p99 from the latency histogram
    delta), refit count, and — when ``slo_p95_s`` > 0 — a per-chunk
    p95 SLO verdict and total violation count. Wall-clock fields are
    nondeterministic; equality checks strip the block.

    Host spans (``repro.obs.profile``; each with the stat ``chunk``, the
    chunk's index): ``fleet.stream.chunk`` around each chunk (a
    ``jax.profiler.StepTraceAnnotation``, ``step_num`` the index),
    holding ``fleet.stream.take``, two ``fleet.stream.snapshot``, the
    launch's ``fleet.serve.*`` spans, ``fleet.stream.record`` and, when a
    refit runs, ``fleet.stream.refit``.
    """
    import time

    import jax

    from repro.obs.profile import span
    if chunk_ticks <= 0:
        raise ValueError(f"chunk_ticks={chunk_ticks} must be positive")
    dt = pool.dt
    sp = sched.params
    host = (None if pool.backend == "jax"
            else _HostServe(pool, sched, dispatch_every, obs))
    counts_all = None
    if not hasattr(source, "take"):
        counts_all = source.counts_matrix(sp.W)[:n_steps]
    chunks = []
    done = 0
    last_refit = 0
    refits = 0
    violations = 0
    while done < n_steps:
        c = len(chunks)
        with jax.profiler.StepTraceAnnotation("fleet.stream.chunk",
                                              step_num=c, chunk=c):
            k = min(int(chunk_ticks), n_steps - done)
            with span("fleet.stream.take", chunk=c):
                counts = (source.take(k) if counts_all is None
                          else counts_all[done:done + k])
            with span("fleet.stream.snapshot", chunk=c):
                before = _chunk_snapshot(sched.state)
            t0 = time.perf_counter()
            if host is None:
                pool.run_serve(sched, counts,
                               dispatch_every=dispatch_every, obs=obs,
                               chunk=c)
            else:
                host.window(counts, done)
            wall = time.perf_counter() - t0
            with span("fleet.stream.snapshot", chunk=c):
                after = _chunk_snapshot(sched.state)
            with span("fleet.stream.record", chunk=c):
                hist = after["lat_hist"] - before["lat_hist"]
                completed = after["completed"] - before["completed"]
                lat_sum = after["lat_sum"] - before["lat_sum"]
                rec = {"tick0": done, "ticks": k,
                       "wall_s": wall,
                       "throughput_rps": completed / (k * dt),
                       "mean_latency_s": (lat_sum * dt / completed
                                          if completed else 0.0),
                       "p50_s": _hist_percentile(hist, sp.lat_max_s, 0.50),
                       "p95_s": _hist_percentile(hist, sp.lat_max_s, 0.95),
                       "p99_s": _hist_percentile(hist, sp.lat_max_s,
                                                 0.99)}
                for f in _CHUNK_COUNTERS:
                    if f != "lat_sum":
                        rec[f] = after[f] - before[f]
                if slo_p95_s > 0.0:
                    rec["slo_ok"] = bool(rec["p95_s"] <= slo_p95_s)
                    violations += not rec["slo_ok"]
                chunks.append(rec)
            done += k
            if (refit_every and done < n_steps
                    and done - last_refit >= refit_every):
                with span("fleet.stream.refit", chunk=c):
                    if sched.refit_forecast(done):
                        refits += 1
                last_refit = done
    summary = sched.summary(n_steps * dt)
    summary["stream"] = {"chunk_ticks": int(chunk_ticks),
                         "refit_every": int(refit_every),
                         "refits": refits,
                         "n_chunks": len(chunks),
                         "chunks": chunks}
    if slo_p95_s > 0.0:
        summary["stream"]["slo_p95_s"] = float(slo_p95_s)
        summary["stream"]["slo_violations"] = violations
    return summary
