"""Struct-of-arrays fleet state: the contract between worker backends.

``FleetParams`` is everything static about a fleet run (trace bank, stacked
workload tables, capacitor bank constants, policy) and ``FleetState`` is
everything a tick mutates — one length-N array per field. The per-tick
transition (harvest -> brown-out/boot -> acquire -> progress -> emit) is a
pure function of ``(params, state)``; backends only differ in *how* they
evaluate it:

- ``repro.fleet.backend_numpy`` — the in-place NumPy reference, pinned
  bit-exact against the scalar ``core.intermittent`` executor at N=1;
- ``repro.fleet.backend_jax`` — the same expressions as one
  ``jax.lax.scan`` over the whole trace (float64 via ``enable_x64``), so
  the two backends agree on emitted/skipped/power-cycle counts exactly.

Capacitor constants ``C``/``v_max`` are per-worker arrays (heterogeneous
fleets mix capacitor sizes); the turn-on/brown-out thresholds stay fleet
scalars (one MCU supervisor class per fleet).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.core.budget import CostTable
from repro.core.policies import Policy


@dataclasses.dataclass(frozen=True)
class FleetParams:
    """Static per-run configuration shared by every backend."""

    dt: float
    n: int  # workers
    T: int  # trace length (ticks)
    mode: str  # "local" | "dispatch"
    power: np.ndarray  # (R, T) harvested power, W
    trace_index: np.ndarray  # (N,) worker -> trace row
    phase: np.ndarray | None  # (N,) tick offset into the row, or None
    # capacitor bank (per-worker C/v_max: heterogeneous fleets)
    C: np.ndarray  # (N,) farads
    v_max: np.ndarray  # (N,)
    v_on: float
    v_off: float
    eff: float  # booster efficiency
    active_power_w: np.ndarray  # (N,) MCU active draw (MCU-class mixing)
    # stacked workload tables: (W, U_max) unit costs padded with +inf
    UC: np.ndarray
    FIX: np.ndarray  # (W,)
    EMITC: np.ndarray  # (W,)
    NU: np.ndarray  # (W,) int64
    tables: tuple[CostTable, ...]
    # local mode only
    P: float  # sampling period, s
    policy: Policy | None
    acc: np.ndarray | None  # (n_units + 1,) accuracy table
    # quantized serve-tick contract (kernel="q32"/"pallas"): energies are
    # int32 quanta of this many joules and FleetState.v holds stored
    # energy E = 0.5 C v^2 in quanta instead of volts. None = float64.
    quantum_j: float | None = None
    # persistence plane (repro.persist): execution discipline per fleet.
    # "none" is the approximate single-power-cycle tick; "ckpt" and
    # "undolog" are the exact-equivalence baselines where a request
    # survives power failure and completes at full unit count. The (W,)
    # joule tables below are built by repro.persist.persist_tables from
    # the MCU FRAM per-byte energies; None whenever persist == "none".
    persist: str = "none"
    CKPT_J: np.ndarray | None = None  # (W,) checkpoint image write, J
    REST_J: np.ndarray | None = None  # (W,) restore read on wake, J
    COMMIT_J: np.ndarray | None = None  # (W,) per-unit undo-log commit, J


@dataclasses.dataclass
class FleetState:
    """Everything one lockstep tick reads or writes; all fields (N,)."""

    # capacitor + lifecycle
    v: np.ndarray
    on: np.ndarray
    cycles: np.ndarray
    acquired: np.ndarray
    skipped: np.ndarray
    e_work: np.ndarray
    e_harvest: np.ndarray
    # local-mode sampling
    next_sample_t: np.ndarray
    sample_counter: np.ndarray
    # in-flight work (volatile by design)
    has_work: np.ndarray
    w_ticket: np.ndarray
    w_t_acq: np.ndarray
    w_cycle_acq: np.ndarray
    w_units_done: np.ndarray
    w_left: np.ndarray
    w_target: np.ndarray  # total units to run
    w_tile: np.ndarray  # per-request units; 0 = absolute target
    w_wl: np.ndarray
    w_batch: np.ndarray
    # dispatch-mode pending assignment (not yet acquired)
    p_pending: np.ndarray
    p_ticket: np.ndarray
    p_wl: np.ndarray
    p_units: np.ndarray
    p_batch: np.ndarray
    p_t_assigned: np.ndarray
    # emission aggregates (backend-independent accounting: the JAX backend
    # returns no per-result records, only these counters)
    emit_count: np.ndarray
    emit_units_sum: np.ndarray
    emit_acc_sum: np.ndarray
    # persistence plane (persist != "none"): a brown-out mid-request sets
    # need_restore and the worker pays REST_J on its next productive wake
    # before continuing. ck_units is the checkpointed progress counter
    # (ckpt: restored on wake; undolog: unused — w_units_done itself is
    # the durable per-unit commit counter). e_persist is the FRAM joule
    # ledger; persists/restores count checkpoint-or-commit writes and
    # restore reads. All structurally zero when persist == "none".
    need_restore: np.ndarray
    ck_units: np.ndarray
    e_persist: np.ndarray
    persists: np.ndarray
    restores: np.ndarray


STATE_FIELDS: tuple[str, ...] = tuple(
    f.name for f in dataclasses.fields(FleetState))


def init_state(n: int, *, quantized: bool = False) -> FleetState:
    """Fresh device state for ``n`` workers: discharged capacitors (0 V),
    everything off/idle, all counters zero. Returns a :class:`FleetState`
    of (N,) arrays.

    The state is dtype-parametric. ``quantized=False`` (the default) is
    the float64 contract: ``v`` in volts, energies in joules, times in
    seconds. ``quantized=True`` is the int32 contract the serve-tick
    megakernel runs (``repro.fleet.qtick``): ``v`` holds stored energy
    ``E = 0.5 C v^2`` in integer quanta of ``FleetParams.quantum_j``,
    ``e_work``/``e_harvest``/``w_left`` are quanta, and the acquisition
    timestamps ``w_t_acq``/``p_t_assigned`` are integer tick indices.
    Both precisions flow through ``backend_numpy``/``backend_jax``
    unchanged — same fields, same transition, different dtypes."""
    e_dt = np.int32 if quantized else np.float64  # energies
    c_dt = np.int32 if quantized else np.int64  # counters / ids
    t_dt = np.int32 if quantized else np.float64  # acquisition times
    z = lambda dt=np.float64: np.zeros(n, dtype=dt)  # noqa: E731
    return FleetState(
        v=z(e_dt), on=z(bool), cycles=z(c_dt), acquired=z(c_dt),
        skipped=z(c_dt), e_work=z(e_dt), e_harvest=z(e_dt),
        next_sample_t=z(), sample_counter=z(np.int64),
        has_work=z(bool), w_ticket=z(c_dt), w_t_acq=z(t_dt),
        w_cycle_acq=z(c_dt), w_units_done=z(c_dt), w_left=z(e_dt),
        w_target=z(c_dt), w_tile=z(c_dt), w_wl=z(c_dt),
        w_batch=np.ones(n, dtype=c_dt),
        p_pending=z(bool), p_ticket=z(c_dt), p_wl=z(c_dt),
        p_units=z(c_dt), p_batch=np.ones(n, dtype=c_dt),
        p_t_assigned=z(t_dt),
        emit_count=z(c_dt), emit_units_sum=z(c_dt),
        emit_acc_sum=z(),
        need_restore=z(bool), ck_units=z(c_dt), e_persist=z(e_dt),
        persists=z(c_dt), restores=z(c_dt))


def state_as_tuple(s: FleetState) -> tuple:
    """Field-ordered flat tuple of the state arrays (``STATE_FIELDS``
    order) — the pytree form the JAX scan carries."""
    return tuple(getattr(s, f) for f in STATE_FIELDS)


def state_from_tuple(t: Sequence) -> FleetState:
    """Inverse of :func:`state_as_tuple`."""
    return FleetState(**dict(zip(STATE_FIELDS, t)))


# ---------------------------------------------------------------------------
# Scheduler control plane (array-native: repro.fleet.sched)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SchedParams:
    """Static control-plane configuration: everything the array-native
    scheduler step (``repro.fleet.sched``) reads but never writes. Pure
    NumPy constants; the JAX backend converts them on use (``xp.asarray``
    inside the shared ops, baked into the trace as constants).

    Units: every cost/energy table is in joules, power in watts, times in
    seconds, windows/lookaheads in ticks of ``dt`` seconds."""

    n: int  # workers
    W: int  # workloads
    Q: int  # queue ring capacity per workload (requests)
    B: int  # max batch per assignment (requests)
    max_queue: int  # global admission bound (queued requests)
    max_retries: int  # retries granted before a request counts as lost
    shed_after_s: float  # queue-age shedding threshold, seconds
    grace_s: float  # straggler grace period, seconds
    deadline_factor: float  # straggler deadline = grace + factor * est
    dt: float  # tick length, seconds
    # stacked workload tables, padded with +inf beyond each table's units
    CU: np.ndarray  # (W, U+2) CostTable.cumulative, J (incl fixed+emit)
    UCUM: np.ndarray  # (W, U+2) unit-cost prefix, J (excl fixed/emit)
    FIX: np.ndarray  # (W,) fixed acquisition cost, J
    EMITC: np.ndarray  # (W,) emission (BLE packet) cost, J
    NU: np.ndarray  # (W,) int64 unit counts
    FULL: np.ndarray  # (W,) cost of all units, J (straggler estimate)
    ACC: np.ndarray  # (W, U+1) expected-accuracy tables (dimensionless)
    P_REQ: np.ndarray  # (W,) SMART floor units (huge sentinel: see
    # sched._BIG -> the floor is unattainable and admission always skips)
    IS_SMART: np.ndarray  # (W,) bool; False -> greedy admission
    # forecast routing: the compiled pluggable forecaster
    # (repro.core.forecast), gathered per worker
    forecast: bool  # False -> reactive (instantaneous-charge) planning
    lookahead_ticks: int  # forecast window L, ticks
    forecaster: str  # selection mode ("ou"/"occlusion"/"burst"/"arp"/"auto")
    fc_order: int  # lag window P the planners gather (ticks of history)
    FC_MU: np.ndarray  # (N,) affine forecast base, W (0 for regime rows)
    FC_W: np.ndarray  # (N, P) window-mean deviation weights (dimensionless)
    FC_THRESH: np.ndarray  # (N,) regime threshold on current power, W
    FC_HI: np.ndarray  # (N,) regime forecast addend (p_now >= THRESH), W
    FC_LO: np.ndarray  # (N,) regime forecast addend (p_now < THRESH), W
    FC_MODEL: np.ndarray  # (N,) int8 forecast.MODEL_CODES per worker
    ECAP: np.ndarray  # (N,) storable usable-energy ceiling, J
    ACTIVE_P: np.ndarray  # (N,) per-worker MCU active power, W
    # latency histogram (fused-scan-friendly percentile estimates)
    lat_bins: int  # histogram bins
    lat_max_s: float  # histogram range, seconds
    # quality plane (repro.quality): per-sample oracle tables the ledger
    # gathers at completion time. QTAB rows beyond a workload's S_Q are
    # padding; sample ids cycle mod S_Q. Costs are quantized to integer
    # nanojoules so the ledger counters stay bit-exact across backends.
    quality: str  # table provenance: "proxy" | "measured"
    value_order: bool  # sched="quality": serve queues by WL_RANK, not age
    S_Q: np.ndarray  # (W,) int64 oracle samples per workload
    QTAB: np.ndarray  # (W, S_max, U+1) int64 0/1 per-sample correctness
    QJ_NJ: np.ndarray  # (W, U+1) int64 nanojoules per completed request
    QVALUE: np.ndarray  # (W,) marginal accuracy-per-joule at the admission
    # knob (dimensionless per joule; the sched="quality" rank key)
    WL_RANK: np.ndarray  # (W,) int64 queue service order by QVALUE desc
    QTARGET: np.ndarray  # (W,) int64 smallest knob reaching max measured
    # accuracy (sched="quality" sizes batches so each request affords it)
    # hierarchical sharded control plane (--mesh-fleet K): the worker axis
    # splits into `shards` contiguous blocks of n/shards workers, each
    # running an independent control plane over a max_queue/shards
    # admission slice. The defaults keep the single-plane behavior; the
    # per-shard view of these params is sched.shard_sched_params.
    shards: int = 1
    rebalance_every: int = 0  # cross-shard work-stealing cadence, ticks
    # (0 = off; must be a positive multiple of dispatch_every when on)
    rebalance_max: int = 8  # max requests moved per workload per event
    # forecaster fit provenance: "full" fits on the whole (R, T) bank at
    # construction (the historical offline behavior — it peeks at future
    # harvest), "causal" starts from the zero-inflow prior and refits
    # from only the observed prefix (FleetScheduler.refit_forecast /
    # the streaming loop; see docs/streaming_serve.md)
    forecaster_fit: str = "full"
    # persistence plane (docs/persistence_plane.md): the execution
    # discipline the dispatcher sizes work for. Exact disciplines pin the
    # knob at NU (every unit runs) and admission only requires the
    # fixed+emit overhead funded now — the persisted request survives
    # power failure and spans recharge cycles. The FRAM per-byte energies
    # price the checkpoint/commit/restore tables (repro.persist).
    persist: str = "none"  # "none" | "ckpt" | "undolog"
    fram_write_j_per_byte: float = 18e-9
    fram_read_j_per_byte: float = 7e-9
    # quantized fleets (FleetParams.quantum_j set): dispatch decides on
    # the integer usable quanta ``us``, each table entry being the
    # smallest ``us`` at which its float64 comparison turns true
    # (sched.quanta_thresholds; INT32_MAX: never). None for float fleets.
    THR_AFF: np.ndarray | None = None  # (W, U+2) int32 knob k affordable
    THR_B: np.ndarray | None = None  # (W, U+2, B) int32 batch b+1 at knob j
    THR_U: np.ndarray | None = None  # (W, B, U+2) int32 knob k per request
    # of a batch of a+1


@dataclasses.dataclass
class SchedState:
    """Everything one scheduler tick reads or writes — queue ring-buffers,
    per-worker in-flight assignments, and aggregate accounting. All
    counters are arrays (0-d for scalars) so the state threads through a
    ``lax.scan`` carry unchanged."""

    # per-workload FIFO ring buffers (front = oldest; retries re-enter at
    # the front with their original arrival time)
    q_t: np.ndarray  # (W, Q) arrival times
    q_r: np.ndarray  # (W, Q) retry counts
    q_head: np.ndarray  # (W,) physical index of the logical front
    q_len: np.ndarray  # (W,)
    # per-worker in-flight assignment (mirrors the device's pending/work)
    f_n: np.ndarray  # (N,) requests in flight; 0 = none
    f_wl: np.ndarray  # (N,)
    f_units: np.ndarray  # (N,) per-request knob units
    f_t0: np.ndarray  # (N,) assignment time
    f_arr: np.ndarray  # (N, B) request arrival times
    f_retry: np.ndarray  # (N, B) request retry counts
    # aggregate accounting (0-d / small arrays; the fused scan returns no
    # per-request records, exactly like the worker backends' counters)
    submitted: np.ndarray
    rejected: np.ndarray
    shed: np.ndarray
    lost: np.ndarray
    evicted: np.ndarray
    requeued: np.ndarray
    completed: np.ndarray
    completed_wl: np.ndarray  # (W,)
    units_wl: np.ndarray  # (W,)
    acc_wl: np.ndarray  # (W,)
    lat_sum: np.ndarray  # int64 completed requests' latency, in ticks
    lat_hist: np.ndarray  # (lat_bins,)
    batch_hist: np.ndarray  # (B+1,) assignments by batch size
    # quality ledger (repro.quality.ledger): measured-correct completions
    # and table-priced spend, both integer so backends agree bit-exactly
    meas_wl: np.ndarray  # (W,) int64 oracle-correct completed requests
    joules_nj_wl: np.ndarray  # (W,) int64 nanojoules spent on completions
    # sharded control plane: queued requests received from the ring
    # predecessor by the cross-shard rebalance step (0 when shards == 1
    # or rebalance is off)
    rebalanced: np.ndarray


SCHED_FIELDS: tuple[str, ...] = tuple(
    f.name for f in dataclasses.fields(SchedState))


def init_sched_state(sp: SchedParams) -> SchedState:
    """Empty control-plane state sized for ``sp``: empty ring buffers,
    no in-flight assignments, all counters zero. Arrival times are
    seconds; retry counts and all counters are int64."""
    i = lambda *s: np.zeros(s, dtype=np.int64)  # noqa: E731
    f = lambda *s: np.zeros(s, dtype=np.float64)  # noqa: E731
    return SchedState(
        q_t=f(sp.W, sp.Q), q_r=i(sp.W, sp.Q), q_head=i(sp.W),
        q_len=i(sp.W),
        f_n=i(sp.n), f_wl=i(sp.n), f_units=i(sp.n), f_t0=f(sp.n),
        f_arr=f(sp.n, sp.B), f_retry=i(sp.n, sp.B),
        submitted=i(), rejected=i(), shed=i(), lost=i(), evicted=i(),
        requeued=i(), completed=i(),
        completed_wl=i(sp.W), units_wl=i(sp.W), acc_wl=f(sp.W),
        lat_sum=i(), lat_hist=i(sp.lat_bins), batch_hist=i(sp.B + 1),
        meas_wl=i(sp.W), joules_nj_wl=i(sp.W), rebalanced=i())


def sched_state_as_tuple(s: SchedState) -> tuple:
    """Field-ordered flat tuple (``SCHED_FIELDS`` order) — the pytree
    form the fused serve scan carries alongside the device state."""
    return tuple(getattr(s, f) for f in SCHED_FIELDS)


def sched_state_from_tuple(t: Sequence) -> SchedState:
    """Inverse of :func:`sched_state_as_tuple`."""
    return SchedState(**dict(zip(SCHED_FIELDS, t)))


def stack_cost_tables(workloads: Sequence[CostTable]
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """Stack per-workload :class:`CostTable` columns into (W, U_max)
    arrays. Returns ``(UC, FIX, EMITC, NU)``: per-unit costs (J), fixed
    acquisition cost (J), emission cost (J), and unit counts (int64).
    Per-worker gathers make the progression loop workload-heterogeneous
    without Python branching; unit slots beyond a table's length are
    +inf (never affordable, never started)."""
    u_max = max(c.n_units for c in workloads)
    UC = np.full((len(workloads), u_max), np.inf)
    for w, c in enumerate(workloads):
        UC[w, :c.n_units] = c.unit_costs
    FIX = np.array([c.fixed_cost for c in workloads])
    EMITC = np.array([c.emit_cost for c in workloads])
    NU = np.array([c.n_units for c in workloads], dtype=np.int64)
    return UC, FIX, EMITC, NU
