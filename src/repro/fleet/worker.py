"""Vectorized intermittent worker pool: N devices advance in lockstep.

Array-based (struct-of-arrays) reformulation of the *approximate* mode of
``repro.core.intermittent.IntermittentExecutor.step``: every piece of
per-device state (capacitor voltage, on/off, in-flight work, counters)
is a length-N array (``repro.fleet.state.FleetState``) and one ``step(i)``
call advances all N workers by one trace tick with no per-worker Python
loop. The per-tick transition itself lives in pluggable backends:

- ``backend="numpy"`` (default): ``repro.fleet.backend_numpy``, the
  in-place reference that mirrors the scalar executor expression-for-
  expression, so a 1-worker pool reproduces the scalar results exactly
  (pinned by tests/test_fleet.py).
- ``backend="jax"``: ``repro.fleet.backend_jax``, the same transition as
  a single ``jax.lax.scan`` over the whole trace, built for
  >=100k-worker fleets in one accelerator launch: :meth:`step_macro` in
  local mode, :meth:`run_serve` (with the scheduler in the scan) in
  dispatch mode. Counts agree exactly with the NumPy reference (pinned
  by tests/test_fleet_backends.py); per-result ``results[w]`` records
  are a NumPy-backend-only feature — the JAX path reports the aggregate
  emission counters instead.

Two request modes:

- ``local``: each worker samples its own sensor every
  ``sampling_period_s`` and runs the configured Policy — the independent-
  workers baseline, and the mode the scalar-agreement test uses.
- ``dispatch``: workers are idle until a scheduler assigns them a request
  (or a batch of requests) via :meth:`assign`; emissions and losses are
  reported as events the NumPy host serve consumes via
  :meth:`pop_events` (the JAX serve scan consumes them in-scan).

Heterogeneous fleets: pass per-worker ``capacitance_f`` / ``v_max``
arrays to mix capacitor sizes across the fleet (both backends support it;
scalars fall back to the homogeneous ``cap`` configuration).

Persistence plane (``persist={"none","ckpt","undolog"}``): the default
approximate runtime has no NVM state machine (``e_nvm`` is structurally
zero), matching the paper's thesis. The two exact disciplines vectorize
the measured baselines — ``ckpt`` (Mementos-style voltage-triggered
image checkpoints) and ``undolog`` (Alpaca-style task-granular commits)
— as the same array-native tick with joule-charged FRAM draws, so the
5-7x approximate-vs-exact gap is measured inside one engine
(docs/persistence_plane.md).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

from repro.core.budget import CostTable
from repro.core import energy
from repro.core.energy import Capacitor, EnergyTrace, McuEnergyModel
from repro.core.intermittent import EmittedResult
from repro.core.policies import Policy
from repro.fleet import backend_numpy
from repro.fleet.backend_numpy import EMIT, LOST  # re-export (scheduler)
from repro.fleet.state import (STATE_FIELDS, FleetParams, FleetState,
                               init_state, stack_cost_tables)

__all__ = ["EMIT", "LOST", "FleetWorkerPool", "PoolStats", "stack_traces"]

BACKENDS = ("numpy", "jax")
# device-tick numerics/implementation (see repro.fleet.backend_jax):
# float64 XLA scan, quantized int32 XLA scan, or the fused Pallas
# serve-tick megakernel (repro.kernels.serve_tick)
KERNEL_MODES = ("xla", "q32", "pallas")


def stack_traces(traces: Sequence[EnergyTrace]) -> np.ndarray:
    """Stack equal-grid traces into the (R, T) power matrix the pool eats."""
    dt = traces[0].dt
    T = traces[0].power_w.shape[0]
    for tr in traces:
        # isclose, not ==: resampled traces carry representable-but-unequal
        # dt (e.g. 600/60000 vs 0.01) that share the grid for all purposes
        if not math.isclose(tr.dt, dt, rel_tol=1e-9, abs_tol=0.0) \
                or tr.power_w.shape[0] != T:
            raise ValueError("all traces must share dt and length")
    return np.stack([tr.power_w for tr in traces]).astype(np.float64)


@dataclasses.dataclass
class PoolStats:
    """Fleet-level aggregation of the per-worker state arrays."""

    n_workers: int
    emitted: int
    acquired: int
    skipped: int
    power_cycles: int
    energy_harvested_j: float
    energy_on_work_j: float
    energy_on_nvm_j: float  # 0.0 for approximate; FRAM joules under persist
    energy_on_sleep_j: float  # idem (sleep draws are below trace resolution)
    duration_s: float

    @property
    def throughput_per_min(self) -> float:
        return 60.0 * self.emitted / max(self.duration_s, 1e-9)


class FleetWorkerPool:
    """N harvest-powered approximate-intermittent devices in lockstep.

    ``power_w`` is an (R, T) matrix of harvested power in W on a ``dt``
    grid; ``trace_index`` maps each worker to a row (workers may share
    rows — with distinct ``phase`` offsets they decorrelate cheaply
    instead of costing R=N trace syntheses).
    """

    def __init__(self, power_w: np.ndarray, dt: float, *,
                 workloads: Sequence[CostTable],
                 n_workers: int | None = None,
                 trace_index: np.ndarray | None = None,
                 phase: np.ndarray | None = None,
                 mode: str = "local",
                 policy: Policy | None = None,
                 accuracy_table: np.ndarray | None = None,
                 sampling_period_s: float = 10.0,
                 mcu: McuEnergyModel | None = None,
                 cap: Capacitor | None = None,
                 capacitance_f: np.ndarray | float | None = None,
                 v_max: np.ndarray | float | None = None,
                 active_power_w: np.ndarray | float | None = None,
                 backend: str = "numpy",
                 kernel: str = "xla",
                 fleet_placement: str = "mesh",
                 persist: str = "none",
                 interpret: bool = False):
        if mode not in ("local", "dispatch"):
            raise ValueError(f"unknown pool mode {mode!r}")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"choose from {BACKENDS}")
        if kernel not in KERNEL_MODES:
            raise ValueError(f"unknown kernel {kernel!r}; "
                             f"choose from {KERNEL_MODES}")
        if kernel != "xla" and mode != "dispatch":
            raise ValueError(
                "quantized kernels (q32/pallas) implement the dispatch "
                "serve tick only; local mode stays float64")
        from repro.persist import PERSIST_MODES, persist_tables
        if persist not in PERSIST_MODES:
            raise ValueError(f"unknown persist mode {persist!r}; "
                             f"choose from {PERSIST_MODES}")
        if persist != "none" and mode != "dispatch":
            raise ValueError(
                "--persist ckpt/undolog are exact serve disciplines; "
                "they require the dispatch mode (local mode is the "
                "approximate independent-workers baseline)")
        if persist != "none" and kernel == "pallas":
            raise ValueError(
                "--persist ckpt/undolog supports the xla and q32 kernels; "
                "the Pallas serve megakernel implements the approximate "
                "tick only")
        power = np.asarray(power_w, dtype=np.float64)
        if power.ndim != 2:
            raise ValueError("power_w must be (n_traces, T)")
        T = power.shape[1]
        n = int(n_workers if n_workers is not None else power.shape[0])
        if mode == "local" and (policy is None or accuracy_table is None
                                or len(workloads) != 1):
            raise ValueError("local mode needs exactly one workload table, "
                             "a policy and an accuracy table")
        cap = cap or Capacitor()
        C = np.broadcast_to(np.asarray(
            cap.capacitance_f if capacitance_f is None else capacitance_f,
            dtype=np.float64), (n,)).copy()
        vmax = np.broadcast_to(np.asarray(
            cap.v_max if v_max is None else v_max,
            dtype=np.float64), (n,)).copy()
        UC, FIX, EMITC, NU = stack_cost_tables(workloads)
        self.mcu = mcu or McuEnergyModel()
        CKPT_J, REST_J, COMMIT_J = persist_tables(persist, NU, self.mcu)
        # per-worker active draw: MCU-class mixing (heterogeneous fleets);
        # a scalar broadcasts to the homogeneous reference device
        AP = np.broadcast_to(np.asarray(
            self.mcu.active_power_w if active_power_w is None
            else active_power_w, dtype=np.float64), (n,)).copy()
        self.params = FleetParams(
            dt=float(dt), n=n, T=T, mode=mode, power=power,
            trace_index=(np.arange(n) % power.shape[0]
                         if trace_index is None
                         else np.asarray(trace_index, dtype=np.int64)),
            phase=(None if phase is None
                   else np.asarray(phase, dtype=np.int64) % T),
            C=C, v_max=vmax, v_on=float(cap.v_on), v_off=float(cap.v_off),
            eff=float(cap.booster_eff),
            active_power_w=AP,
            UC=UC, FIX=FIX, EMITC=EMITC, NU=NU, tables=tuple(workloads),
            P=float(sampling_period_s), policy=policy,
            acc=accuracy_table,
            quantum_j=(None if kernel == "xla"
                       else energy.DEFAULT_QUANTUM_J),
            persist=persist, CKPT_J=CKPT_J, REST_J=REST_J,
            COMMIT_J=COMMIT_J)
        self.state = init_state(n, quantized=kernel != "xla")
        self.backend = backend
        self.kernel = kernel
        # sharded-serve evaluation: "mesh" (shard_map over a real fleet
        # mesh; raises without K devices) or "single" (one-device vmap,
        # only when asked) — placements are bit-identical, see backend_jax
        self.fleet_placement = fleet_placement
        # Pallas kernels interpret only when asked (CPU tests)
        self.interpret = interpret
        self._jax = None  # lazily-built JaxFleetBackend
        self.results: list[list[EmittedResult]] = [[] for _ in range(n)]
        self.events: list[tuple] = []
        self.steps_done = 0

    def __getattr__(self, name: str):
        # legacy attribute surface: state arrays (pool.v, pool.on, ...) and
        # params fields (pool.dt, pool.mode, pool.v_on, ...) read through
        d = object.__getattribute__(self, "__dict__")
        for holder in ("state", "params"):
            obj = d.get(holder)
            if obj is not None and hasattr(obj, name):
                return getattr(obj, name)
        raise AttributeError(
            f"{type(self).__name__!s} has no attribute {name!r}")

    def __setattr__(self, name: str, value) -> None:
        # keep legacy whole-array assignment working: `pool.v = arr` must
        # rebind the state field the backends read, not shadow it
        d = self.__dict__
        if name in STATE_FIELDS and d.get("state") is not None:
            setattr(d["state"], name, value)
            return
        params = d.get("params")
        if params is not None and name not in d and hasattr(params, name):
            raise AttributeError(
                f"{name!r} is a frozen fleet parameter; build a new pool "
                "to change it")
        object.__setattr__(self, name, value)

    def reset(self) -> None:
        """Fresh per-worker state (discharged capacitors, zero counters);
        params, backend, and any compiled scan functions are kept — a
        reset + run re-executes the trace without re-tracing."""
        self.state = init_state(self.params.n,
                                quantized=self.kernel != "xla")
        self.results = [[] for _ in range(self.params.n)]
        self.events = []
        self.steps_done = 0

    @property
    def emitted_count(self) -> int:
        return int(self.state.emit_count.sum())

    @property
    def n_wl(self) -> int:
        return len(self.params.tables)

    # -- capacitor bank ------------------------------------------------------

    def usable_energy(self) -> np.ndarray:
        return backend_numpy.usable_energy(self.params, self.state)

    # -- dispatch-mode API ---------------------------------------------------

    def dispatchable(self) -> np.ndarray:
        """Workers the scheduler may assign to: on, idle, nothing pending."""
        s = self.state
        return s.on & ~s.has_work & ~s.p_pending

    def assign(self, workers: np.ndarray, tickets: np.ndarray,
               workload: np.ndarray, req_units: np.ndarray,
               batch: np.ndarray, t: float) -> None:
        """Queue an assignment; the worker acquires it on its next tick."""
        s = self.state
        s.p_pending[workers] = True
        s.p_ticket[workers] = tickets
        s.p_wl[workers] = workload
        s.p_units[workers] = req_units
        s.p_batch[workers] = batch
        s.p_t_assigned[workers] = t

    def evict(self, workers: np.ndarray) -> list[int]:
        """Revoke pending/in-flight assignments (scheduler deadline pass).
        Work is volatile, so eviction simply drops it; returns tickets."""
        s = self.state
        tickets = []
        for w in np.atleast_1d(workers):
            if s.p_pending[w]:
                tickets.append(int(s.p_ticket[w]))
                s.p_pending[w] = False
            elif s.has_work[w]:
                tickets.append(int(s.w_ticket[w]))
                s.has_work[w] = False
        return tickets

    def pop_events(self) -> list[tuple]:
        ev, self.events = self.events, []
        return ev

    # -- lockstep stepping ---------------------------------------------------

    def step(self, i: int) -> None:
        """Advance all N workers by one dt (trace index ``i``) through the
        NumPy reference transition (single-tick stepping is host-side by
        definition)."""
        backend_numpy.tick(self.params, self.state, i, self.results,
                           self.events)
        self.steps_done = i + 1

    def step_macro(self, i0: int, n_ticks: int) -> None:
        """Advance ``n_ticks`` ticks starting at trace index ``i0``: a
        JAX local-mode pool runs them as one ``lax.scan`` launch; the
        NumPy backend loops :meth:`step`. A JAX dispatch-mode pool
        advances only with its scheduler, through :meth:`run_serve`."""
        if self.backend == "numpy":
            for i in range(i0, i0 + n_ticks):
                self.step(i)
            return
        self.state = self._jax_backend().run(self.state, i0, n_ticks)
        self.steps_done = i0 + n_ticks

    def _jax_backend(self):
        """The lazily-built ``JaxFleetBackend`` of a JAX pool."""
        if self._jax is None:
            from repro.fleet.backend_jax import JaxFleetBackend
            self._jax = JaxFleetBackend(
                self.params, kernel=self.kernel,
                fleet_placement=self.fleet_placement,
                interpret=self.interpret)
        return self._jax

    def run_serve(self, sched, arrivals: np.ndarray, *,
                  dispatch_every: int = 10, obs=None,
                  chunk: int = 0) -> None:
        """Fused serve: device physics AND the array-native scheduler as
        one ``lax.scan`` launch (JAX backend only; the NumPy reference
        drives the same control-plane expressions tick by tick in
        ``repro.fleet.scheduler``'s host window). ``sched`` is a
        ``FleetScheduler``; its state is advanced in place. ``obs`` (a
        ``repro.obs.FleetObs``) rides the scan carry and is updated in
        place — the serve results are bit-identical with or without it.
        ``chunk`` tags the launch's host spans (the stream's chunk
        index)."""
        if self.backend != "jax":
            raise ValueError("run_serve is the fused jax path; numpy "
                             "pools serve through run_fleet's host window")
        self.state, sched.state = self._jax_backend().run_serve(
            self.state, sched.params, sched.state, arrivals,
            i0=self.steps_done, dispatch_every=dispatch_every, obs=obs,
            chunk=chunk)
        self.steps_done += int(np.asarray(arrivals).shape[0])

    # -- driving + accounting ------------------------------------------------

    def run(self, n_steps: int | None = None) -> PoolStats:
        n_steps = self.params.T if n_steps is None else n_steps
        self.step_macro(0, n_steps)
        return self.stats()

    def stats(self) -> PoolStats:
        s = self.state
        # quantized pools account energy in integer quanta; convert the
        # accumulators back to joules at the reporting boundary
        q = self.params.quantum_j
        e_scale = 1.0 if q is None else q
        return PoolStats(
            n_workers=self.params.n,
            emitted=self.emitted_count,
            acquired=int(s.acquired.sum()),
            skipped=int(s.skipped.sum()),
            power_cycles=int(s.cycles.sum()),
            energy_harvested_j=float(s.e_harvest.sum()) * e_scale,
            energy_on_work_j=float(s.e_work.sum()) * e_scale,
            energy_on_nvm_j=float(np.asarray(s.e_persist).sum()) * e_scale,
            energy_on_sleep_j=0.0,
            duration_s=self.steps_done * self.params.dt)
