"""JAX fleet backend: the whole trace as one ``lax.scan`` device launch.

The per-tick transition of ``repro.fleet.backend_numpy`` re-expressed as a
pure function over the struct-of-arrays ``FleetState`` — harvest, wake,
acquire, progress, emit are the *same float64 expressions* (shared via the
stateless capacitor helpers in ``core.energy`` and the ``xp=jnp`` policy
closed forms), evaluated as a batched whole-array step: each masked
``jnp.where`` lane is exactly what a ``jax.vmap`` of the scalar device
step would compute, with the data-dependent unit loop as a fleet-wide
``lax.while_loop`` that retires lanes as their dt budget drains. A run of
``n_ticks`` is a single ``lax.scan`` over that step — 100k+ workers fit
one accelerator launch instead of 100k Python-object updates per tick.

Numerical contract: under ``jax.enable_x64(True)`` every operation runs
in IEEE double on the CPU, like the NumPy reference. XLA:CPU contracts
multiply-add chains into FMAs, so capacitor *voltages* can drift from
NumPy by ~1 ulp; every discrete outcome — emitted / skipped / acquired /
power-cycle counts, drawn energies, emission times — agrees exactly on
shared traces because threshold comparisons sit ulps away from the knife
edge with probability ~1e-13 per event (tests/test_fleet_backends.py pins
count equality). XLA:TPU emulates float64 and does not round as IEEE
does, so on the chip that agreement is measured (``chip_smoke.py``), not
promised; the int32 ``q32``/``pallas`` ticks do not depend on it.

``run`` is that scan for local-mode fleets. A dispatch-mode fleet
serves through ``run_serve``: the array-native control plane
(``repro.fleet.sched``) is traced *into* the scan — admission and event
collection every tick, shed/dispatch/evict under a ``lax.cond`` at the
dispatch cadence — so an entire serve trace (workers AND scheduler) is a
single compiled launch. Each tick's events are fixed-capacity (N,)
arrays — code / time / ticket / units per worker — that the in-scan
collect consumes the same tick; they never reach the host. Capacity
one per worker per tick is an invariant, not a truncation: a worker's
assignment can terminate (emit or loss) at most once per tick.

``kernel`` selects the device-tick numerics/implementation:

- ``"xla"`` (default) — the float64 jnp expression chain above;
- ``"q32"`` — the int32 quantized tick (``repro.fleet.qtick``) traced
  as pure XLA: same scan, integer energy quanta, no sqrt;
- ``"pallas"`` — the same quantized tick fused into one VMEM-resident
  Pallas pass per tick (``repro.kernels.serve_tick``), compiled for the
  TPU, or interpret-mode (still pure XLA, still bit-exact vs ``q32``)
  when the caller passes ``interpret=True``, as the CPU tests do.
  Quantized kernels are dispatch-mode only and need a quantized
  ``FleetState`` (``init_state(n, quantized=True)``) plus
  ``FleetParams.quantum_j`` — ``FleetWorkerPool(kernel=...)`` wires all
  three.
"""
from __future__ import annotations

import collections
import copy
import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.energy import (capacitor_draw, capacitor_harvest,
                               capacitor_usable_energy,
                               capacitor_usable_q)
from repro.fleet.state import (STATE_FIELDS, FleetParams, FleetState,
                               SchedParams, SchedState,
                               sched_state_as_tuple,
                               sched_state_from_tuple, state_as_tuple,
                               state_from_tuple)
from repro.obs.profile import span

_S = collections.namedtuple("_S", STATE_FIELDS)

# event codes in the fixed-capacity array log
EV_NONE, EV_EMIT, EV_LOST = 0, 1, 2


def _nbytes(tree) -> int:
    """Bytes held by the arrays of ``tree`` (host or device; reads
    shapes only, never waits on the device)."""
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


class JaxFleetBackend:
    """Compiled scan runner for one ``FleetParams`` configuration."""

    def __init__(self, params: FleetParams, *, kernel: str = "xla",
                 fleet_placement: str = "mesh", interpret: bool = False):
        self.p = params
        self.kernel = kernel
        self.fleet_placement = fleet_placement
        # Pallas kernels run interpreted only when the caller asks (CPU
        # tests); compiled otherwise, so a host without a TPU fails loudly
        self.interpret = interpret
        if kernel not in ("xla", "q32", "pallas"):
            raise ValueError(f"unknown kernel {kernel!r}")
        if fleet_placement not in ("mesh", "single"):
            raise ValueError(
                f"unknown fleet_placement {fleet_placement!r} "
                "(mesh | single)")
        if kernel != "xla":
            if params.mode != "dispatch":
                raise ValueError(
                    "quantized kernels (q32/pallas) implement the serve "
                    "tick only; local mode stays float64")
            if params.quantum_j is None:
                raise ValueError(
                    "quantized kernels need FleetParams.quantum_j (use "
                    "FleetWorkerPool(kernel=...) to wire params + state)")
        if kernel == "pallas" and params.persist != "none":
            raise ValueError(
                "--persist ckpt/undolog supports the xla and q32 "
                "kernels; the Pallas serve megakernel implements the "
                "approximate tick only")
        if params.mode == "local":
            # surface non-traceable policies at build time, not mid-scan:
            # the base-class decide_batch is the NumPy-only loop fallback,
            # and an override without an `xp` parameter is a pre-xp custom
            # policy that would die with an opaque error inside tracing
            import inspect

            from repro.core.policies import Policy
            impl = type(params.policy).decide_batch
            if (impl is Policy.decide_batch
                    or "xp" not in inspect.signature(impl).parameters):
                raise TypeError(
                    f"policy {type(params.policy).__name__}'s decide_batch "
                    "cannot run under jax tracing; the jax backend needs "
                    "an xp-aware closed form (see core.policies)")
        with jax.enable_x64(True):
            self.power = jnp.asarray(params.power)
            self.trace_index = jnp.asarray(params.trace_index)
            self.phase = (None if params.phase is None
                          else jnp.asarray(params.phase))
            self.C = jnp.asarray(params.C)
            self.v_max = jnp.asarray(params.v_max)
            self.UC = jnp.asarray(params.UC)
            self.FIX = jnp.asarray(params.FIX)
            self.EMITC = jnp.asarray(params.EMITC)
            self.NU = jnp.asarray(params.NU)
            self.AP = jnp.asarray(params.active_power_w)
            zw = np.zeros(np.asarray(params.FIX).shape[0])
            self.CKPT_J = jnp.asarray(params.CKPT_J
                                      if params.CKPT_J is not None else zw)
            self.REST_J = jnp.asarray(params.REST_J
                                      if params.REST_J is not None else zw)
            self.COMMIT_J = jnp.asarray(
                params.COMMIT_J if params.COMMIT_J is not None else zw)
            self.ACC = (None if params.acc is None
                        else jnp.asarray(np.asarray(params.acc,
                                                    dtype=np.float64)))
            if kernel != "xla":
                from repro.fleet import qtick as Q
                qp_np = Q.quantize_fleet_cached(params)
                self._qp_host = qp_np
                self._qp = Q.convert_arrays(qp_np, jnp.asarray)
                if kernel == "pallas":
                    from repro.kernels.serve_tick import replicate_table
                    pad8 = lambda k: -(-k // 8) * 8  # noqa: E731
                    w, u = qp_np.UCQ.shape
                    self._k_tables = dict(
                        uc=replicate_table(qp_np.UCQ.reshape(-1),
                                           pad8(w * u)),
                        fix=replicate_table(qp_np.FIXQ, pad8(w)),
                        emitc=replicate_table(qp_np.EMITCQ, pad8(w)))
        self._compiled: dict[int, callable] = {}
        self._serve_compiled: dict[tuple, callable] = {}
        self._serve_builds = 0  # serve programs built (fleet.serve.compile)
        self._serve_sp: SchedParams | None = None
        self._pow_cs = None  # lazy shared power prefix-sum (obs)

    # -- public API ----------------------------------------------------------

    def run(self, state: FleetState, i0: int,
            n_ticks: int) -> FleetState:
        """Advance a local-mode fleet ``n_ticks`` from trace index ``i0``
        as one float64 ``lax.scan``; returns the updated host-side state.
        A dispatch-mode fleet advances only with its scheduler, through
        :meth:`run_serve`."""
        if self.p.mode != "local":
            raise ValueError(
                "a dispatch-mode fleet serves through run_serve "
                "(run_fleet / run_fleet_stream), with its scheduler in "
                "the scan")
        with jax.enable_x64(True):
            st = tuple(jnp.asarray(x) for x in state_as_tuple(state))
            fn = self._compiled.get(n_ticks)
            if fn is None:
                fn = self._build(n_ticks)
                self._compiled[n_ticks] = fn
            st_out = fn(st, jnp.asarray(i0, jnp.int64))
            # np.array (copy): the host state must stay writable
            st_out = tuple(np.array(x) for x in st_out)
        return state_from_tuple(st_out)

    # -- compiled scan -------------------------------------------------------

    def _pick_tick(self):
        """The per-tick transition for this backend's kernel mode."""
        if self.kernel == "q32":
            return self._tick_q
        if self.kernel == "pallas":
            return self._tick_pallas
        return self._tick

    def _build(self, n_ticks: int):
        def scan_fn(st, i0):
            def body(st, j):
                # local mode records no events: the log is empty (None)
                return self._tick(st, None, i0 + j)[0], None

            st, _ = lax.scan(body, st,
                             jnp.arange(n_ticks, dtype=jnp.int64))
            return st

        return jax.jit(scan_fn)

    # -- fused serve scan (workers + scheduler in one launch) ---------------

    def run_serve(self, state: FleetState, sp: SchedParams,
                  sched_state: SchedState, arrivals: np.ndarray, *,
                  i0: int = 0, dispatch_every: int = 10, obs=None,
                  chunk: int = 0) -> tuple[FleetState, SchedState]:
        """The whole serve trace — device physics AND the array-native
        control plane (``repro.fleet.sched``) — as one ``lax.scan``: the
        per-tick arrival counts are the scan input, admission/collection
        run every tick, the shed/dispatch/evict passes fire under a
        ``lax.cond`` at the dispatch cadence, and only the two final
        states come back to the host. No per-tick transfers.

        ``obs`` (a ``repro.obs.FleetObs``) threads the telemetry /
        event-ring arrays through the scan carry and writes them back
        here — the serve expressions themselves are untouched (the
        zero-perturbation contract), and with ``obs=None`` the compiled
        program is byte-identical to the uninstrumented build.

        Host spans (``repro.obs.profile.span``, each with the stat
        ``chunk``): ``fleet.serve.upload`` (stat ``bytes``), then
        ``fleet.serve.call``, or ``fleet.serve.compile`` on the first
        call of a program just built (stats ``n_ticks``,
        ``dispatch_every``, ``builds``), then ``fleet.serve.readback``
        (stat ``bytes``)."""
        if self.p.mode != "dispatch":
            raise ValueError("run_serve needs a dispatch-mode fleet")
        if obs is not None and self.kernel != "xla":
            raise ValueError(
                "the observability plane reads float64 device state; "
                "quantized kernels (q32/pallas) run uninstrumented")
        if sp.shards > 1:
            return self._run_serve_sharded(
                state, sp, sched_state, arrivals, i0=i0,
                dispatch_every=int(dispatch_every), obs=obs, chunk=chunk)
        from repro.fleet import sched as S
        arrivals = np.asarray(arrivals, dtype=np.int64)
        n_ticks = arrivals.shape[0]
        op = None if obs is None else obs.op
        key = (n_ticks, int(dispatch_every), op)
        # a causal refit only rebinds the FC_* forecast tables, which
        # enter the compiled launch as runtime arguments — every other
        # change to the control-plane config forces a re-trace
        if not S.sched_params_compatible(self._serve_sp, sp):
            self._serve_compiled = {}
        self._serve_sp = sp
        host = [state_as_tuple(state), sched_state_as_tuple(sched_state)]
        if op is not None:
            from repro.obs.state import (ring_as_tuple, ring_from_tuple,
                                         tele_as_tuple, tele_from_tuple)
            host += [tele_as_tuple(obs.tele),
                     None if obs.ring is None else ring_as_tuple(obs.ring)]
        host += [self._worker_inputs(sp), arrivals, np.int64(i0)]
        with jax.enable_x64(True):
            with span("fleet.serve.upload", chunk=chunk,
                      bytes=_nbytes(host)):
                args = jax.tree.map(jnp.asarray, host)
            fn, launch = self._serve_fn(
                key, lambda: self._build_serve(sp, n_ticks,
                                               int(dispatch_every), op=op),
                chunk)
            with launch:
                out = fn(*args)
            with span("fleet.serve.readback", chunk=chunk,
                      bytes=_nbytes(out)):
                # np.array (copy): the host state must stay writable
                fs = tuple(np.array(x) for x in out[0])
                ss, *obs_out = jax.tree.map(np.asarray, out[1:])
        if op is not None:
            tele, ring = obs_out
            obs.tele = tele_from_tuple(tele)
            if ring is not None:
                obs.ring = ring_from_tuple(ring)
        return state_from_tuple(fs), sched_state_from_tuple(ss)

    def _serve_fn(self, key, build, chunk: int):
        """The serve program of ``key`` (``build()`` on a miss) and the
        host span its launch runs in: ``fleet.serve.compile`` on the
        first call of a program just built (that call traces and compiles
        it), else ``fleet.serve.call``. ``key`` leads with the chunk's
        ticks and the dispatch cadence."""
        fn = self._serve_compiled.get(key)
        if fn is not None:
            return fn, span("fleet.serve.call", chunk=chunk)
        fn = self._serve_compiled[key] = build()
        self._serve_builds += 1
        return fn, span("fleet.serve.compile", chunk=chunk,
                        n_ticks=key[0], dispatch_every=key[1],
                        builds=self._serve_builds)

    def _power_cumsum(self):
        """Shared (R, T+1) power prefix-sum, computed once in NumPy (so
        the obs forecast-error gathers read values bit-identical to the
        host driver's) and cached on device."""
        if self._pow_cs is None:
            from repro.obs.telemetry import power_cumsum
            with jax.enable_x64(True):
                self._pow_cs = jnp.asarray(
                    power_cumsum(np.asarray(self.p.power)))
        return self._pow_cs

    def _serve_body(self, view, sp: SchedParams, dispatch_every: int,
                    op=None, obs_cs=None, rebalance=None):
        """The per-tick serve transition as a ``lax.scan`` body closure.

        ``view`` carries the device-resident per-worker constants:
        ``self`` for the single-shard build, or a shard-sliced shallow
        copy under the sharded build — ``_tick``/``_tick_q`` and the
        scheduler passes then read shard-local rows with no code
        changes (replicated tables like the power matrix and cost
        tables stay closure-captured, which ``shard_map`` handles
        bit-identically to ``vmap``). ``rebalance`` (sharded builds
        only) splices the cross-shard work-stealing exchange between
        budget planning and dispatch at the ``sp.rebalance_every``
        cadence."""
        from repro.fleet import sched as S
        if op is not None:
            from repro.obs import telemetry as O
        p = view.p
        n = p.n
        tick = view._pick_tick()
        quant = self.kernel != "xla"

        def body(carry, xs):
            if op is None:
                fs, ss = carry
                i, counts = xs
            else:
                (fs, ss), (tele, ring) = carry
                i, j, counts = xs
            fs0 = _S(*fs)
            ssb = ss  # tick-start snapshot (immutable namedtuple view)
            t = i * p.dt
            with jax.named_scope("fleet.admit"):
                ss = S.admit(sp, ss, counts, t, jnp)
            is_tick = (i % dispatch_every) == 0

            def do_dispatch(args):
                fsn, ss = args
                with jax.named_scope("fleet.shed"):
                    ss = S.shed(sp, ss, t, jnp)
                with jax.named_scope("fleet.plan"):
                    if quant:
                        # dispatch decides on the usable quanta; the
                        # joules (the exact float64 expression of the
                        # NumPy host path) feed forecast planning
                        us = capacitor_usable_q(fsn.v, view._qp.E_OFF, jnp)
                        budget_now = us.astype(jnp.float64) * p.quantum_j
                    else:
                        us = None
                        budget_now = view._usable(fsn.v)
                    pw_lags = S.power_lags(view.power, view.trace_index,
                                           i, p.T, sp.fc_order,
                                           phase=view.phase, xp=jnp)
                    budget_plan = S.plan_budget(sp, budget_now, pw_lags,
                                                p.eff, jnp)
                if rebalance is not None:
                    with jax.named_scope("fleet.rebalance"):
                        ss = lax.cond((i % sp.rebalance_every) == 0,
                                      lambda s: rebalance(s, budget_plan),
                                      lambda s: s, ss)
                with jax.named_scope("fleet.dispatch"):
                    dispatchable = fsn.on & ~fsn.has_work & ~fsn.p_pending
                    ss, a = S.dispatch(sp, ss, dispatchable, budget_now,
                                       budget_plan, t, jnp, us=us)
                with jax.named_scope("fleet.assign"):
                    cast = ((lambda x: x.astype(jnp.int32)) if quant
                            else (lambda x: x))
                    fsn = fsn._replace(
                        p_pending=fsn.p_pending | a.mask,
                        p_wl=jnp.where(a.mask, cast(a.wl), fsn.p_wl),
                        p_units=jnp.where(a.mask, cast(a.units),
                                          fsn.p_units),
                        p_batch=jnp.where(a.mask,
                                          cast(jnp.maximum(a.batch, 1)),
                                          fsn.p_batch),
                        p_t_assigned=jnp.where(
                            a.mask, cast(i) if quant else t,
                            fsn.p_t_assigned))
                return fsn, ss

            fsn, ss = lax.cond(is_tick, do_dispatch, lambda x: x,
                               (fs0, ss))
            with jax.named_scope("fleet.tick"):
                if quant:
                    ev0 = tuple(jnp.zeros(n, jnp.int32) for _ in range(4))
                else:
                    ev0 = (jnp.zeros(n, jnp.int64),
                           jnp.zeros(n, jnp.float64),
                           jnp.zeros(n, jnp.int64), jnp.zeros(n, jnp.int64))
                fs2, ev = tick(tuple(fsn), ev0, i)
            with jax.named_scope("fleet.collect"):
                evc, _, _, evu = ev
                ss = S.collect(sp, ss, evc == EV_EMIT, evc == EV_LOST,
                               evu.astype(jnp.int64) if quant else evu,
                               t, jnp)

            def do_evict(args):
                fsn, ss = args
                ss, evm = S.evict(sp, ss, t, jnp)
                return fsn._replace(p_pending=fsn.p_pending & ~evm,
                                    has_work=fsn.has_work & ~evm), ss

            fs2s = _S(*fs2)
            with jax.named_scope("fleet.evict"):
                fsn2, ss = lax.cond(is_tick, do_evict, lambda x: x,
                                    (fs2s, ss))
            if op is None:
                return (tuple(fsn2), ss), None
            # observability: pure reads of the before/after snapshots
            # above — never feeds back into fs/ss (zero perturbation)
            col = ((i % p.T) if view.phase is None
                   else (i + view.phase) % p.T)
            pw = view.power[view.trace_index, col]
            tele, ring = O.obs_tick(
                op, sp, tele, ring, i=i, j=j, is_tick=is_tick, pw=pw,
                eff=p.eff, dt=p.dt, b=O.dev_snap(fs0),
                sb=O.sched_snap(ssb, jnp),
                assign_mask=fsn.p_pending & ~fs0.p_pending,
                assign_wl=fsn.p_wl,
                evict_mask=((fs2s.p_pending | fs2s.has_work)
                            & ~(fsn2.p_pending | fsn2.has_work)),
                fs=fsn2, ss=ss, power=view.power, cs=obs_cs,
                trace_index=view.trace_index, phase=view.phase, T=p.T,
                xp=jnp)
            return ((tuple(fsn2), ss), (tele, ring)), None

        return body

    def _build_serve(self, sp: SchedParams, n_ticks: int,
                     dispatch_every: int, op=None):
        from repro.fleet import sched as S
        obs_cs = (self._power_cumsum()
                  if op is not None and sp.forecast else None)

        # the per-worker tables (FC_* forecasts included) arrive as the
        # runtime `pw` dict: the streaming loop's causal refits swap the
        # forecasts between chunks without re-tracing, and (N,)-sized
        # arrays baked in as constants would cost the TPU compiler
        # minutes of constant folding. The body closure is built inside
        # the traced function so the passes read the traced tables; the
        # small workload tables stay baked constants
        def make_body(pw):
            return self._serve_body(self._view(pw, self.p.n),
                                    dataclasses.replace(sp, **pw["sp"]),
                                    dispatch_every, op=op, obs_cs=obs_cs)

        if op is None:
            def serve_fn(fs, ss, pw, arr, i0):
                xs = (i0 + jnp.arange(n_ticks, dtype=jnp.int64), arr)
                (fs, ss), _ = lax.scan(make_body(pw), (fs, S.SS(*ss)),
                                       xs)
                return fs, tuple(ss)
        else:
            def serve_fn(fs, ss, tele, ring, pw, arr, i0):
                # the obs tick index j is GLOBAL (i0 + local), matching
                # the host drivers' j=i: windowed telemetry and ring
                # timestamps stay chunk-invariant when a serve trace is
                # split across multiple launches
                idx = i0 + jnp.arange(n_ticks, dtype=jnp.int64)
                xs = (idx, idx, arr)
                ((fs, ss), (tele, ring)), _ = lax.scan(
                    make_body(pw), ((fs, S.SS(*ss)), (tele, ring)), xs)
                return fs, tuple(ss), tele, ring

        return jax.jit(serve_fn)

    # -- sharded serve scan (--mesh-fleet K: shard_map over the fleet axis) --

    def _run_serve_sharded(self, state: FleetState, sp: SchedParams,
                           sched_state: SchedState, arrivals, *, i0,
                           dispatch_every, obs, chunk: int = 0):
        """``run_serve`` for ``sp.shards == K > 1``: the worker axis is
        split into K contiguous row-shards, each with its own control
        plane (per-shard ring queues, ``max_queue // K`` admission),
        and the whole K-shard program runs as ONE logical launch —
        ``shard_map`` over a K-device ``(fleet,)`` mesh, or, only when
        ``fleet_placement="single"`` asks for it, a one-device ``vmap``
        with the same named axis. The two placements (and the NumPy
        host twin) are bit-identical: the shard split is semantic, the
        placement is not. Host spans as :meth:`run_serve`'s."""
        from repro.fleet import sched as S
        p = self.p
        K = sp.shards
        ns = p.n // K
        if self.kernel == "pallas":
            raise ValueError(
                "--mesh-fleet > 1 supports the xla and q32 kernels; the "
                "Pallas serve megakernel tiles a single-device worker "
                "axis (use --kernel q32 for sharded quantized runs)")
        if obs is not None and obs.op.mode != "tele":
            raise ValueError(
                "--obs trace keeps a global per-worker event ring and "
                "is not supported under --mesh-fleet > 1; use --obs "
                "tele (windowed counters reduce exactly across shards)")
        if sp.rebalance_every and (sp.rebalance_every % dispatch_every):
            raise ValueError(
                f"rebalance_every={sp.rebalance_every} ticks must be a "
                f"positive multiple of dispatch_every={dispatch_every}: "
                "the work-stealing exchange runs inside the dispatch "
                "pass")
        use_mesh = self.fleet_placement == "mesh"
        arrivals = np.asarray(arrivals, dtype=np.int64)
        n_ticks = arrivals.shape[0]
        arr = S.split_counts(arrivals, K)  # (K, n_ticks, W)
        op = None if obs is None else obs.op
        key = (n_ticks, int(dispatch_every), op, "sharded", use_mesh)
        # per-worker tables (FC_* included) already enter as runtime
        # inputs via sh["sp"], so a causal refit keeps the trace
        if not S.sched_params_compatible(self._serve_sp, sp):
            self._serve_compiled = {}
        self._serve_sp = sp

        def resh(x):
            a = np.asarray(x)
            return np.ascontiguousarray(a.reshape((K, ns) + a.shape[1:]))

        host = {"fs": tuple(resh(x) for x in state_as_tuple(state)),
                # the sched state is already stacked (K, ...)
                "ss": sched_state_as_tuple(sched_state), "arr": arr,
                **self._worker_inputs(sp, resh)}
        host = (host, np.int64(i0))
        with jax.enable_x64(True):
            with span("fleet.serve.upload", chunk=chunk,
                      bytes=_nbytes(host)):
                args = jax.tree.map(jnp.asarray, host)
            fn, launch = self._serve_fn(
                key, lambda: self._build_serve_sharded(
                    sp, n_ticks, int(dispatch_every), op, use_mesh),
                chunk)
            with launch:
                out = fn(*args)
            spanned = len(jax.tree.leaves(out)[0].sharding.device_set)
            if use_mesh and spanned != K:
                raise RuntimeError(
                    f"the {K}-shard mesh serve ran on {spanned} device(s)")
            with span("fleet.serve.readback", chunk=chunk,
                      bytes=_nbytes(out)):
                fs = tuple(np.array(x).reshape((K * ns,) + x.shape[2:])
                           for x in out[0])
                ss, *tele = jax.tree.map(np.asarray, out[1:])
        if op is not None:
            from repro.obs.state import tele_as_tuple, tele_from_tuple
            # per-shard windows summed over K: every channel is a
            # scatter-add, so the shard sum IS the global counter
            obs.tele = tele_from_tuple(tuple(
                np.asarray(o) + t.sum(axis=0)
                for o, t in zip(tele_as_tuple(obs.tele), tele[0])))
        return state_from_tuple(fs), sched_state_from_tuple(ss)

    def _build_serve_sharded(self, sp: SchedParams, n_ticks: int,
                             dispatch_every: int, op, use_mesh: bool):
        from jax.sharding import PartitionSpec as P

        from repro.fleet import sched as S
        from repro.sharding.context import FLEET_AXIS, make_fleet_mesh
        K = sp.shards
        ns = self.p.n // K
        obs_cs = (self._power_cumsum()
                  if op is not None and sp.forecast else None)
        if op is not None:
            from repro.obs.state import init_tele, tele_as_tuple
            tele_tmpl = [(x.shape, x.dtype)
                         for x in tele_as_tuple(init_tele(op))]

        def per_shard(sh, i0):
            # the shard view: same backend methods, per-worker constants
            # swapped for this shard's contiguous rows
            view = self._view(sh, ns)
            sps = S.shard_sched_params(sp, per_worker=sh["sp"])

            rebalance = None
            if sp.rebalance_every:
                fwd = [(s, (s + 1) % K) for s in range(K)]
                bwd = [((s + 1) % K, s) for s in range(K)]

                def rebalance(ss, budget_plan):
                    # forecast-weighted surplus exchange around the
                    # shard ring (docs/sharded_fleet.md): all-integer,
                    # so the NumPy twin (rebalance_host) is bit-equal
                    cap = S.rebalance_capacity(budget_plan, jnp)
                    backlog = jnp.sum(ss.q_len)
                    b_tot = lax.psum(backlog, FLEET_AXIS)
                    c_tot = lax.psum(cap, FLEET_AXIS)
                    surplus, deficit = S.rebalance_targets(
                        backlog, cap, b_tot, c_tot, jnp)
                    give = jnp.minimum(
                        surplus, lax.ppermute(deficit, FLEET_AXIS, bwd))
                    move = S.rebalance_moves(sps, ss.q_len, give, jnp)
                    ss, bt, br = S.queue_pop_tail(sps, ss, move, jnp)
                    got = [lax.ppermute(x, FLEET_AXIS, fwd)
                           for x in (move, bt, br)]
                    return S.queue_push_tail(sps, ss, *got, xp=jnp)

            body = self._serve_body(view, sps, dispatch_every, op=op,
                                    obs_cs=obs_cs, rebalance=rebalance)
            fs, ss, arr = sh["fs"], sh["ss"], sh["arr"]
            idx = jnp.arange(n_ticks, dtype=jnp.int64)
            if op is None:
                (fs, ss), _ = lax.scan(body, (fs, S.SS(*ss)),
                                       (i0 + idx, arr))
                return fs, tuple(ss)
            tele = tuple(jnp.zeros(s, d) for s, d in tele_tmpl)
            # global obs index j = i0 + local, matching the host twin
            ((fs, ss), (tele, _)), _ = lax.scan(
                body, ((fs, S.SS(*ss)), (tele, None)),
                (i0 + idx, i0 + idx, arr))
            return fs, tuple(ss), tele

        if use_mesh:
            mesh = make_fleet_mesh(K)

            def shard_fn(sh, i0):
                out = per_shard(jax.tree.map(lambda x: x[0], sh), i0)
                return jax.tree.map(lambda x: x[None], out)

            mapped = jax.shard_map(shard_fn, mesh=mesh,
                                   in_specs=(P(FLEET_AXIS), P()),
                                   out_specs=P(FLEET_AXIS), check_vma=False)
        else:
            mapped = jax.vmap(per_shard, in_axes=(0, None),
                              axis_name=FLEET_AXIS)
        return jax.jit(mapped)

    # per-worker (N,) arrays the tick and the control plane read: they
    # enter the compiled serve programs as runtime inputs
    _QP_WORKER_FIELDS = ("E_ON", "E_OFF", "E_MAX", "ESTEP")

    def _worker_inputs(self, sp: SchedParams, resh=None) -> dict:
        """The per-worker runtime inputs of a serve program, as host
        arrays (the launch uploads them); ``resh`` reshapes each (N, ...)
        array (the sharded build's (K, N/K, ...) split). A ``None`` phase
        becomes zeros: ``(i + 0) % T == i % T``."""
        from repro.fleet import sched as S
        p = self.p
        wk = {"ti": p.trace_index,
              "ph": (np.zeros(p.n, np.int64) if p.phase is None
                     else p.phase),
              "C": p.C, "v_max": p.v_max, "AP": p.active_power_w,
              "sp": {f: getattr(sp, f) for f in S.PER_WORKER_FIELDS}}
        if self.kernel != "xla":
            wk["qp"] = {f: getattr(self._qp_host, f)
                        for f in self._QP_WORKER_FIELDS}
        return jax.tree.map(np.asarray if resh is None else resh, wk)

    def _view(self, wk: dict, n: int) -> "JaxFleetBackend":
        """A shallow copy of this backend whose per-worker constants are
        the traced rows of ``wk`` (from :meth:`_worker_inputs`) for
        ``n`` workers: the tick methods then read them unchanged."""
        view = copy.copy(self)
        view.p = dataclasses.replace(self.p, n=n)
        view.trace_index = wk["ti"]
        view.phase = wk["ph"]
        view.C = wk["C"]
        view.v_max = wk["v_max"]
        view.AP = wk["AP"]
        if self.kernel != "xla":
            view._qp = dataclasses.replace(self._qp, **wk["qp"])
        return view

    def _usable(self, v):
        return capacitor_usable_energy(v, capacitance_f=self.C,
                                       v_off=self.p.v_off, xp=jnp)

    def _draw(self, v, amount):
        return capacitor_draw(v, amount, capacitance_f=self.C,
                              v_off=self.p.v_off, xp=jnp)

    def _harvest(self, v, pw):
        p = self.p
        return capacitor_harvest(v, pw, p.dt, capacitance_f=self.C,
                                 booster_eff=p.eff, v_max=self.v_max,
                                 xp=jnp)

    def _rec(self, ev, mask, code, t, ticket, units):
        """Record events for ``mask`` lanes into the fixed-capacity log
        (first event per worker per tick wins; see module docstring
        for why a second cannot occur)."""
        evc, evt, evtk, evu = ev
        new = mask & (evc == EV_NONE)
        return (jnp.where(new, code, evc), jnp.where(new, t, evt),
                jnp.where(new, ticket, evtk), jnp.where(new, units, evu))

    def _tick_q(self, st, ev, i):
        """Quantized tick as pure XLA: the ``kernel="q32"`` path — the
        exact xp-generic integer expressions of ``repro.fleet.qtick``
        traced with ``xp=jnp`` (the reference the Pallas megakernel is
        pinned against, and the measured CPU speedup over float64)."""
        from repro.fleet import qtick as Q
        qh = Q.harvest_row(self.p, self._qp, self.trace_index,
                           self.phase, i, jnp)
        return Q.tick_q(self.p, self._qp, st, ev, qh, i, jnp,
                        lax.while_loop)

    def _tick_pallas(self, st, ev, i):
        """Quantized tick as one fused Pallas pass per tick
        (``repro.kernels.serve_tick``): compiled for the TPU, or
        interpret-mode when the backend was built with ``interpret``.
        The kernel emits a fresh event log; it is merged into the passed
        log first-event-wins (the one-event-per-worker invariant)."""
        from repro.fleet import qtick as Q
        from repro.kernels import serve_tick as K
        p = self.p
        s = _S(*st)
        qh = Q.harvest_row(p, self._qp, self.trace_index, self.phase, i,
                           jnp)
        rw = {f: getattr(s, f) for f in K.RW_FIELDS}
        ro = {f: getattr(s, f) for f in K.RO_FIELDS}
        consts = dict(e_on=self._qp.E_ON, e_off=self._qp.E_OFF,
                      e_max=self._qp.E_MAX, estep=self._qp.ESTEP)
        rw_out, evk, _led = K.serve_tick(
            rw, ro, consts, self._k_tables, qh.astype(jnp.int32),
            i.astype(jnp.int32) if hasattr(i, "astype")
            else jnp.int32(i),
            u_max=int(p.UC.shape[1]), interpret=self.interpret)
        evc0 = ev[0]
        new = (evk[0] != EV_NONE) & (evc0 == EV_NONE)
        ev = tuple(jnp.where(new, a, b) for a, b in zip(evk, ev))
        return tuple(s._replace(**rw_out)), ev

    def _tick(self, st, ev, i):
        p = self.p
        s = _S(*st)
        dt = p.dt
        t = i * dt

        # 1. harvest (mirrors Capacitor.harvest)
        col = (i % p.T) if self.phase is None else (i + self.phase) % p.T
        pw = self.power[self.trace_index, col]
        e_harvest = s.e_harvest + p.eff * pw * dt
        v = self._harvest(s.v, pw)

        # 2. turn on at v_on
        waking = ~s.on & (v >= p.v_on)
        on = s.on | waking
        cycles = s.cycles + waking
        working = on & s.has_work
        idle = on & ~s.has_work
        s = s._replace(v=v, on=on, cycles=cycles, e_harvest=e_harvest)

        # 2b. persistence plane: pay the FRAM restore read before the
        # worker may progress again (the restore consumes its tick)
        if p.persist != "none":
            s, working = self._restore(s, working)

        # 3. acquisition
        if p.mode == "local":
            s = self._acquire_local(s, idle, t)
        else:
            s, ev = self._acquire_dispatch(s, idle, t, ev)

        # 4. progress in-flight work by one dt of active execution
        s, ev, emit_now = self._progress(s, working, t, ev)

        # 5. emission (BLE packet / host transfer)
        finish = (working & s.has_work & s.on
                  & ((s.w_units_done >= s.w_target) | emit_now))
        s, ev = self._emit(s, finish, t, ev)
        return tuple(s), ev

    def _acquire_local(self, s, idle, t):
        p = self.p
        due = idle & (t >= s.next_sample_t)
        delta = t - s.next_sample_t
        k = jnp.floor_divide(delta, p.P)
        sample_counter = s.sample_counter + jnp.where(
            due, k.astype(jnp.int64) + 1, 0)
        next_sample_t = s.next_sample_t + jnp.where(
            due, p.P * (k + 1.0), 0.0)
        # decide BEFORE spending anything (SMART skips the whole round)
        us = self._usable(s.v)
        from repro.core.policies import SKIP
        init, refine = p.policy.decide_batch(us, p.tables[0], p.acc,
                                             xp=jnp)
        skip = due & (init == SKIP)
        skipped = s.skipped + skip
        go = due & ~(init == SKIP)
        fixed = p.FIX[0]
        v2, ok = self._draw(s.v, jnp.minimum(fixed, us))
        v = jnp.where(go, v2, s.v)
        on = s.on & ~(go & ~ok)
        succ = go & ok
        return s._replace(
            v=v, on=on, skipped=skipped, sample_counter=sample_counter,
            next_sample_t=next_sample_t,
            e_work=s.e_work + jnp.where(succ, fixed, 0.0),
            acquired=s.acquired + succ,
            has_work=s.has_work | succ,
            w_ticket=jnp.where(succ, sample_counter - 1, s.w_ticket),
            w_t_acq=jnp.where(succ, t, s.w_t_acq),
            w_cycle_acq=jnp.where(succ, s.cycles, s.w_cycle_acq),
            w_units_done=jnp.where(succ, 0, s.w_units_done),
            w_left=jnp.where(succ, 0.0, s.w_left),
            w_target=jnp.where(succ, jnp.where(refine, p.NU[0], init),
                               s.w_target),
            w_tile=jnp.where(succ, 0, s.w_tile),
            w_wl=jnp.where(succ, 0, s.w_wl),
            w_batch=jnp.where(succ, 1, s.w_batch))

    def _restore(self, s, working):
        """Persistence-plane restore (persist != "none"): pay the FRAM
        read that reloads the progress image (ckpt) or log header
        (undolog); the restore consumes the worker's tick. Mirrors
        ``backend_numpy._restore`` expression for expression."""
        p = self.p
        rest = working & s.need_restore
        rj = self.REST_J[s.w_wl]
        v2, okr = self._draw(s.v, rj)
        v = jnp.where(rest, v2, s.v)
        okrest = rest & okr
        failr = rest & ~okr
        wud = s.w_units_done
        if p.persist == "ckpt":
            # Mementos semantics: rewind to the checkpointed counter
            wud = jnp.where(okrest, s.ck_units, wud)
        s = s._replace(
            v=v, on=s.on & ~failr,
            need_restore=s.need_restore & ~okrest,
            restores=s.restores + okrest,
            e_persist=s.e_persist + jnp.where(okrest, rj, 0.0),
            w_units_done=wud,
            w_left=jnp.where(okrest, 0.0, s.w_left))
        return s, working & ~rest

    def _acquire_dispatch(self, s, idle, t, ev):
        p = self.p
        due = idle & s.p_pending
        us = self._usable(s.v)
        fixed = self.FIX[s.p_wl]
        v2, ok = self._draw(s.v, jnp.minimum(fixed, us))
        v = jnp.where(due, v2, s.v)
        fail = due & ~ok
        succ = due & ok
        on = s.on & ~fail
        if p.persist == "none":
            p_pending = s.p_pending & ~due
            ev = self._rec(ev, fail, EV_LOST, t, s.p_ticket, 0)
        else:
            # exact disciplines never drop an accepted request: a failed
            # acquisition keeps the assignment pending across recharge
            p_pending = s.p_pending & ~succ
        s = s._replace(
            v=v, on=on, p_pending=p_pending,
            e_work=s.e_work + jnp.where(succ, fixed, 0.0),
            acquired=s.acquired + succ,
            has_work=s.has_work | succ,
            w_ticket=jnp.where(succ, s.p_ticket, s.w_ticket),
            w_t_acq=jnp.where(succ, t, s.w_t_acq),
            w_cycle_acq=jnp.where(succ, s.cycles, s.w_cycle_acq),
            w_units_done=jnp.where(succ, 0, s.w_units_done),
            w_left=jnp.where(succ, 0.0, s.w_left),
            w_tile=jnp.where(succ, s.p_units, s.w_tile),
            w_batch=jnp.where(succ, s.p_batch, s.w_batch),
            w_target=jnp.where(succ, s.p_units * s.p_batch, s.w_target),
            w_wl=jnp.where(succ, s.p_wl, s.w_wl))
        if p.persist != "none":
            # fresh request: clear stale persistence from a predecessor
            s = s._replace(need_restore=s.need_restore & ~succ,
                           ck_units=jnp.where(succ, 0, s.ck_units))
        return s, ev

    def _progress(self, s, working, t, ev):
        p = self.p
        dispatch = p.mode == "dispatch"
        u_max = p.UC.shape[1]
        e_step = jnp.where(working, self.AP * p.dt, 0.0)
        run = working & (s.w_units_done < s.w_target)
        emit_now = jnp.zeros(p.n, dtype=bool)
        ckpt_w = self.CKPT_J[s.w_wl]
        commit_w = self.COMMIT_J[s.w_wl]
        carry = (s.v, s.on, s.has_work, s.e_work, s.w_left, s.w_units_done,
                 e_step, run, emit_now, ev,
                 s.need_restore, s.ck_units, s.e_persist, s.persists)

        def cond(c):
            return jnp.any(c[7])

        def body(c):
            (v, on, has_work, e_work, w_left, w_units_done, e_step, run,
             emit_now, ev, need_restore, ck_units, e_persist,
             persists) = c
            # unit boundary: start the next unit only if unit + reserve
            # are affordable now. Approximate: reserve = the BLE emit
            # packet and "cant" emits the partial result. Exact: the
            # reserve also covers the checkpoint image / unit commit,
            # and "cant" is a forced power-down — the request persists.
            starting = run & (w_left <= 0)
            gidx = jnp.where(s.w_tile > 0,
                             w_units_done % jnp.maximum(s.w_tile, 1),
                             w_units_done)
            nc = self.UC[s.w_wl, jnp.clip(gidx, 0, u_max - 1)]
            us = self._usable(v)
            if p.persist == "none":
                cant = starting & (us < nc + self.EMITC[s.w_wl])
                emit_now = emit_now | cant
            else:
                rsv = ckpt_w if p.persist == "ckpt" else commit_w
                cant = starting & (us < nc + rsv + self.EMITC[s.w_wl])
                if p.persist == "ckpt":
                    # voltage trigger fired: serialize dirty progress
                    # to FRAM before dying (funded by the previous
                    # boundary's reserve)
                    dirty = cant & (w_units_done != ck_units)
                    v2, okc = self._draw(v, ckpt_w)
                    v = jnp.where(dirty, v2, v)
                    wrote = dirty & okc
                    ck_units = jnp.where(wrote, w_units_done, ck_units)
                    persists = persists + wrote
                    e_persist = e_persist + jnp.where(wrote, ckpt_w, 0.0)
                on = on & ~cant
                need_restore = need_restore | cant
            run = run & ~cant
            w_left = jnp.where(starting & ~cant, nc, w_left)
            take = jnp.minimum(e_step, w_left)
            v2, ok = self._draw(v, take)
            v = jnp.where(run, v2, v)
            fail = run & ~ok
            on = on & ~fail
            if p.persist == "none":
                # power failure mid-work: volatile by design; work lost
                has_work = has_work & ~fail
                if dispatch:
                    ev = self._rec(ev, fail, EV_LOST, t, s.w_ticket, 0)
            else:
                # the persisted request survives; restore re-runs it
                need_restore = need_restore | fail
            run = run & ok
            e_work = e_work + jnp.where(run, take, 0.0)
            w_left = jnp.where(run, w_left - take, w_left)
            e_step = jnp.where(run, e_step - take, e_step)
            fin = run & (w_left <= 1e-18)
            if p.persist == "undolog":
                # Alpaca task commit: the completed unit's undo-buffer
                # write makes w_units_done durable (funded by the
                # boundary reserve)
                v2, okc = self._draw(v, commit_w)
                v = jnp.where(fin, v2, v)
                halted = fin & ~okc
                on = on & ~halted
                need_restore = need_restore | halted
                run = run & ~halted
                fin = fin & okc
                persists = persists + fin
                e_persist = e_persist + jnp.where(fin, commit_w, 0.0)
            w_units_done = w_units_done + fin
            w_left = jnp.where(fin, 0.0, w_left)
            run = run & (e_step > 0) & (w_units_done < s.w_target)
            return (v, on, has_work, e_work, w_left, w_units_done, e_step,
                    run, emit_now, ev, need_restore, ck_units, e_persist,
                    persists)

        (v, on, has_work, e_work, w_left, w_units_done, _, _, emit_now,
         ev, need_restore, ck_units, e_persist, persists
         ) = lax.while_loop(cond, body, carry)
        s = s._replace(v=v, on=on, has_work=has_work, e_work=e_work,
                       w_left=w_left, w_units_done=w_units_done,
                       need_restore=need_restore, ck_units=ck_units,
                       e_persist=e_persist, persists=persists)
        return s, ev, emit_now

    def _emit(self, s, finish, t, ev):
        p = self.p
        ec = self.EMITC[s.w_wl]
        v2, ok = self._draw(s.v, ec)
        v = jnp.where(finish, v2, s.v)
        efail = finish & ~ok
        esucc = finish & ok
        on = s.on & ~efail
        if p.persist == "none":
            has_work = s.has_work & ~finish  # volatile: failed emission
            # loses the work
        else:
            # persisted work retries the emission after the next restore
            has_work = s.has_work & ~esucc
            s = s._replace(need_restore=s.need_restore | efail)
        if p.mode == "dispatch":
            if p.persist == "none":
                ev = self._rec(ev, efail, EV_LOST, t, s.w_ticket, 0)
            ev = self._rec(ev, esucc, EV_EMIT, t, s.w_ticket,
                           s.w_units_done)
        emit_acc_sum = s.emit_acc_sum
        if p.mode == "local":
            emit_acc_sum = emit_acc_sum + jnp.where(
                esucc,
                self.ACC[jnp.clip(s.w_units_done, 0, int(p.NU[0]))], 0.0)
        return s._replace(
            v=v, on=on, has_work=has_work,
            e_work=s.e_work + jnp.where(esucc, ec, 0.0),
            emit_count=s.emit_count + esucc,
            emit_units_sum=s.emit_units_sum + jnp.where(
                esucc, s.w_units_done, 0),
            emit_acc_sum=emit_acc_sum), ev
