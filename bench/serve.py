"""Drives the system under test: the launcher's own build functions and the
program's chunk loop, ``run_fleet_stream``, as one timed window.

Set-up builds the ``--backend jax --scheduler on`` pool and scheduler
once, with the benchmark's trace bank and the configuration's initial
charge, serves two warm-up chunks (the one chunk program this cell
uses), times a few more chunks to size the window, then puts the fleet
back to its initial state: the pool through its own ``reset()`` and the
initial charge, the scheduler by a copy taken before the warm-up. The
window is then one ``run_fleet_stream`` call from tick 0, what
``python -m repro.launch.fleet --backend jax --scheduler on --stream``
runs, fed by the benchmark's own arrival source. The fleet and scheduler
states after the window's first ``reference_ticks`` ticks are kept for
the comparison with the reference (all of a shorter window).

Spans (``jax.profiler.TraceAnnotation``, on the device trace's clock):
``bench.window`` around the call, ``bench.take`` around each arrival
take and ``bench.run_serve`` around each chunk launch, the last wrapped
on the pool from outside.
"""
from __future__ import annotations

import copy
import math
import time
import types

import numpy as np

import deploy
from traffic import ArrivalSource

# compile events: lowering to a module, and creating an executable
# (compiled, or loaded from the persistent cache)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCount:
    """Counts compile events in this process from its creation on."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1


def build(config: dict, seed: int, power: np.ndarray):
    """The program's pool and scheduler of one configuration, through the
    launcher's build functions, charged as the configuration says."""
    from repro.fleet.scheduler import FleetScheduler
    from repro.launch import fleet as L
    workloads = [L.WORKLOAD_FACTORIES[n]() for n in config["workloads"]]
    pool = L.build_dispatch_pool(
        power, float(config["dt_s"]), int(config["workers"]), workloads,
        seed, backend="jax", kernel=config["kernel"])
    sched = FleetScheduler(
        pool, workloads, max_queue=int(config["max_queue"]),
        max_batch=int(config["max_batch"]),
        max_retries=int(config["max_retries"]),
        grace_s=float(config["grace_s"]),
        shed_after_s=float(config["shed_after_s"]), sched=config["sched"],
        lat_bins=int(config["lat_bins"]))
    charge(pool, config, seed)
    return pool, sched


def charge(pool, config: dict, seed: int) -> None:
    pool.state.v = deploy.initial_quanta(config, seed).astype(
        pool.state.v.dtype)


def stream_args(config: dict) -> dict:
    return dict(chunk_ticks=int(config["chunk_ticks"]),
                dispatch_every=int(config["dispatch_every"]),
                refit_every=0)


def _spanned(fn, name: str):
    import jax

    def wrapper(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)
    return wrapper


def checked_chunks(config: dict, n_chunks: int) -> int:
    """How many of the window's leading chunks the reference replays."""
    return min(n_chunks, max(1, int(config["reference_ticks"])
                             // int(config["chunk_ticks"])))


def _sized_window(config: dict, per_chunk_s: float, seconds: float) -> int:
    """Whole chunks filling ``seconds``, at least ``min_window_chunks``,
    and within the bank and ``max_window_ticks`` (where given)."""
    ck = int(config["chunk_ticks"])
    n = max(math.ceil(seconds / per_chunk_s),
            int(config["min_window_chunks"]))
    most = min(deploy.bank_ticks(config),
               int(config.get("max_window_ticks", 1 << 62)))
    return min(n, most // ck)


def serve(config: dict, seed: int, rows: np.ndarray, power: np.ndarray,
          seconds: float, compiles: CompileCount,
          trace_dir: str | None = None):
    """Builds, warms up, sizes and serves the window. Returns a record of
    the window: its ticks, the take stamps, its end, the compiles in it,
    the program's summary, its states after the checked chunks, and the
    device's peak memory."""
    import jax
    from repro.fleet.scheduler import run_fleet_stream
    pool, sched = build(config, seed, power)
    ck = int(config["chunk_ticks"])
    args = stream_args(config)
    fresh = copy.copy(sched)
    fresh.state = copy.deepcopy(sched.state)
    pool.run_serve = _spanned(pool.run_serve, "bench.run_serve")

    # warm-up: two chunks; the second is the first warm one
    src = ArrivalSource(rows)
    run_fleet_stream(pool, sched, src, 2 * ck, **args)
    c, el = 1, time.perf_counter() - src.stamps[1]
    pos = 2 * ck
    # time 4, 16, ... more chunks until a second has passed
    while not trace_dir and el < 1.0 and pos + 4 * c * ck <= len(rows):
        c *= 4
        t0 = time.perf_counter()
        run_fleet_stream(pool, sched, ArrivalSource(rows[pos:]), c * ck,
                         **args)
        el = time.perf_counter() - t0
        pos += c * ck
    n_chunks = (int(config["trace_chunks"]) if trace_dir
                else _sized_window(config, el / c, seconds))
    pool.reset()
    charge(pool, config, seed)
    n_check = checked_chunks(config, n_chunks)
    held = []
    launch = pool.run_serve

    def run_serve(sched_, arrivals, **kwargs):
        launch(sched_, arrivals, **kwargs)
        if pool.steps_done == n_check * ck:  # each launch binds new states
            held.append((pool.state, sched_.state))
    pool.run_serve = run_serve

    source = ArrivalSource(rows)
    source.take = _spanned(source.take, "bench.take")
    n_ticks = n_chunks * ck
    before = compiles.n
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # spans and device ops, no Python calls
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        summary = run_fleet_stream(pool, fresh, source, n_ticks, **args)
    t_end = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    return types.SimpleNamespace(
        ticks=n_ticks, chunks=n_chunks, stamps=list(source.stamps),
        t_end=t_end, compiles=compiles.n - before, summary=summary,
        checked_chunks=n_check, checked_states=held[0],
        offered=int(rows[:n_ticks].sum()),
        memory_peak_bytes=peak_bytes(jax.local_devices()))


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
