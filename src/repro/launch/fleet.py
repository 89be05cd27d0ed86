"""Fleet serving launcher: scheduled vs independent intermittent workers.

    PYTHONPATH=src python -m repro.launch.fleet --workers 256 --duration 120
    PYTHONPATH=src python -m repro.launch.fleet --workers 1024 \
        --traces RF,SOM,SOR,SIR --scheduler both --json out.json
    PYTHONPATH=src python -m repro.launch.fleet --workers 1024 \
        --backend jax --sched forecast --lookahead 5 --traces SOM,SOR
    PYTHONPATH=src python -m repro.launch.fleet --workers 1024 \
        --sched forecast --forecaster auto --traces SIM,RF
    PYTHONPATH=src python -m repro.launch.fleet --workers 100000 \
        --backend jax --scheduler off --hetero --hetero-mcu
    PYTHONPATH=src python -m repro.launch.fleet --workers 256 \
        --quality measured --sched quality --traces SIM,RF
    PYTHONPATH=src python -m repro.launch.fleet --workers 4096 \
        --backend jax --scheduler on --mesh-fleet 8 --rebalance-every 1 \
        --fleet-placement single

Builds a harvest-powered worker fleet over a mix of energy-trace families,
then serves one global HAR + Harris + LM request stream either through the
array-native control plane (``repro.fleet.sched``) or as independent
self-sampling workers (the no-scheduler baseline), and prints the fleet
metrics. ``--backend jax`` fuses the whole serve trace — workers and
scheduler — into one ``lax.scan`` device launch; ``--sched forecast``
routes and batches on the forecast harvest over the next ``--lookahead``
seconds instead of instantaneous charge, under the ``--forecaster``
model (``repro.core.forecast``: OU / occlusion / burst / AR(p), or
``auto`` to match each worker's trace family); ``--hetero``
mixes capacitor sizes and ``--hetero-mcu`` mixes MCU classes (per-worker
active power) across the fleet. ``--quality measured`` swaps the
analytic accuracy proxies for tables measured by the quality oracles
(``repro.quality``: real SVM inference, Harris corner equivalence, real
anytime-LM decodes), and ``--sched quality`` serves queues by marginal
measured-accuracy-per-joule instead of age. ``--persist ckpt|undolog``
swaps the approximate discipline for the measured exact-equivalence
baselines (voltage-triggered checkpoints / task-granular undo-log
commits, joule-charged FRAM — docs/persistence_plane.md). The helpers
here are reused
by ``benchmarks/fleet_throughput.py``, ``benchmarks/fleet_quality.py``
and ``examples/fleet_serve.py``.
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import numpy as np

from repro.core.energy import Capacitor, McuEnergyModel, get_trace
from repro.core.forecast import FORECASTER_MODES
from repro.core.policies import Greedy, Smart
from repro.fleet.sched import SCHED_MODES
from repro.fleet.scheduler import FleetScheduler, RequestStream, run_fleet
from repro.fleet.worker import FleetWorkerPool, stack_traces
from repro.fleet.workloads import (FleetWorkload, har_workload,
                                   harris_workload, lm_workload)

WORKLOAD_FACTORIES = {
    "har": har_workload,
    "harris": harris_workload,
    "lm": lm_workload,
}


# the persistent compile cache's home when JAX_COMPILATION_CACHE_DIR is
# unset: a fixed path, since the directory is part of every entry's key
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Place JAX's persistent compilation cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it
    itself; otherwise the cache lives at the repository's fixed
    ``.jax_cache``. Streaming chunks share one length, so each serve
    program compiles once per cache. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def trace_family_labels(trace_names: list[str], n_rows: int) -> list[str]:
    """Per-row family labels matching :func:`make_power_matrix`'s row
    cycling — the one place the rule exists, so forecaster family labels
    cannot drift from the rows they describe."""
    return [trace_names[r % len(trace_names)] for r in range(n_rows)]


def make_power_matrix(trace_names: list[str], n_rows: int,
                      duration_s: float, dt: float = 0.01,
                      seed: int = 0) -> np.ndarray:
    """(n_rows, T) harvested-power matrix cycling through the families
    (row r gets ``trace_family_labels(trace_names, n_rows)[r]``);
    distinct seeds per row. Workers share rows (with phase offsets) so a
    1000-worker fleet does not pay 1000 trace syntheses."""
    rows = [get_trace(fam, seed=seed + r, duration_s=duration_s, dt=dt)
            for r, fam in enumerate(trace_family_labels(trace_names,
                                                        n_rows))]
    return stack_traces(rows)


def hetero_capacitors(n_workers: int, seed: int = 0,
                      cap: Capacitor | None = None
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Per-worker ``(capacitance_f, v_max)`` arrays for a heterogeneous
    fleet: capacitance log-uniform in [0.5x, 2x] of the reference buffer
    (device classes mixing 735 uF..2.9 mF parts), v_max jittered within
    the supervisor's rating band."""
    cap = cap or Capacitor()
    rng = np.random.default_rng(seed)
    C = cap.capacitance_f * np.exp(rng.uniform(np.log(0.5), np.log(2.0),
                                               n_workers))
    v_max = cap.v_max + rng.uniform(0.0, 0.2, n_workers)
    return C, v_max


def hetero_mcu(n_workers: int, seed: int = 0,
               mcu: McuEnergyModel | None = None) -> np.ndarray:
    """Per-worker active power for an MCU-class-heterogeneous fleet:
    each worker draws one of {0.5x, 1x, 2x} the reference device's active
    power (low-power, reference, and fast MCU bins)."""
    mcu = mcu or McuEnergyModel()
    rng = np.random.default_rng(seed + 1)
    classes = mcu.active_power_w * np.array([0.5, 1.0, 2.0])
    return rng.choice(classes, size=n_workers)


def build_dispatch_pool(power: np.ndarray, dt: float, n_workers: int,
                        workloads: list[FleetWorkload],
                        seed: int = 0, *, backend: str = "numpy",
                        capacitance_f: np.ndarray | None = None,
                        v_max: np.ndarray | None = None,
                        active_power_w: np.ndarray | None = None,
                        kernel: str = "xla",
                        fleet_placement: str = "mesh",
                        persist: str = "none",
                        interpret: bool = False) -> FleetWorkerPool:
    rng = np.random.default_rng(seed)
    return FleetWorkerPool(
        power, dt, workloads=[w.costs for w in workloads], mode="dispatch",
        n_workers=n_workers,
        trace_index=np.arange(n_workers) % power.shape[0],
        phase=rng.integers(0, power.shape[1], n_workers),
        backend=backend, capacitance_f=capacitance_f, v_max=v_max,
        active_power_w=active_power_w, kernel=kernel,
        fleet_placement=fleet_placement, persist=persist,
        interpret=interpret)


def run_scheduled(power: np.ndarray, dt: float, n_workers: int,
                  workloads: list[FleetWorkload], *, rate_rps: float,
                  mix: np.ndarray, n_steps: int, seed: int = 0,
                  max_batch: int = 4, shed_after_s: float = 30.0,
                  dispatch_every: int = 10, backend: str = "numpy",
                  sched: str = "reactive", lookahead_s: float = 5.0,
                  forecaster: str = "ou",
                  trace_families: list[str] | None = None,
                  forecaster_fit: str = "full",
                  capacitance_f: np.ndarray | None = None,
                  v_max: np.ndarray | None = None,
                  active_power_w: np.ndarray | None = None,
                  obs_mode: str = "off", obs_window_s: float = 1.0,
                  obs_ring: int = 256, trace_out: str = "",
                  obs_print: bool = False, kernel: str = "xla",
                  mesh_fleet: int = 1, rebalance_every_s: float = 0.0,
                  rebalance_max: int = 8,
                  fleet_placement: str = "mesh",
                  stream_mode: bool = False, chunk_ticks: int = 0,
                  refit_every_s: float = 0.0,
                  slo_p95_s: float = 0.0,
                  persist: str = "none",
                  grace_s: float = 20.0,
                  interpret: bool = False, profile_dir: str = "") -> dict:
    pool = build_dispatch_pool(power, dt, n_workers, workloads, seed,
                               backend=backend, capacitance_f=capacitance_f,
                               v_max=v_max, active_power_w=active_power_w,
                               kernel=kernel,
                               fleet_placement=fleet_placement,
                               persist=persist, interpret=interpret)
    # the trace covers building the scheduler (its dispatch thresholds)
    # and the serve
    from repro.obs.profile import profiled
    with profiled(profile_dir):
        # the rebalance cadence rounds to ticks; run_serve validates it is a
        # multiple of the dispatch cadence
        scheduler = FleetScheduler(pool, workloads, max_batch=max_batch,
                                   grace_s=grace_s,
                                   shed_after_s=shed_after_s, sched=sched,
                                   lookahead_s=lookahead_s,
                                   forecaster=forecaster,
                                   trace_families=trace_families,
                                   forecaster_fit=forecaster_fit,
                                   shards=mesh_fleet,
                                   rebalance_every=int(round(
                                       rebalance_every_s / dt)),
                                   rebalance_max=rebalance_max)
        obs = None
        if obs_mode != "off":
            from repro.obs import make_fleet_obs
            obs = make_fleet_obs(obs_mode, pool.params, scheduler.params,
                                 n_steps,
                                 window=max(int(round(obs_window_s / dt)), 1),
                                 ring=obs_ring)
        stream = RequestStream(rate_rps, mix, n_steps, dt, seed=seed + 1)
        if stream_mode:
            # streaming online serve: a live client thread feeds arrival
            # rows into the chunked steady-state loop (chunk boundaries
            # are where causal refits and per-chunk SLO records happen)
            from repro.fleet.scheduler import StreamClient, run_fleet_stream
            client = StreamClient(stream, scheduler.params.W, n_steps)
            summary = run_fleet_stream(
                pool, scheduler, client, n_steps,
                chunk_ticks=chunk_ticks or max(n_steps // 8, 1),
                dispatch_every=dispatch_every,
                refit_every=int(round(refit_every_s / dt)), obs=obs,
                slo_p95_s=slo_p95_s)
        else:
            summary = run_fleet(pool, scheduler, stream, n_steps,
                                dispatch_every=dispatch_every, obs=obs)
    summary["mode"] = "scheduled"
    summary["sched"] = sched
    summary["persist"] = persist
    summary["forecaster"] = forecaster
    summary["n_workers"] = n_workers
    summary["backend"] = backend
    summary["kernel"] = kernel
    summary["mesh_fleet"] = mesh_fleet
    if obs is not None:
        summary["obs"] = obs.summary()
        if trace_out and obs.ring is not None:
            from repro.obs import write_trace
            write_trace(trace_out, obs.op, obs.ring, dt, tele=obs.tele)
            summary["obs"]["trace_out"] = trace_out
        if obs_print:  # terminal summaries on stderr (stdout is JSON)
            import sys as _sys
            from repro.obs import format_ring_summary, format_tele_summary
            print(format_tele_summary(obs.op, obs.tele, dt),
                  file=_sys.stderr)
            if obs.ring is not None:
                print(format_ring_summary(obs.op, obs.ring, dt),
                      file=_sys.stderr)
    return summary


def run_independent(power: np.ndarray, dt: float, n_workers: int,
                    workloads: list[FleetWorkload], *, mix: np.ndarray,
                    period_s: float, n_steps: int, seed: int = 0,
                    backend: str = "numpy",
                    capacitance_f: np.ndarray | None = None,
                    v_max: np.ndarray | None = None,
                    active_power_w: np.ndarray | None = None) -> dict:
    """No-scheduler baseline: workers are pinned to a workload (by the
    request mix) and self-sample every ``period_s`` — same offered load
    as a ``rate_rps = n_workers / period_s`` stream, no routing.
    Accounting reads the pools' aggregate emission counters (not the
    per-result records) so the JAX backend serves it unchanged."""
    counts = (np.asarray(mix) / np.sum(mix) * n_workers).astype(int)
    counts[0] += n_workers - counts.sum()
    completed = 0
    units_sum = 0.0
    acc_sum = 0.0
    harvested = 0.0
    work = 0.0
    skipped = 0
    per_wl = {}
    rng = np.random.default_rng(seed)
    start = 0
    for wl, cnt in zip(workloads, counts):
        if cnt == 0:
            continue
        sl = slice(start, start + cnt)
        start += cnt
        pool = FleetWorkerPool(
            power, dt, workloads=[wl.costs], mode="local", n_workers=cnt,
            policy=Smart(wl.floor) if wl.floor > 0 else Greedy(),
            accuracy_table=wl.accuracy,
            sampling_period_s=period_s,
            trace_index=np.arange(cnt) % power.shape[0],
            phase=rng.integers(0, power.shape[1], cnt),
            backend=backend,
            capacitance_f=(None if capacitance_f is None
                           else capacitance_f[sl]),
            v_max=None if v_max is None else v_max[sl],
            active_power_w=(None if active_power_w is None
                            else active_power_w[sl]))
        st = pool.run(n_steps)
        completed += st.emitted
        skipped += st.skipped
        units_sum += float(pool.state.emit_units_sum.sum())
        acc_sum += float(pool.state.emit_acc_sum.sum())
        harvested += st.energy_harvested_j
        work += st.energy_on_work_j
        per_wl[wl.name] = {"workers": int(cnt), "completed": st.emitted}
    return {
        "mode": "independent",
        "n_workers": n_workers,
        "backend": backend,
        "completed": completed,
        "skipped": skipped,
        "throughput_rps": completed / (n_steps * dt),
        "mean_units": units_sum / max(completed, 1),
        "mean_expected_accuracy": acc_sum / max(completed, 1),
        "per_workload": per_wl,
        "energy": {"harvested_j": harvested, "work_j": work,
                   "j_per_completed": work / max(completed, 1),
                   "conservation_ok": bool(harvested + 1e-9 >= work)},
    }


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workers", type=int, default=256)
    ap.add_argument("--duration", type=float, default=120.0)
    ap.add_argument("--dt", type=float, default=0.01)
    ap.add_argument("--traces", default="RF,SOM,SIM,SOR,SIR")
    ap.add_argument("--trace-rows", type=int, default=0,
                    help="distinct trace rows (0: min(32, workers))")
    ap.add_argument("--workloads", default="har,harris,lm")
    ap.add_argument("--mix", default="0.4,0.3,0.3")
    ap.add_argument("--period", type=float, default=10.0,
                    help="per-worker sampling period; the request rate is "
                         "workers/period so both modes see the same load")
    ap.add_argument("--scheduler", choices=("on", "off", "both"),
                    default="both")
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="worker-pool backend: numpy reference lockstep or "
                         "one jax lax.scan launch (the fused serve scan "
                         "with the scheduler on)")
    ap.add_argument("--kernel", choices=("xla", "q32", "pallas"),
                    default="xla",
                    help="serve-tick kernel: float64 XLA expression chain "
                         "(xla), the int32-quantized pure-XLA twin (q32), "
                         "or the fused Pallas megakernel over quantized "
                         "state (pallas; compiled for the TPU unless "
                         "--interpret)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels through the Pallas "
                         "interpreter (pure XLA; for CPU-only hosts and "
                         "tests)")
    ap.add_argument("--mesh-fleet", type=int, default=1,
                    help="shard the serve scan K ways over a (fleet,) "
                         "device mesh: per-shard control planes, one "
                         "logical launch (jax backend; numpy runs the "
                         "bit-equal host twin). K must divide --workers")
    ap.add_argument("--rebalance-every", type=float, default=0.0,
                    help="cross-shard work-stealing cadence in seconds "
                         "(0: off). Queued requests flow around the "
                         "shard ring from backlogged to energy-rich "
                         "shards; must be a multiple of the dispatch "
                         "cadence and needs --mesh-fleet > 1")
    ap.add_argument("--fleet-placement",
                    choices=("mesh", "single"), default="mesh",
                    help="where the sharded scan runs: a real K-device "
                         "mesh (mesh; fails unless K devices exist) or a "
                         "single-device vmap of the same K-shard program "
                         "(single) — bit-identical results")
    ap.add_argument("--hetero", action="store_true",
                    help="heterogeneous fleet: per-worker capacitance/v_max")
    ap.add_argument("--hetero-mcu", action="store_true",
                    help="MCU-class mixing: per-worker active power")
    ap.add_argument("--sched", choices=SCHED_MODES, default="reactive",
                    help="routing/batching budget: instantaneous charge "
                         "(reactive), the harvest forecast over the next "
                         "--lookahead seconds (forecast), or reactive "
                         "budgets with queues served by marginal "
                         "measured-accuracy-per-joule (quality)")
    ap.add_argument("--quality", choices=("proxy", "measured"),
                    default="proxy",
                    help="accuracy-table provenance: analytic proxies "
                         "(proxy) or tables measured by the quality "
                         "oracles — real SVM inference, Harris corner "
                         "equivalence, real anytime-LM decodes "
                         "(measured; calibrates once per process)")
    ap.add_argument("--oracle-bank", type=float, default=1.0,
                    help="oracle sample-bank scale for --quality "
                         "measured: multiplies the calibration sample "
                         "counts (1.0 keeps the seconds-scale CI "
                         "default; larger banks cut table variance at "
                         "proportional calibration cost)")
    ap.add_argument("--lookahead", type=float, default=5.0,
                    help="forecast horizon in seconds (sched=forecast)")
    ap.add_argument("--forecaster", choices=FORECASTER_MODES, default="ou",
                    help="harvest forecast model (sched=forecast): OU "
                         "mean reversion, occlusion/burst regime models, "
                         "a learned AR(p) fit, or auto per-row selection "
                         "matched to each trace row's family")
    ap.add_argument("--forecaster-fit", choices=("full", "causal"),
                    default="full",
                    help="forecaster fit provenance (sched=forecast): "
                         "fit on the whole trace bank at construction "
                         "(full — the historical offline behavior, which "
                         "peeks at future harvest) or start from the "
                         "zero-inflow prior and refit from only the "
                         "observed prefix at streaming chunk boundaries "
                         "(causal; pair with --stream --refit-every)")
    ap.add_argument("--stream", action="store_true",
                    help="streaming online serve: a live client thread "
                         "feeds arrivals into the chunked steady-state "
                         "loop (fixed window per launch, full state "
                         "carried across chunk boundaries). Bit-exact "
                         "with the whole-trace launch when no refits "
                         "fire; per-chunk latency records land in the "
                         "summary's 'stream' block")
    ap.add_argument("--chunk-ticks", type=int, default=0,
                    help="ticks per streaming chunk (--stream; 0 picks "
                         "n_steps/8). Need not divide the trace length "
                         "— the final chunk covers the remainder")
    ap.add_argument("--refit-every", type=float, default=0.0,
                    help="causal forecaster refit cadence in seconds "
                         "(--stream with --forecaster-fit causal; 0: "
                         "off). Refits at chunk boundaries from only "
                         "the observed harvest prefix and swaps the "
                         "forecast tables without re-tracing the scan")
    ap.add_argument("--slo-p95", type=float, default=0.0,
                    help="per-chunk p95 latency SLO in seconds "
                         "(--stream; 0: off): each chunk record gets a "
                         "verdict and the stream block counts "
                         "violations")
    ap.add_argument("--profile-dir", default="",
                    help="record the serve (--scheduler on) under "
                         "jax.profiler into this directory: device "
                         "operations named by their fleet.* scopes and "
                         "the host's fleet.stream.*/fleet.serve.* spans "
                         "on one clock (docs/observability.md, 'Program "
                         "spans'; open in TensorBoard or Perfetto)")
    ap.add_argument("--persist", choices=("none", "ckpt", "undolog"),
                    default="none",
                    help="execution discipline (docs/persistence_plane."
                         "md): the paper's approximate runtime with no "
                         "NVM state machine (none), voltage-triggered "
                         "image checkpoints restored after every power "
                         "failure (ckpt, Mementos-style), or task-"
                         "granular undo-log commits with idempotent "
                         "re-execution (undolog, Alpaca-style). The "
                         "exact disciplines run every workload unit and "
                         "survive brown-outs at measured FRAM joule "
                         "cost; requires --scheduler on")
    ap.add_argument("--grace", type=float, default=20.0,
                    help="straggler-eviction grace in seconds; exact "
                         "persist disciplines span recharge cycles, so "
                         "raise it when comparing against --persist "
                         "ckpt/undolog")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--shed-after", type=float, default=30.0)
    ap.add_argument("--obs", choices=("off", "tele", "trace"),
                    default="off",
                    help="observability plane (repro.obs): windowed "
                         "telemetry channels (tele) plus per-worker "
                         "event rings with Perfetto export (trace); "
                         "serve results are bit-identical either way")
    ap.add_argument("--obs-window", type=float, default=1.0,
                    help="telemetry window length in seconds")
    ap.add_argument("--trace-out", default="",
                    help="write the Chrome trace-event / Perfetto JSON "
                         "here (--obs trace; open in chrome://tracing)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="", help="write summary to this path")
    args = ap.parse_args(argv)
    init_compile_cache()

    names = args.traces.split(",")
    wl_names = args.workloads.split(",")
    unknown = [n for n in wl_names if n not in WORKLOAD_FACTORIES]
    if unknown:
        ap.error(f"unknown workload(s) {unknown}; "
                 f"choose from {sorted(WORKLOAD_FACTORIES)}")
    if args.quality == "measured":
        from repro.quality.calibrate import measured_workloads
        workloads = measured_workloads(wl_names, seed=args.seed,
                                       bank=args.oracle_bank)
    else:
        workloads = [WORKLOAD_FACTORIES[n]() for n in wl_names]
    mix = np.array([float(x) for x in args.mix.split(",")])
    if mix.shape[0] != len(workloads):
        ap.error(f"--mix has {mix.shape[0]} entries for "
                 f"{len(workloads)} workloads")
    if args.persist != "none" and args.scheduler != "on":
        ap.error("--persist ckpt/undolog are dispatch-plane disciplines; "
                 "the independent baseline is approximate-only — use "
                 "--scheduler on")
    n_rows = args.trace_rows or min(32, args.workers)
    power = make_power_matrix(names, n_rows, args.duration, args.dt,
                              args.seed)
    n_steps = int(args.duration / args.dt)
    rate = args.workers / args.period
    cf = vm = ap_w = None
    if args.hetero:
        cf, vm = hetero_capacitors(args.workers, args.seed)
    if args.hetero_mcu:
        ap_w = hetero_mcu(args.workers, args.seed)

    out: dict = {"config": vars(args)}
    families = trace_family_labels(names, n_rows)
    if args.scheduler in ("on", "both"):
        out["scheduled"] = run_scheduled(
            power, args.dt, args.workers, workloads, rate_rps=rate, mix=mix,
            n_steps=n_steps, seed=args.seed, max_batch=args.max_batch,
            shed_after_s=args.shed_after, backend=args.backend,
            sched=args.sched, lookahead_s=args.lookahead,
            forecaster=args.forecaster, trace_families=families,
            forecaster_fit=args.forecaster_fit,
            capacitance_f=cf, v_max=vm, active_power_w=ap_w,
            obs_mode=args.obs, obs_window_s=args.obs_window,
            trace_out=args.trace_out, obs_print=True, kernel=args.kernel,
            mesh_fleet=args.mesh_fleet,
            rebalance_every_s=args.rebalance_every,
            fleet_placement=args.fleet_placement,
            stream_mode=args.stream, chunk_ticks=args.chunk_ticks,
            refit_every_s=args.refit_every, slo_p95_s=args.slo_p95,
            persist=args.persist, grace_s=args.grace,
            interpret=args.interpret, profile_dir=args.profile_dir)
    if args.scheduler in ("off", "both"):
        out["independent"] = run_independent(
            power, args.dt, args.workers, workloads, mix=mix,
            period_s=args.period, n_steps=n_steps, seed=args.seed,
            backend=args.backend, capacitance_f=cf, v_max=vm,
            active_power_w=ap_w)
    if "scheduled" in out and "independent" in out:
        out["speedup_completed"] = (
            out["scheduled"]["completed"]
            / max(out["independent"]["completed"], 1))
    print(json.dumps(out, indent=1, default=str))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, default=str)
    return out


if __name__ == "__main__":
    main()
