"""Fleet throughput: energy-aware scheduler vs independent workers,
NumPy-vs-JAX worker-backend scaling, and the fused forecast-aware control
plane.

Claims checked:
- at >=1000 workers over a 600 s mixed RF/solar trace, the central
  scheduler (admission + energy-proportional routing + batching +
  shedding) completes more requests than the same fleet serving the same
  offered load as independent self-sampling workers — routing moves work
  from energy-starved workers to charged ones instead of skipping it;
- the vectorized worker pool scales: completed-request throughput grows
  near-linearly with fleet size (>=1000-worker scaling curve);
- the JAX ``lax.scan`` backend (a) agrees with the NumPy reference on
  emitted/skipped/power-cycle counts, and (b) carries the fleet to
  >=100k workers in one device launch (``--backend jax``);
- the array-native control plane (``--control-plane``): a full
  1024-worker / 600 s serve trace with ``--backend jax`` runs workers AND
  scheduler as one compiled launch, agrees with the NumPy per-tick
  reference on all request/emission counts, and forecast routing beats
  reactive routing on completed requests for the solar trace families;
- pluggable forecasters (``--forecasters``): the forecaster-vs-family
  completed-requests matrix at 1024 workers / 600 s — regime-aware
  models (occlusion for mobile solar, burst for RF) complete at least as
  many requests as the OU mean reversion on their matched families
  (SIM, RF) while ``auto`` per-row selection matches the best
  single-family model everywhere;
- the sharded serve scan (``--mesh-fleet K``): the same K-shard program
  — per-shard control planes, deterministic arrival split, optional
  cross-shard work stealing — evaluated by the NumPy host twin, as a
  single-device ``vmap`` over the shard axis, and as a ``shard_map``
  over a real K-device mesh produces bit-identical summaries (every
  request/quality/latency counter), rebalance off or on — placement
  never changes bits (docs/sharded_fleet.md);
- energy conservation holds fleet-wide (harvested >= work; NVM == 0 by
  construction for the approximate runtime).

    python -m benchmarks.fleet_throughput                 # scheduler claims
    python -m benchmarks.fleet_throughput --backend jax   # backend scaling
    python -m benchmarks.fleet_throughput --control-plane # fused scheduler
    python -m benchmarks.fleet_throughput --control-plane --forecaster auto
    python -m benchmarks.fleet_throughput --forecasters   # model matrix
    python -m benchmarks.fleet_throughput --smoke         # CI agreement gate
    python -m benchmarks.fleet_throughput --smoke --mesh-fleet 8  # sharded gate

JSON lands in experiments/fleet_throughput.json (scheduler claims),
experiments/fleet_backend_scaling.json (backend scaling),
experiments/fleet_control_plane.json (control plane), and
experiments/fleet_forecasters.json (forecaster matrix), same convention
as benchmarks/run.py; docs/experiments.md documents every schema.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

from benchmarks.common import (emit, host_metadata,
                               require_pallas_target)
from repro.core.energy import power_matrix
from repro.core.forecast import FAMILY_FORECASTER, FORECASTER_MODES
from repro.launch.fleet import (hetero_capacitors, make_power_matrix,
                                run_independent, run_scheduled,
                                trace_family_labels)
from repro.fleet.workloads import har_workload, harris_workload, lm_workload

TRACES = ["RF", "SOM", "SIM", "SOR", "SIR"]
MIX = np.array([0.4, 0.3, 0.3])
DT = 0.01
PERIOD_S = 10.0  # per-worker sampling period == fleet load of N/10 rps


def _workloads():
    return [har_workload(), harris_workload(), lm_workload()]


def run_comparison(n_workers: int = 1024, duration_s: float = 600.0,
                   seed: int = 0) -> dict:
    wls = _workloads()
    power = make_power_matrix(TRACES, min(32, n_workers), duration_s, DT,
                              seed)
    n_steps = int(duration_s / DT)
    rate = n_workers / PERIOD_S
    sched = run_scheduled(power, DT, n_workers, wls, rate_rps=rate, mix=MIX,
                          n_steps=n_steps, seed=seed)
    indep = run_independent(power, DT, n_workers, wls, mix=MIX,
                            period_s=PERIOD_S, n_steps=n_steps, seed=seed)
    return {
        "n_workers": n_workers,
        "duration_s": duration_s,
        "scheduled": sched,
        "independent": indep,
        "speedup_completed": sched["completed"] / max(indep["completed"], 1),
    }


def scaling_curve(sizes=(64, 256, 1024), duration_s: float = 120.0,
                  seed: int = 1) -> dict:
    out = {}
    for n in sizes:
        wls = _workloads()
        power = make_power_matrix(TRACES, min(32, n), duration_s, DT,
                                  seed + n)
        n_steps = int(duration_s / DT)
        s = run_scheduled(power, DT, n, wls, rate_rps=n / PERIOD_S, mix=MIX,
                          n_steps=n_steps, seed=seed)
        out[str(n)] = {
            "completed": s["completed"],
            "throughput_rps": s["throughput_rps"],
            "rps_per_worker": s["throughput_rps"] / n,
        }
    return out


# ---------------------------------------------------------------------------
# NumPy-vs-JAX backend: agreement, wall-clock, >=100k scaling
# ---------------------------------------------------------------------------


def _timed_independent(backend: str, n_workers: int, duration_s: float,
                       power: np.ndarray,
                       seed: int = 0) -> tuple[dict, float]:
    n_steps = int(duration_s / DT)
    t0 = time.perf_counter()
    res = run_independent(power, DT, n_workers, _workloads(), mix=MIX,
                          period_s=PERIOD_S, n_steps=n_steps, seed=seed,
                          backend=backend)
    return res, time.perf_counter() - t0


def _backend_agreement(n_workers: int, duration_s: float, n_rows: int,
                       seed: int = 0) -> dict:
    """The one definition of backend agreement: both backends serve the
    same mixed-workload fleet on one shared trace bank, and the
    completed/skipped counts must match. Used by the recorded benchmark
    and the CI smoke gate alike so the two cannot drift."""
    power = power_matrix(TRACES, min(n_rows, n_workers), duration_s, DT,
                         seed)
    np_res, _ = _timed_independent("numpy", n_workers, duration_s, power,
                                   seed)
    jax_res, _ = _timed_independent("jax", n_workers, duration_s, power,
                                    seed)
    agree = (np_res["completed"] == jax_res["completed"]
             and np_res["skipped"] == jax_res["skipped"])
    return {
        "n_workers": n_workers,
        "duration_s": duration_s,
        "counts_agree": bool(agree),
        "completed": {"numpy": np_res["completed"],
                      "jax": jax_res["completed"]},
        "skipped": {"numpy": np_res["skipped"], "jax": jax_res["skipped"]},
    }


def backend_comparison(n_workers: int = 1024, duration_s: float = 120.0,
                       seed: int = 0) -> dict:
    """Same fleet, both backends: count agreement (full mixed-workload
    fleet) + wall-clock on one representative pool. The JAX pool is timed
    cold (includes trace+compile of the scan) and again after ``reset()``
    — the same compiled scan, fresh state — so the steady-state number is
    genuinely warm instead of silently re-tracing per run."""
    out = _backend_agreement(n_workers, duration_s, 32, seed)
    power = power_matrix(TRACES, min(32, n_workers), duration_s, DT, seed)

    wl = har_workload()
    n_steps = int(duration_s / DT)

    def _pool(backend):
        from repro.core.policies import Greedy
        from repro.fleet.worker import FleetWorkerPool
        return FleetWorkerPool(
            power, DT, workloads=[wl.costs], mode="local",
            n_workers=n_workers, policy=Greedy(),
            accuracy_table=wl.accuracy, sampling_period_s=PERIOD_S,
            trace_index=np.arange(n_workers) % power.shape[0],
            phase=np.random.default_rng(seed).integers(
                0, power.shape[1], n_workers),
            backend=backend)

    pool_np = _pool("numpy")
    t0 = time.perf_counter()
    st_np = pool_np.run(n_steps)
    np_s = time.perf_counter() - t0

    pool_jax = _pool("jax")
    t0 = time.perf_counter()
    pool_jax.run(n_steps)
    jax_cold_s = time.perf_counter() - t0
    pool_jax.reset()
    t0 = time.perf_counter()
    st_jax = pool_jax.run(n_steps)
    jax_s = time.perf_counter() - t0
    assert st_np.emitted == st_jax.emitted  # the timed pools agree too

    out["wall_s"] = {"numpy": np_s, "jax_warm": jax_s,
                     "jax_including_compile": jax_cold_s}
    out["speedup_jax_over_numpy_warm"] = np_s / max(jax_s, 1e-9)
    return out


def jax_scaling_curve(sizes=(1024, 8192, 32768, 131072),
                      duration_s: float = 20.0, seed: int = 2,
                      hetero: bool = True) -> dict:
    """Worker-count scaling of the scan backend (local HAR fleet,
    heterogeneous capacitors): one pool per size, timed cold (includes
    the one-off scan compile) and warm (``reset()`` + re-run of the same
    compiled launch — the steady-state ceiling)."""
    from repro.core.policies import Greedy
    from repro.fleet.worker import FleetWorkerPool

    wl = har_workload()
    n_steps = int(duration_s / DT)
    out = {}
    for n in sizes:
        power = power_matrix(TRACES, min(64, n), duration_s, DT, seed + 1)
        cf = vm = None
        if hetero:
            cf, vm = hetero_capacitors(n, seed)
        rng = np.random.default_rng(seed)
        pool = FleetWorkerPool(
            power, DT, workloads=[wl.costs], mode="local", n_workers=n,
            policy=Greedy(), accuracy_table=wl.accuracy,
            sampling_period_s=PERIOD_S,
            trace_index=np.arange(n) % power.shape[0],
            phase=rng.integers(0, power.shape[1], n),
            backend="jax", capacitance_f=cf, v_max=vm)
        t0 = time.perf_counter()
        pool.run(n_steps)
        cold = time.perf_counter() - t0
        pool.reset()
        t0 = time.perf_counter()
        res = pool.run(n_steps)
        warm = time.perf_counter() - t0
        out[str(n)] = {
            "completed": res.emitted,
            "wall_s_cold": cold,
            "wall_s_warm": warm,
            "worker_ticks_per_s": n * n_steps / max(warm, 1e-9),
        }
    return out


def run_backend_suite(max_workers: int = 131072) -> dict:
    sizes = tuple(n for n in (1024, 8192, 32768, 131072)
                  if n <= max_workers)
    t0 = time.perf_counter()
    comp = backend_comparison()
    curve = jax_scaling_curve(sizes=sizes)
    total = time.perf_counter() - t0
    res = {"comparison": comp, "jax_scaling": curve,
           "host": host_metadata()}
    us = total * 1e6 / (1 + len(curve))
    emit("fleet.backend_counts_agree", us, str(comp["counts_agree"]))
    emit("fleet.backend_jax_speedup_1024", us,
         f"{comp['speedup_jax_over_numpy_warm']:.2f}x")
    top = str(max(int(k) for k in curve))
    emit(f"fleet.jax_worker_ticks_per_s_at_{top}", us,
         f"{curve[top]['worker_ticks_per_s']:.2e}")
    out = Path("experiments")
    out.mkdir(exist_ok=True)
    (out / "fleet_backend_scaling.json").write_text(
        json.dumps(res, indent=1, default=str))
    return res


# ---------------------------------------------------------------------------
# fused control plane: reactive vs forecast, host-tick vs one-launch
# ---------------------------------------------------------------------------

_COUNT_KEYS = ("submitted", "completed", "rejected", "shed", "lost",
               "evicted", "requeued")


def _sched_agreement(n_workers: int, duration_s: float, n_rows: int,
                     seed: int = 0, sched: str = "forecast",
                     traces=None, forecaster: str = "ou",
                     forecaster_fit: str = "full",
                     workloads=None, obs_mode: str = "off",
                     obs_window_s: float = 1.0,
                     trace_out: str = "", kernel: str = "xla",
                     persist: str = "none",
                     grace_s: float = 20.0) -> dict:
    """One definition of *scheduler* agreement: the NumPy per-tick driver
    and the fused JAX launch serve the same stream over one trace bank
    and must match on every request-lifecycle counter and on the pool's
    emitted/skipped/power-cycle counts. Used by the recorded benchmark
    and the CI smoke gate alike. With ``obs_mode`` on, both runs are
    instrumented (repro.obs) and every telemetry channel must *also*
    agree bit-exactly (``obs_channels_agree``)."""
    names = traces or TRACES
    rows = min(n_rows, n_workers)
    power = make_power_matrix(names, rows, duration_s, DT, seed)
    families = trace_family_labels(names, rows)
    n_steps = int(duration_s / DT)
    rate = n_workers / PERIOD_S
    res = {}
    for backend in ("numpy", "jax"):
        res[backend] = run_scheduled(
            power, DT, n_workers, workloads or _workloads(),
            rate_rps=rate, mix=MIX, n_steps=n_steps, seed=seed,
            backend=backend, sched=sched, forecaster=forecaster,
            forecaster_fit=forecaster_fit,
            trace_families=families, obs_mode=obs_mode,
            obs_window_s=obs_window_s,
            trace_out=(trace_out if backend == "jax" else ""),
            kernel=kernel, persist=persist, grace_s=grace_s)
    agree = all(res["numpy"][k] == res["jax"][k] for k in _COUNT_KEYS)
    out = {
        "n_workers": n_workers,
        "duration_s": duration_s,
        "sched": sched,
        "forecaster": forecaster,
        "counts_agree": bool(agree),
        "counts": {b: {k: res[b][k] for k in _COUNT_KEYS}
                   for b in ("numpy", "jax")},
    }
    if persist != "none":
        # the persist ledgers (FRAM joules + checkpoint/commit/restore
        # counters) must be bit-equal across the twin evaluations too
        pk = ("nvm_j", "persists", "restores")
        a = {k: res["numpy"]["energy"][k] for k in pk}
        b = {k: res["jax"]["energy"][k] for k in pk}
        out["persist"] = persist
        out["persist_ledger"] = a
        out["persist_agree"] = bool(a == b)
    if obs_mode != "off":
        a = res["numpy"]["obs"]["channels"]
        b = res["jax"]["obs"]["channels"]
        out["obs_channels_agree"] = bool(
            all(a[name] == b[name] for name in a))
        out["obs_events"] = res["jax"]["obs"]["events"]
    return out


def control_plane_comparison(n_workers: int = 1024,
                             duration_s: float = 600.0,
                             seed: int = 0) -> dict:
    """Forecast vs reactive routing, per solar family, on the fused JAX
    launch: same fleet, same stream, only the routing budget changes."""
    n_steps = int(duration_s / DT)
    rate = n_workers / PERIOD_S
    out = {}
    for fam in ("SOM", "SOR", "SIM"):
        power = make_power_matrix([fam], min(32, n_workers), duration_s,
                                  DT, seed)
        per = {}
        for sched in ("reactive", "forecast"):
            r = run_scheduled(power, DT, n_workers, _workloads(),
                              rate_rps=rate, mix=MIX, n_steps=n_steps,
                              seed=seed, backend="jax", sched=sched)
            per[sched] = {k: r[k] for k in _COUNT_KEYS}
            per[sched]["throughput_rps"] = r["throughput_rps"]
            per[sched]["mean_expected_accuracy"] = \
                r["mean_expected_accuracy"]
        per["forecast_over_reactive"] = (
            per["forecast"]["completed"]
            / max(per["reactive"]["completed"], 1))
        out[fam] = per
    return out


# ---------------------------------------------------------------------------
# pluggable forecasters: model x trace-family completed-requests matrix
# ---------------------------------------------------------------------------

FORECASTER_FAMILIES = ("SOM", "SIM", "SOR", "SIR", "RF", "ECL")


def forecaster_matrix(n_workers: int = 1024, duration_s: float = 600.0,
                      seed: int = 0, backend: str = "jax",
                      period_s: float = 2 * PERIOD_S,
                      forecasters=FORECASTER_MODES,
                      families=FORECASTER_FAMILIES) -> dict:
    """Forecaster x trace-family matrix: one single-family fleet per
    family, served with forecast routing under each forecast model (same
    stream, same workers — only the planning budget's conditional
    expectation changes). The headline claim: the regime-aware models
    (occlusion on mobile solar, burst on RF) complete at least as many
    requests as the OU mean reversion on their matched families, and
    ``auto`` per-row selection tracks the matched model.

    The matrix runs at *moderate* load (``period_s`` = 20 s -> rate
    N/20 rps, half the throughput suites' N/10): at N/10 the scarce
    families (RF, SIR, SIM) are energy-saturated — ~40% of arrivals shed
    whatever the forecast says, and completions measure harvested joules
    rather than decision quality. Below saturation, routing and batch
    sizing are what decide completions, which is the thing a forecaster
    can influence."""
    n_steps = int(duration_s / DT)
    rate = n_workers / period_s
    rows = min(32, n_workers)
    out: dict = {"n_workers": n_workers, "duration_s": duration_s,
                 "families": {}}
    for fam in families:
        power = make_power_matrix([fam], rows, duration_s, DT, seed)
        per = {}
        for fc in forecasters:
            r = run_scheduled(
                power, DT, n_workers, _workloads(), rate_rps=rate,
                mix=MIX, n_steps=n_steps, seed=seed, backend=backend,
                sched="forecast", forecaster=fc,
                trace_families=[fam] * rows)
            per[fc] = {k: r[k] for k in _COUNT_KEYS}
            per[fc]["throughput_rps"] = r["throughput_rps"]
            per[fc]["mean_expected_accuracy"] = r["mean_expected_accuracy"]
        matched = FAMILY_FORECASTER[fam]
        per["matched_model"] = matched
        per["matched_over_ou"] = (per[matched]["completed"]
                                  / max(per["ou"]["completed"], 1))
        per["auto_over_ou"] = (per["auto"]["completed"]
                               / max(per["ou"]["completed"], 1))
        out["families"][fam] = per
    out["regime_beats_ou_on_matched"] = all(
        out["families"][f][out["families"][f]["matched_model"]]
        ["completed"] >= out["families"][f]["ou"]["completed"]
        for f in families if out["families"][f]["matched_model"] != "ou")
    return out


def run_forecaster_suite(n_workers: int = 1024,
                         duration_s: float = 600.0,
                         backend: str = "jax") -> dict:
    t0 = time.perf_counter()
    res = forecaster_matrix(n_workers, duration_s, backend=backend)
    res["host"] = host_metadata()
    total = time.perf_counter() - t0
    us = total * 1e6 / max(len(res["families"]), 1)
    for fam, per in res["families"].items():
        emit(f"fleet.forecaster_matched_over_ou_{fam}", us,
             f"{per['matched_over_ou']:.3f}x")
    emit("fleet.forecaster_regime_beats_ou_on_matched", us,
         str(res["regime_beats_ou_on_matched"]))
    out = Path("experiments")
    out.mkdir(exist_ok=True)
    (out / "fleet_forecasters.json").write_text(
        json.dumps(res, indent=1, default=str))
    return res


def run_control_plane_suite(n_workers: int = 1024,
                            duration_s: float = 600.0,
                            forecaster: str = "ou",
                            forecaster_fit: str = "full",
                            obs_mode: str = "off",
                            obs_window_s: float = 1.0,
                            trace_out: str = "") -> dict:
    t0 = time.perf_counter()
    agree = _sched_agreement(n_workers, duration_s, 32, sched="forecast",
                             forecaster=forecaster,
                             forecaster_fit=forecaster_fit,
                             obs_mode=obs_mode,
                             obs_window_s=obs_window_s,
                             trace_out=trace_out)
    comp = control_plane_comparison(n_workers, duration_s)
    total = time.perf_counter() - t0
    res = {"agreement": agree, "forecast_vs_reactive": comp,
           "host": host_metadata()}
    us = total * 1e6 / 2
    emit("fleet.sched_counts_agree", us, str(agree["counts_agree"]))
    if obs_mode != "off":
        emit("fleet.obs_channels_agree", us,
             str(agree["obs_channels_agree"]))
    for fam, per in comp.items():
        emit(f"fleet.forecast_over_reactive_{fam}", us,
             f"{per['forecast_over_reactive']:.3f}x")
    out = Path("experiments")
    out.mkdir(exist_ok=True)
    (out / "fleet_control_plane.json").write_text(
        json.dumps(res, indent=1, default=str))
    return res


def _quant_agreement(n_workers: int, duration_s: float, n_rows: int,
                     seed: int = 0, kernel: str = "pallas",
                     interpret: bool = False) -> dict:
    """One definition of *kernel* agreement: the float64 XLA serve scan,
    the int32-quantized pure-XLA twin (``q32``), the NumPy quantized
    reference driver, and the fused Pallas megakernel (through the
    Pallas interpreter when ``interpret``) all serve the same stream over one trace bank. The three
    quantized paths trace the same integer tick (``repro.fleet.qtick``)
    and must agree EXACTLY on every request-lifecycle counter; the
    float64 reference must agree within the pinned quantization
    tolerance (<=1% or 2 requests on each counter — in practice the
    1 nJ quantum keeps the counts identical; see docs/kernels.md)."""
    power = make_power_matrix(TRACES, min(n_rows, n_workers), duration_s,
                              DT, seed)
    n_steps = int(duration_s / DT)
    rate = n_workers / PERIOD_S
    res = {}
    for name, backend, k in (("f64", "numpy", "xla"),
                             ("numpy_q32", "numpy", "q32"),
                             ("jax_q32", "jax", "q32"),
                             ("jax_kernel", "jax", kernel)):
        res[name] = run_scheduled(power, DT, n_workers, _workloads(),
                                  rate_rps=rate, mix=MIX, n_steps=n_steps,
                                  seed=seed, backend=backend, kernel=k,
                                  interpret=interpret)
    qpaths = ("numpy_q32", "jax_q32", "jax_kernel")
    exact = all(res[a][k] == res[qpaths[0]][k]
                for a in qpaths[1:] for k in _COUNT_KEYS)
    tol = all(abs(res["f64"][k] - res[qpaths[0]][k])
              <= max(2, 0.01 * res["f64"][k]) for k in _COUNT_KEYS)
    return {
        "n_workers": n_workers,
        "duration_s": duration_s,
        "kernel": kernel,
        "quantized_counts_exact": bool(exact),
        "f64_within_tolerance": bool(tol),
        "counts": {b: {k: res[b][k] for k in _COUNT_KEYS} for b in res},
    }


def _strip_run_meta(summary: dict) -> dict:
    """Drop the launcher-provenance keys (which legitimately differ
    between the twin evaluations) and the streaming block (per-chunk
    wall clocks are nondeterministic) so everything else — every
    counter, histogram, quality and energy figure — can be compared
    verbatim."""
    return {k: v for k, v in summary.items()
            if k not in ("mode", "backend", "mesh_fleet", "obs",
                         "stream")}


def _sharded_agreement(n_workers: int, duration_s: float, n_rows: int,
                       mesh_fleet: int, rebalance_every_s: float = 0.0,
                       seed: int = 0, kernel: str = "xla") -> dict:
    """One definition of *sharded* agreement — the three-evaluation
    exactness contract (docs/sharded_fleet.md): the same K-shard serve
    program (K per-shard control planes over contiguous worker blocks,
    deterministic arrival split, optional work-stealing ring) evaluated
    (a) by the NumPy host twin, (b) as a single-device ``vmap`` over
    the shard axis, and (c) as a ``shard_map`` over a real K-device
    mesh (when K devices exist) must produce bit-identical summaries —
    every request/device/quality/latency counter, rebalance off or on.
    Placement never changes bits. Used by the recorded benchmark and
    the CI smoke gate alike so the two cannot drift."""
    import jax

    rows = min(n_rows, n_workers)
    power = make_power_matrix(TRACES, rows, duration_s, DT, seed)
    families = trace_family_labels(TRACES, rows)
    n_steps = int(duration_s / DT)
    rate = n_workers / PERIOD_S
    has_mesh = jax.device_count() >= mesh_fleet
    runs = [("numpy_twin", "numpy", "mesh"),
            ("jax_single", "jax", "single")]
    if has_mesh:
        runs.append(("jax_mesh", "jax", "mesh"))
    res: dict = {}
    wall: dict = {}
    for name, backend, placement in runs:
        t0 = time.perf_counter()
        res[name] = run_scheduled(
            power, DT, n_workers, _workloads(), rate_rps=rate, mix=MIX,
            n_steps=n_steps, seed=seed, backend=backend, sched="forecast",
            trace_families=families, kernel=kernel,
            mesh_fleet=mesh_fleet, rebalance_every_s=rebalance_every_s,
            fleet_placement=placement)
        wall[name] = time.perf_counter() - t0
    blobs = {n: json.dumps(_strip_run_meta(r), sort_keys=True,
                           default=str) for n, r in res.items()}
    agree = all(b == blobs["numpy_twin"] for b in blobs.values())
    return {
        "n_workers": n_workers,
        "duration_s": duration_s,
        "mesh_fleet": mesh_fleet,
        "kernel": kernel,
        "rebalance_every_s": rebalance_every_s,
        "mesh_evaluated": has_mesh,
        "summaries_agree": bool(agree),
        "rebalanced": int(res["numpy_twin"]["rebalanced"]),
        "counts": {n: {k: r[k] for k in _COUNT_KEYS + ("rebalanced",)}
                   for n, r in res.items()},
        "wall_s": wall,
    }


def run_sharded_smoke(n_workers: int = 256, duration_s: float = 30.0,
                      mesh_fleet: int = 8,
                      rebalance_every_s: float = 1.0) -> dict:
    """CI gate for ``--mesh-fleet``: sharded-vs-single-device(-vs-host)
    bit-equality for the xla chain with rebalance off AND on at N=256,
    the quantized q32 kernel with rebalance on at N=256, and a shorter
    xla rebalance-on run at N=1024. The rebalance-on run must actually
    move requests, or the gate would be vacuous.
    Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    to exercise the real shard_map mesh on a CPU-only host."""
    out = {}
    for tag, kernel, reb, n, dur in (
            ("xla_reb_off", "xla", 0.0, n_workers, duration_s),
            ("xla_reb_on", "xla", rebalance_every_s, n_workers,
             duration_s),
            ("q32_reb_on", "q32", rebalance_every_s, n_workers,
             duration_s),
            ("xla_reb_on_1024", "xla", rebalance_every_s, 1024,
             duration_s / 3)):
        r = _sharded_agreement(n, dur, 16, mesh_fleet,
                               rebalance_every_s=reb, kernel=kernel)
        if not r["summaries_agree"]:
            print(json.dumps(r, indent=1), file=sys.stderr)
            raise SystemExit(f"fleet sharded smoke ({tag}) FAILED: "
                             "summaries disagree across evaluations")
        out[tag] = r
        emit(f"fleet.sharded_{tag}_agree", r["wall_s"]["jax_single"] * 1e6,
             str(r["summaries_agree"]))
    if out["xla_reb_on"]["rebalanced"] == 0:
        raise SystemExit("fleet sharded smoke FAILED: the rebalance-on "
                         "run moved no requests (gate is vacuous)")
    return out


def _stream_agreement(n_workers: int, duration_s: float, n_rows: int,
                      chunk_ticks: int, *, backend: str = "jax",
                      kernel: str = "xla", mesh_fleet: int = 1,
                      rebalance_every_s: float = 0.0,
                      fleet_placement: str = "mesh",
                      seed: int = 0) -> dict:
    """Whole-trace vs chunked-stream bit-equality for one config: the
    same pool/scheduler/arrival world served as a single launch and as
    the ``--stream`` chunked steady-state loop (live client thread,
    state carried across chunk boundaries) must produce identical full
    summaries — the tentpole gate of the streaming serve plane."""
    rows = min(n_rows, n_workers)
    power = make_power_matrix(TRACES, rows, duration_s, DT, seed)
    families = trace_family_labels(TRACES, rows)
    n_steps = int(duration_s / DT)
    rate = n_workers / PERIOD_S
    common = dict(rate_rps=rate, mix=MIX, n_steps=n_steps, seed=seed,
                  backend=backend, sched="forecast",
                  trace_families=families, kernel=kernel,
                  mesh_fleet=mesh_fleet,
                  rebalance_every_s=rebalance_every_s,
                  fleet_placement=fleet_placement)
    t0 = time.perf_counter()
    whole = run_scheduled(power, DT, n_workers, _workloads(), **common)
    t1 = time.perf_counter()
    chunked = run_scheduled(power, DT, n_workers, _workloads(),
                            stream_mode=True, chunk_ticks=chunk_ticks,
                            **common)
    t2 = time.perf_counter()
    agree = (json.dumps(_strip_run_meta(whole), sort_keys=True,
                        default=str)
             == json.dumps(_strip_run_meta(chunked), sort_keys=True,
                           default=str))
    return {
        "n_workers": n_workers,
        "duration_s": duration_s,
        "backend": backend,
        "kernel": kernel,
        "mesh_fleet": mesh_fleet,
        "rebalance_every_s": rebalance_every_s,
        "chunk_ticks": chunk_ticks,
        "n_chunks": chunked["stream"]["n_chunks"],
        "summaries_agree": bool(agree),
        "rebalanced": int(whole["rebalanced"]),
        "counts": {n: {k: r[k] for k in _COUNT_KEYS}
                   for n, r in (("whole", whole),
                                ("chunked", chunked))},
        "wall_s": {"whole": t1 - t0, "chunked": t2 - t1},
    }


def run_stream_smoke(n_workers: int = 256, duration_s: float = 30.0,
                     chunk_ticks: int = 700) -> dict:
    """CI gate for ``--stream``: the chunked steady-state loop must be
    bit-exact with the whole-trace launch — on the NumPy host reference
    and the fused jax scan (chunk size NOT dividing the horizon, so the
    remainder chunk is exercised), on the quantized q32 kernel, and on
    the K=8 sharded program with work stealing off AND on (vmap
    placement: no forced-device environment needed)."""
    out = {}
    for tag, kw in (
            ("numpy", dict(backend="numpy")),
            ("jax", dict(backend="jax")),
            ("jax_q32", dict(backend="jax", kernel="q32")),
            ("mesh8_reb_off", dict(backend="jax", mesh_fleet=8,
                                   fleet_placement="single")),
            ("mesh8_reb_on", dict(backend="jax", mesh_fleet=8,
                                  rebalance_every_s=1.0,
                                  fleet_placement="single"))):
        r = _stream_agreement(n_workers, duration_s, 16, chunk_ticks,
                              **kw)
        if not r["summaries_agree"]:
            print(json.dumps(r, indent=1), file=sys.stderr)
            raise SystemExit(f"fleet stream smoke ({tag}) FAILED: "
                             "chunked summary diverged from the "
                             "whole-trace launch")
        out[tag] = r
        emit(f"fleet.stream_{tag}_agree", r["wall_s"]["chunked"] * 1e6,
             str(r["summaries_agree"]))
    if out["mesh8_reb_on"]["rebalanced"] == 0:
        raise SystemExit("fleet stream smoke FAILED: the rebalance-on "
                         "run moved no requests (gate is vacuous)")
    # cross-backend: the chunked numpy and jax runs above also share
    # one arrival world — their discrete counters must match exactly
    a = out["numpy"]["counts"]["chunked"]
    b = out["jax"]["counts"]["chunked"]
    if a != b:
        raise SystemExit(f"fleet stream smoke FAILED: chunked counts "
                         f"disagree across backends ({a} vs {b})")
    return out


def run_persist_smoke(persist: str, n_workers: int = 128,
                      duration_s: float = 30.0) -> dict:
    """CI gate for ``--persist ckpt|undolog``: the NumPy per-tick
    reference and the fused JAX launch serve the same stream under the
    exact persistence discipline and must agree bit-exactly on every
    request-lifecycle counter AND on the persist ledger (FRAM joules,
    checkpoint/commit count, restore count) — on the float64 chain and
    on the int32-quantized q32 kernel. The run must actually persist
    and restore at least once, or the gate would be vacuous."""
    out = {}
    for tag, kernel in (("f64", "xla"), ("q32", "q32")):
        r = _sched_agreement(n_workers, duration_s, 8, sched="forecast",
                             kernel=kernel, persist=persist,
                             grace_s=60.0)
        if not (r["counts_agree"] and r["persist_agree"]):
            print(json.dumps(r, indent=1), file=sys.stderr)
            raise SystemExit(f"fleet persist={persist} smoke ({tag}) "
                             "FAILED: counters or persist ledgers "
                             "disagree across backends")
        out[tag] = r
        emit(f"fleet.persist_{persist}_{tag}_agree", 0.0, "True")
    led = out["f64"]["persist_ledger"]
    if led["persists"] == 0 or led["restores"] == 0:
        raise SystemExit(f"fleet persist={persist} smoke FAILED: no "
                         "checkpoint/commit or restore fired (gate is "
                         "vacuous)")
    return out


def run_smoke(n_workers: int = 256, duration_s: float = 30.0,
              kernel: str = "xla", interpret: bool = False) -> dict:
    """CI gate: short shared trace, both backends, counts must match
    exactly (exercises the scan path on interpret-mode-only hosts) —
    for the local-mode pools, the fused forecast control plane, the
    per-row automatic forecaster selection (regime + OU rows mixed),
    AND the quality scheduler over a real trained-and-measured HAR
    workload (the measured-oracle path). With ``--kernel q32|pallas``
    the gate instead pins the quantized serve-tick paths against each
    other (exact) and against the float64 reference (pinned
    tolerance)."""
    if kernel != "xla":
        if kernel == "pallas":
            require_pallas_target(interpret)
        kres = _quant_agreement(n_workers, duration_s, 16, kernel=kernel,
                                interpret=interpret)
        if not (kres["quantized_counts_exact"]
                and kres["f64_within_tolerance"]):
            print(json.dumps(kres, indent=1), file=sys.stderr)
            raise SystemExit(f"fleet kernel={kernel} smoke FAILED: "
                             "serve counters disagree")
        return {"kernel_agreement": kres}
    res = _backend_agreement(n_workers, duration_s, 16)
    if not res["counts_agree"]:
        print(json.dumps(res, indent=1), file=sys.stderr)
        raise SystemExit("fleet backend smoke FAILED: counts disagree")
    sres = _sched_agreement(64, duration_s, 8, sched="forecast")
    if not sres["counts_agree"]:
        print(json.dumps(sres, indent=1), file=sys.stderr)
        raise SystemExit("fleet scheduler smoke FAILED: counts disagree")
    ares = _sched_agreement(64, duration_s, 8, sched="forecast",
                            forecaster="auto")
    if not ares["counts_agree"]:
        print(json.dumps(ares, indent=1), file=sys.stderr)
        raise SystemExit("fleet forecaster-auto smoke FAILED: "
                         "counts disagree")
    # the measured-quality path: a REAL trained-and-measured HAR
    # workload (per-sample oracle table wired as qtab; CI-sized build)
    # served under the quality scheduler must also agree exactly
    qres = _sched_agreement(
        64, duration_s, 8, sched="quality",
        workloads=[har_workload(real=True), harris_workload(),
                   lm_workload()])
    if not qres["counts_agree"]:
        print(json.dumps(qres, indent=1), file=sys.stderr)
        raise SystemExit("fleet quality-sched (real har) smoke FAILED: "
                         "counts disagree")
    return {"local": res, "sched_forecast": sres,
            "sched_forecast_auto": ares, "sched_quality_real_har": qres}


def run_scheduler_suite() -> dict:
    t0 = time.perf_counter()
    comp = run_comparison()
    t_comp = time.perf_counter() - t0
    t0 = time.perf_counter()
    curve = scaling_curve()
    t_curve = time.perf_counter() - t0

    res = {"comparison": comp, "scaling": curve,
           "host": host_metadata()}
    us = t_comp * 1e6 / 2
    emit("fleet.scheduler_vs_independent_speedup", us,
         f"{comp['speedup_completed']:.2f}x")
    emit("fleet.scheduled_throughput_rps", us,
         f"{comp['scheduled']['throughput_rps']:.1f}")
    emit("fleet.scheduled_mean_expected_accuracy", us,
         f"{comp['scheduled']['mean_expected_accuracy']:.3f}")
    emit("fleet.energy_conservation", us,
         str(comp["scheduled"]["energy"]["conservation_ok"]
             and comp["independent"]["energy"]["conservation_ok"]))
    emit("fleet.scaling_rps_at_1024", t_curve * 1e6 / 3,
         f"{curve['1024']['throughput_rps']:.1f}")
    out = Path("experiments")
    out.mkdir(exist_ok=True)
    (out / "fleet_throughput.json").write_text(
        json.dumps(res, indent=1, default=str))
    return res


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="numpy: scheduler-vs-independent claims; "
                         "jax: backend agreement + >=100k scaling")
    ap.add_argument("--max-workers", type=int, default=131072,
                    help="cap for the jax scaling curve")
    ap.add_argument("--control-plane", action="store_true",
                    help="fused scheduler suite: forecast-vs-reactive + "
                         "host-tick-vs-one-launch scaling table")
    ap.add_argument("--forecaster", choices=FORECASTER_MODES, default="ou",
                    help="forecast model the --control-plane agreement "
                         "check runs under (auto: per-row selection by "
                         "trace family)")
    ap.add_argument("--forecaster-fit", choices=("full", "causal"),
                    default="full",
                    help="forecast-table provenance for the "
                         "--control-plane agreement runs: full fits the "
                         "whole trace bank up front (the offline "
                         "default, which peeks past serve time); causal "
                         "starts from the zero prior and only ever sees "
                         "the observed harvest prefix")
    ap.add_argument("--forecasters", action="store_true",
                    help="forecaster-vs-family completed-requests matrix "
                         "(1024 workers, 600 s, on --backend; counts are "
                         "backend-identical) -> "
                         "experiments/fleet_forecasters.json")
    ap.add_argument("--obs", choices=("off", "tele", "trace"),
                    default="off",
                    help="instrument the --control-plane agreement runs "
                         "with the repro.obs telemetry plane (channels "
                         "must agree bit-exactly across backends)")
    ap.add_argument("--obs-window", type=float, default=1.0,
                    help="telemetry window length in seconds")
    ap.add_argument("--trace-out", default="",
                    help="write the fused run's Perfetto JSON here "
                         "(--obs trace)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI agreement gate (256 workers, 30 s)")
    ap.add_argument("--mesh-fleet", type=int, default=1,
                    help="with --smoke: run the sharded agreement gate "
                         "instead — host-twin / single-device vmap / "
                         "K-device shard_map bit-equality, rebalance "
                         "off and on (needs K forced host devices for "
                         "the mesh evaluation; K must divide workers)")
    ap.add_argument("--rebalance-every", type=float, default=1.0,
                    help="work-stealing cadence in seconds for the "
                         "sharded gate's rebalance-on runs")
    ap.add_argument("--kernel", choices=("xla", "q32", "pallas"),
                    default="xla",
                    help="serve-tick kernel the --smoke gate exercises: "
                         "the float64 XLA chain (xla), the quantized "
                         "int32 XLA twin (q32), or the fused Pallas "
                         "megakernel (pallas; compiled for the TPU "
                         "unless --interpret)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas megakernel through the Pallas "
                         "interpreter (for hosts without a TPU)")
    ap.add_argument("--stream", action="store_true",
                    help="with --smoke: run the streaming gate instead "
                         "— chunked ``--stream`` serve must be "
                         "bit-equal with the whole-trace launch on "
                         "numpy, jax, q32 and the K=8 sharded program "
                         "(rebalance off and on)")
    ap.add_argument("--persist", choices=("none", "ckpt", "undolog"),
                    default="none",
                    help="with --smoke: run the persistence gate "
                         "instead — serve under the exact ckpt/undolog "
                         "discipline (docs/persistence_plane.md) and "
                         "require numpy-vs-jax bit-equality on every "
                         "lifecycle counter and persist ledger, on the "
                         "float64 and q32 kernels")
    args = ap.parse_args(argv)
    if args.smoke:
        if args.persist != "none":
            return run_persist_smoke(args.persist)
        if args.stream:
            return run_stream_smoke()
        if args.mesh_fleet > 1:
            return run_sharded_smoke(
                mesh_fleet=args.mesh_fleet,
                rebalance_every_s=args.rebalance_every)
        return run_smoke(kernel=args.kernel, interpret=args.interpret)
    if args.forecasters:
        return run_forecaster_suite(backend=args.backend)
    if args.control_plane:
        return run_control_plane_suite(forecaster=args.forecaster,
                                       forecaster_fit=args.forecaster_fit,
                                       obs_mode=args.obs,
                                       obs_window_s=args.obs_window,
                                       trace_out=args.trace_out)
    if args.backend == "jax":
        return run_backend_suite(args.max_workers)
    return run_scheduler_suite()


if __name__ == "__main__":
    print(json.dumps(main(), indent=1, default=str))
