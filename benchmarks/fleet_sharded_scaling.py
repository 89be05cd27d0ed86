"""Sharded serve scaling: millions of workers, one logical launch.

Claims checked (see docs/sharded_fleet.md):
- the sharded serve scan (``--mesh-fleet K``) carries one *logical*
  launch to >=1M workers: the worker-scaling curve records warm
  ticks/s and worker-ticks/s per fleet size for K=1 (the unsharded
  scan) and K=8 (shard_map over a forced-host-device CPU mesh: before
  jax is imported the benchmark sets
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, which only the
  CPU platform reads; K never exceeds the devices that exist, so a
  four-chip TPU host runs K=4);
- cross-shard work stealing earns its keep on a *skewed* fleet: with
  shards 0..K/2-1 pinned to occluded mobile solar (SIM) and the rest
  to rich outdoor solar (SOR), the rebalance-on run completes more
  requests than rebalance-off (queued requests flow around the shard
  ring from backlogged occluded shards to energy-rich ones); the
  completed-request delta is recorded either way.

    python -m benchmarks.fleet_sharded_scaling            # full curve
    python -m benchmarks.fleet_sharded_scaling --smoke    # quick CI look

JSON lands in experiments/fleet_sharded_scaling.json; docs/experiments.md
documents the schema. Results are bit-identical across placements (the
throughput suite's sharded smoke gates that); this suite measures only
wall clock and the rebalance delta.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

SIZES = (16384, 131072, 1048576)
MESHES = (1, 8)


def _mesh_sizes(k: int) -> tuple[int, ...]:
    """The mesh sizes to measure: 1 and up to ``k`` devices. XLA fixes
    the host device count when its backend starts, so K forced CPU
    devices are requested only while jax is not yet imported; the
    flag is ignored by accelerator platforms. Never asks for more
    devices than exist."""
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={k}"
                .strip())
    import jax
    return tuple(sorted({1, min(k, jax.device_count())}))


def scaling_curve(sizes=SIZES, meshes=MESHES, duration_s: float = 1.0,
                  iters: int = 2, seed: int = 0,
                  kernel: str = "xla") -> dict:
    """Warm wall-clock of the fused serve launch per (fleet size, mesh
    size): the same program, K=1 single-device vs K-way shard_map."""
    from benchmarks.common import timeit_split
    from benchmarks.fleet_megakernel import _serve_runner

    n_steps = int(duration_s / 0.01)
    res: dict = {}
    for n in sizes:
        per: dict = {}
        for k in meshes:
            run, out = _serve_runner(n, duration_s, kernel, seed,
                                     mesh_fleet=k)
            split = timeit_split(run, iters=iters)
            split["completed"] = out["summary"]["completed"]
            split["ticks_per_s"] = n_steps / max(split["warm_s"], 1e-9)
            split["worker_ticks_per_s"] = (n * n_steps
                                           / max(split["warm_s"], 1e-9))
            per[str(k)] = split
        base = per[str(meshes[0])]["warm_s"]
        per["speedup_over_first_mesh_warm"] = {
            str(k): base / max(per[str(k)]["warm_s"], 1e-9)
            for k in meshes}
        res[str(n)] = per
    return res


def rebalance_delta(n: int = 1024, k: int = 8, duration_s: float = 60.0,
                    rebalance_every_s: float = 1.0, seed: int = 0) -> dict:
    """Completed-request delta of cross-shard work stealing on an
    occlusion-skewed fleet: shards 0..K/2-1 harvest occluded mobile
    solar (SIM), shards K/2..K-1 rich outdoor solar (SOR) — same
    stream, same workers, only the rebalance cadence changes. The
    shards run on a K-device mesh, so K must not exceed the devices."""
    import numpy as np

    from benchmarks.fleet_throughput import DT, MIX, PERIOD_S, _workloads
    from repro.fleet.scheduler import (FleetScheduler, RequestStream,
                                      run_fleet)
    from repro.fleet.worker import FleetWorkerPool
    from repro.launch.fleet import make_power_matrix

    fams = ["SIM"] * (k // 2) + ["SOR"] * (k - k // 2)
    power = make_power_matrix(fams, k, duration_s, DT, seed)
    n_steps = int(duration_s / DT)
    wls = _workloads()
    rng = np.random.default_rng(seed)
    phase = rng.integers(0, power.shape[1], n)
    out: dict = {"n_workers": n, "mesh_fleet": k,
                 "duration_s": duration_s,
                 "rebalance_every_s": rebalance_every_s,
                 "shard_families": fams}
    for tag, reb in (("off", 0),
                     ("on", int(round(rebalance_every_s / DT)))):
        pool = FleetWorkerPool(
            power, DT, workloads=[w.costs for w in wls], mode="dispatch",
            n_workers=n, trace_index=np.repeat(np.arange(k), n // k),
            phase=phase, backend="jax")
        sched = FleetScheduler(pool, wls, sched="forecast",
                               trace_families=fams, shards=k,
                               rebalance_every=reb)
        stream = RequestStream(n / PERIOD_S, MIX, n_steps, DT,
                               seed=seed + 1)
        s = run_fleet(pool, sched, stream, n_steps)
        out[tag] = {key: s[key] for key in
                    ("submitted", "completed", "shed", "lost",
                     "requeued", "rebalanced", "latency_p95_s")}
    out["completed_delta"] = (out["on"]["completed"]
                              - out["off"]["completed"])
    out["stealing_helps"] = bool(out["completed_delta"] > 0)
    return out


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", default=",".join(str(s) for s in SIZES),
                    help="comma-separated fleet sizes for the curve")
    ap.add_argument("--duration", type=float, default=1.0,
                    help="simulated seconds per timed run "
                         "(ticks = duration/0.01)")
    ap.add_argument("--iters", type=int, default=2,
                    help="warm repeats per cell")
    ap.add_argument("--smoke", action="store_true",
                    help="quick look: 4096 workers, rebalance delta at "
                         "N=512 over 30 simulated seconds")
    args = ap.parse_args(argv or sys.argv[1:])
    meshes = _mesh_sizes(max(MESHES))

    from benchmarks.common import emit, host_metadata

    sizes = ((4096,) if args.smoke
             else tuple(int(s) for s in args.sizes.split(",")))
    t0 = time.perf_counter()
    curve = scaling_curve(sizes, meshes, args.duration, args.iters)
    # work stealing needs two shards; each runs on a device of its own
    kmax = max(meshes)
    delta = (None if kmax < 2
             else rebalance_delta(512, kmax, 30.0) if args.smoke
             else rebalance_delta(k=kmax))
    total = time.perf_counter() - t0
    res = {"scaling": curve, "rebalance": delta,
           "mesh_sizes": list(meshes), "duration_s": args.duration,
           "host": host_metadata()}
    us = total * 1e6 / max(len(sizes) * len(meshes) + 2, 1)
    top = str(max(int(x) for x in curve))
    for k in meshes:
        emit(f"fleet.sharded_worker_ticks_per_s_at_{top}_k{k}", us,
             f"{curve[top][str(k)]['worker_ticks_per_s']:.2e}")
    if delta is not None:
        emit("fleet.sharded_rebalance_completed_delta", us,
             str(delta["completed_delta"]))
    if not args.smoke:
        out = Path("experiments")
        out.mkdir(exist_ok=True)
        (out / "fleet_sharded_scaling.json").write_text(
            json.dumps(res, indent=1, default=str))
    return res


if __name__ == "__main__":
    print(json.dumps(main(), indent=1, default=str))
