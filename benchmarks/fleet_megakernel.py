"""Serve-tick megakernel benchmark: quantized kernels vs the float64
XLA scan on the fused serve path.

Claims checked:
- the int32-quantized serve tick (``kernel=q32`` — the same integer
  numerics the Pallas megakernel runs, traced as pure XLA) beats the
  float64 expression chain warm once the fleet is large enough for the
  array work to dominate the launch/dispatch overhead (16384+ workers):
  fewer/narrower HBM round-trips per tick (int32 halves the bytes, the
  integer tick drops the sqrt/x**2 voltage<->energy conversions); at
  1024 workers the two are within noise of each other;
- the fused Pallas megakernel (``kernel=pallas``) agrees with the
  quantized scan EXACTLY on every request/device counter (the smoke
  gate pins this; on CPU it runs through the Pallas interpreter, so its
  wall-clock here is a correctness artifact, not the TPU number — the
  interpreter serializes the grid loop);
- the serve tick's roofline entry (benchmarks/roofline.py
  ``serve_tick_roofline``): bytes-touched vs integer ops per
  (block_rows, 128) tile put the kernel far below the v5e ridge, i.e.
  memory-bound, which is why fusing the ~70-op jnp chain into one
  VMEM-resident pass is the right lever.

    python -m benchmarks.fleet_megakernel                # full gate, TPU
    python -m benchmarks.fleet_megakernel --interpret --sizes 1024
                                             # quick look without a TPU

JSON lands in experiments/fleet_megakernel.json; docs/experiments.md
documents the schema, docs/kernels.md the dtype/quantization contract.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from benchmarks.common import (emit, host_metadata,
                               require_pallas_target, timeit_split)
from benchmarks.fleet_throughput import (DT, MIX, PERIOD_S, TRACES,
                                         _quant_agreement, _workloads)
from benchmarks.roofline import serve_tick_roofline
from repro.launch.fleet import make_power_matrix

SIZES = (1024, 16384, 131072)
KERNELS = ("xla", "q32", "pallas")


def _serve_runner(n: int, duration_s: float, kernel: str, seed: int = 0,
                  charge_frac: float = 0.9, mesh_fleet: int = 1,
                  interpret: bool = False):
    """A zero-arg callable running the full fused serve launch; reset
    between calls so every invocation after the first is the warm
    compiled scan over fresh state.

    Capacitors start at ``charge_frac`` of full (cold-start charge-up
    takes >10 simulated seconds at these harvest rates, which would
    leave the acquisition/progression/emit branches of the tick dead for
    the whole horizon — the timing must exercise the full kernel, not
    just harvest+dispatch)."""
    import numpy as np

    from repro.fleet.sched import make_sched_state
    from repro.fleet.scheduler import FleetScheduler, RequestStream, \
        run_fleet
    from repro.launch.fleet import build_dispatch_pool

    n_steps = int(duration_s / DT)
    power = make_power_matrix(TRACES, min(32, n), duration_s, DT, seed)
    wls = _workloads()
    pool = build_dispatch_pool(power, DT, n, wls, seed, backend="jax",
                               kernel=kernel, interpret=interpret)
    sched = FleetScheduler(pool, wls, sched="reactive",
                           shards=mesh_fleet)
    stream = RequestStream(n / PERIOD_S, MIX, n_steps, DT, seed=seed + 1)
    if kernel == "xla":
        # float64 state holds volts; sqrt so the stored ENERGY fraction
        # (E ∝ v²) matches the quantized fixture below
        v0 = np.broadcast_to(np.asarray(pool.params.v_max, np.float64)
                             * charge_frac ** 0.5, (n,)).copy()
    else:
        # quantized state holds int32 energy quanta
        from repro.fleet.qtick import quantize_fleet_cached
        qp = quantize_fleet_cached(pool.params)
        v0 = np.broadcast_to(
            (np.asarray(qp.E_MAX, np.int64)
             * charge_frac).astype(np.int32), (n,)).copy()
    out = {}

    def run():
        pool.reset()
        pool.state.v = v0.copy()
        sched.state = make_sched_state(sched.params)
        out["summary"] = run_fleet(pool, sched, stream, n_steps)

    return run, out


def _serve_tick_fixture(n: int, seed: int = 0, *, interpret: bool):
    """One-tick fixture for the kernel sweep (benchmarks/bench_kernels):
    a charged quantized fleet mid-serve. Returns zero-arg callables
    running one Pallas tick (through the interpreter if ``interpret``)
    and one jitted q32-twin tick over the same state, plus their
    exact-agreement bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fleet import qtick as Q
    from repro.fleet.backend_jax import JaxFleetBackend
    from repro.fleet.state import STATE_FIELDS
    from repro.launch.fleet import build_dispatch_pool

    power = make_power_matrix(TRACES, min(32, n), 10.0, DT, seed)
    pool = build_dispatch_pool(power, DT, n, _workloads(), seed,
                               backend="jax", kernel="pallas")
    rng = np.random.default_rng(seed)
    s = pool.state
    qp = Q.quantize_fleet_cached(pool.params)
    s.v = rng.integers(0, np.asarray(qp.E_MAX) + 1, n).astype(np.int32)
    s.on = s.v >= np.asarray(qp.E_ON)
    s.p_pending = s.on & (rng.random(n) < 0.5)
    s.p_wl = rng.integers(0, 3, n).astype(np.int32)
    s.p_units = rng.integers(1, 4, n).astype(np.int32)
    s.p_batch = rng.integers(1, 4, n).astype(np.int32)

    bk_p = JaxFleetBackend(pool.params, kernel="pallas",
                           interpret=interpret)
    bk_q = JaxFleetBackend(pool.params, kernel="q32")
    with jax.enable_x64(True):
        st = tuple(jnp.asarray(getattr(s, f)) for f in STATE_FIELDS)
        ev0 = tuple(jnp.zeros(n, jnp.int32) for _ in range(4))
        i = jnp.asarray(7, jnp.int64)
        tq = jax.jit(lambda st, ev: bk_q._tick_q(st, ev, i))

    def tick_pallas():
        with jax.enable_x64(True):
            return bk_p._tick_pallas(st, ev0, i)

    def tick_q32():
        with jax.enable_x64(True):
            return tq(st, ev0)

    (st_p, ev_p), (st_q, ev_q) = tick_pallas(), tick_q32()
    agree = all(bool((np.asarray(a) == np.asarray(b)).all())
                for a, b in list(zip(st_p, st_q)) + list(zip(ev_p, ev_q)))
    return tick_pallas, tick_q32, bool(agree)


def kernel_scaling(sizes=SIZES, duration_s: float = 10.0,
                   iters: int = 2, seed: int = 0,
                   mesh_fleet: int = 1, interpret: bool = False) -> dict:
    """Warm wall-clock per kernel per fleet size (cold includes the
    one-off serve-scan trace+compile). ``mesh_fleet > 1`` shards the
    serve scan K ways (docs/sharded_fleet.md) — the Pallas megakernel
    column drops out there, since it tiles a single-device worker
    axis."""
    kernels = KERNELS if mesh_fleet == 1 else ("xla", "q32")
    res: dict = {}
    for n in sizes:
        per: dict = {}
        for kernel in kernels:
            run, out = _serve_runner(n, duration_s, kernel, seed,
                                     mesh_fleet=mesh_fleet,
                                     interpret=interpret)
            split = timeit_split(run, iters=iters)
            split["completed"] = out["summary"]["completed"]
            per[kernel] = split
        per["q32_over_xla_warm"] = (per["xla"]["warm_s"]
                                    / max(per["q32"]["warm_s"], 1e-9))
        if "pallas" in per:
            per["pallas_over_xla_warm"] = (per["xla"]["warm_s"]
                                           / max(per["pallas"]["warm_s"],
                                                 1e-9))
        res[str(n)] = per
    return res


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", default=",".join(str(s) for s in SIZES),
                    help="comma-separated fleet sizes")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="simulated seconds per run (ticks = duration/dt)")
    ap.add_argument("--iters", type=int, default=2,
                    help="warm repeats per cell")
    ap.add_argument("--mesh-fleet", type=int, default=1,
                    help="shard the timed serve scans K ways over the "
                         "fleet mesh (drops the single-device Pallas "
                         "column; K must divide every --sizes entry)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernel through the Pallas "
                         "interpreter (a correctness artifact off the "
                         "TPU, not a kernel timing); without it the "
                         "benchmark refuses to run where JAX finds no "
                         "TPU")
    args = ap.parse_args(argv)
    sizes = tuple(int(s) for s in args.sizes.split(","))
    require_pallas_target(args.interpret)

    t0 = time.perf_counter()
    agree = _quant_agreement(256, 30.0, 16, kernel="pallas",
                             interpret=args.interpret)
    scaling = kernel_scaling(sizes, args.duration, args.iters,
                             mesh_fleet=args.mesh_fleet,
                             interpret=args.interpret)
    total = time.perf_counter() - t0

    res = {
        "agreement": agree,
        "scaling": scaling,
        "roofline": [serve_tick_roofline(n) for n in sizes],
        "quantization": {
            "quantum_j": 1e-9,
            "state_dtype": "int32",
            "contract": "three quantized paths (numpy q32 / jax q32 / "
                        "jax pallas) bit-exact; float64 reference within "
                        "<=1% or 2 requests per lifecycle counter",
        },
        "pallas_note": "CPU wall-clock runs the Pallas interpreter "
                       "(serialized grid loop) and is recorded for "
                       "completeness only; the compiled TPU kernel is "
                       "the fast path. q32-over-xla is the honest "
                       "measured CPU speedup of the quantized tick.",
        "duration_s": args.duration,
        "mesh_fleet": args.mesh_fleet,
        "pallas_interpret": args.interpret,
        "host": host_metadata(),
    }
    us = total * 1e6 / max(len(sizes) * len(KERNELS), 1)
    emit("fleet.megakernel_counts_exact", us,
         str(agree["quantized_counts_exact"]))
    emit("fleet.megakernel_f64_within_tol", us,
         str(agree["f64_within_tolerance"]))
    for n in sizes:
        emit(f"fleet.q32_over_xla_warm_at_{n}", us,
             f"{scaling[str(n)]['q32_over_xla_warm']:.2f}x")
    rl = res["roofline"][-1]
    emit("fleet.serve_tick_roofline_bound", us,
         f"{rl['bound']}@{rl['arithmetic_intensity_ops_per_byte']:.1f}"
         f"ops/B")
    out = Path("experiments")
    out.mkdir(exist_ok=True)
    (out / "fleet_megakernel.json").write_text(
        json.dumps(res, indent=1, default=str))
    return res


if __name__ == "__main__":
    print(json.dumps(main(), indent=1, default=str))
