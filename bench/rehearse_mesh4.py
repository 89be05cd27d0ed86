#!/usr/bin/env python3
"""Rehearse, without a chip, the compile of a four-chip sharded serve.

Builds the configuration's pool and scheduler with the launcher's
build functions (``--mesh-fleet 4 --rebalance-every 1``), then compiles the
program's sharded chunk program for a described ``v5e:2x2`` host and
prints its compile seconds and per-device ``memory_analysis()``:

    JAX_PLATFORMS=cpu python bench/rehearse_mesh4.py --workers 524288

Nothing runs: this says whether XLA:TPU accepts the program and what it
holds per chip, not how fast it is.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", type=int, default=524288)
    ap.add_argument("--config", default="fleet131k_q32")
    ap.add_argument("--shards", type=int, default=4)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import deploy
    from repro.fleet import sched as FS
    from repro.fleet.scheduler import FleetScheduler
    from repro.launch import fleet as L
    from repro.fleet.backend_jax import JaxFleetBackend
    from repro.fleet.state import sched_state_as_tuple, state_as_tuple
    from repro.sharding import context
    jax.config.update("jax_enable_compilation_cache", False)

    config = json.loads((BENCH / "configs" / f"{args.config}.json")
                        .read_text())
    config.update(workers=args.workers)
    K = args.shards
    dt = float(config["dt_s"])
    workloads = [L.WORKLOAD_FACTORIES[n]() for n in config["workloads"]]
    pool = L.build_dispatch_pool(
        deploy.power_matrix(config), dt, args.workers, workloads, 0,
        backend="jax", kernel=config["kernel"])
    sched = FleetScheduler(
        pool, workloads, max_batch=int(config["max_batch"]),
        grace_s=float(config["grace_s"]),
        shed_after_s=float(config["shed_after_s"]), sched=config["sched"],
        shards=K, rebalance_every=int(round(1.0 / dt)))
    sp = sched.params
    ck = int(config["chunk_ticks"])

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(topo.devices[:K], (context.FLEET_AXIS,))
    context.make_fleet_mesh = lambda k: mesh  # the described devices
    ns = args.workers // K

    def resh(x):
        a = np.asarray(x)
        return a.reshape((K, ns) + a.shape[1:])

    with jax.enable_x64(True):
        be = JaxFleetBackend(pool.params, kernel=config["kernel"],
                             fleet_placement="mesh")
        counts = FS.split_counts(np.zeros((ck, sp.W), np.int64), K)
        sh = {"fs": tuple(resh(x) for x in state_as_tuple(pool.state)),
              "ss": tuple(sched_state_as_tuple(sched.state)),
              "arr": counts, **be._worker_inputs(sp, resh)}
        shard = NamedSharding(mesh, P(context.FLEET_AXIS))
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=shard), sh)
        i0 = jax.ShapeDtypeStruct((), "int64",
                                  sharding=NamedSharding(mesh, P()))
        fn = be._build_serve_sharded(sp, ck, int(config["dispatch_every"]),
                                     None, True)
        t0 = time.perf_counter()
        compiled = fn.lower(shapes, i0).compile()
        secs = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    out = {"workers": args.workers, "shards": K, "chunk_ticks": ck,
           "compile_s": secs,
           "per_device_bytes": {
               "arguments": ma.argument_size_in_bytes,
               "outputs": ma.output_size_in_bytes,
               "temporaries": ma.temp_size_in_bytes,
               "code": ma.generated_code_size_in_bytes},
           "collectives": sorted({op for op in ("all-reduce",
                                                "collective-permute")
                                  if op in compiled.as_text()})}
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
