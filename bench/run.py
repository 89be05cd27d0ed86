#!/usr/bin/env python3
"""One run of one benchmark cell of the streaming fleet serve.

    python bench/run.py --workload fleet131k_q32_cold.poisson10s --seed 7 \
        --seconds 30 --trace 0

Reads the cell from ``BENCHMARK.json`` (its configuration and traffic
files, ``bench/cell.py``), draws the arrival rows from ``--seed``, builds
and warms the ``--backend jax --scheduler on`` pool and scheduler with the
launcher's build functions, and serves one window of whole chunks lasting about
``--seconds`` through the program's ``run_fleet_stream``
(``bench/serve.py``). Then it replays the window (its first
``reference_ticks`` ticks) on the plain reference and compares
(``bench/reference.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` serves
a short stretch of the configuration's ``trace_chunks`` chunks under the
profiler and reports the per-layer metrics read from that trace
(``bench/trace_reduce.py``, ``bench/metrics/``).

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where a ``--trace 1`` run's trace holds no
window span or no device operation. The last lines on standard error are the numbers
compared, each with its limit; the last line on standard output is the
result as one JSON object.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def setup_environment() -> None:
    """Import paths, and the persistent compile cache at a fixed directory
    inside the checkout (set before JAX is imported)."""
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def find_chips(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX finds "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default="",
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    return ap.parse_args(argv)


def run(argv=None, *, spec_path: Path = ROOT / "BENCHMARK.json",
        need_chip: bool = True, t_process: float = T_PROCESS) -> dict:
    """One run; returns the result object (also printed). ``need_chip``
    False drives the run on whatever JAX finds (tests on the CPU)."""
    args = parse(argv)
    setup_environment()
    from cell import load_cell, reader
    cell = load_cell(args.workload, spec_path)
    if need_chip:
        device = find_chips(cell.chips)
    else:
        import jax
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": jax.device_count()}
    from repro.launch.fleet import init_compile_cache
    init_compile_cache()
    import deploy
    import reference as R
    import serve as S
    from traffic import arrival_rows

    cfg = cell.config
    power = deploy.power_matrix(cfg)
    rows = arrival_rows(cell.traffic, int(cfg["workers"]), cfg["mix"],
                        deploy.bank_ticks(cfg), float(cfg["dt_s"]),
                        args.seed + 1)
    compiles = S.CompileCount()
    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
    w = S.serve(cfg, args.seed, rows, power, args.seconds, compiles,
                trace_dir)
    got = (R.program_records(w.summary, w.checked_chunks),
           R.program_state(*w.checked_states))
    summary = w.summary
    del w.summary, w.checked_states
    gc.collect()  # the program's pool and device arrays are gone
    reduced = None
    if trace_dir:
        from trace_reduce import reduce_dir
        reduced = reduce_dir(trace_dir)
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None:
            raise SystemExit("bench: the trace holds no bench.window span "
                             "or no device operation inside it")
    t_ref = time.perf_counter()
    ref = R.replay(cfg, args.seed, rows,
                   w.checked_chunks * int(cfg["chunk_ticks"]), power=power)
    cmp = R.compare(got, ref, float(cfg["dt_s"]))
    t_ref = time.perf_counter() - t_ref

    checks = {"compiles_in_window": (w.compiles, R.LIMITS["compiles"]),
              "counter_mismatches": (cmp.counter_mismatches,
                                     R.LIMITS["counter_mismatches"]),
              "float_rel_dev": (cmp.float_rel_dev,
                                R.LIMITS["float_rel_dev"])}
    correct = all(v <= lim for v, lim in checks.values())

    view = types.SimpleNamespace(
        workers=int(cfg["workers"]), chunk_ticks=int(cfg["chunk_ticks"]),
        ticks=w.ticks, stamps=w.stamps, t_end=w.t_end, t_process=t_process,
        trace=reduced)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = reader(m["name"])(view)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device["memory_peak_bytes"] = w.memory_peak_bytes
    out = {"correct": correct, "attempted": w.offered, "failed": 0,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in
                                           reduced.device_ops],
                            "idle_gaps": [list(x) for x in
                                          reduced.idle_gaps]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}

    s = summary
    log(f"bench: {args.workload} seed {args.seed}: {w.chunks} chunks of "
        f"{cfg['chunk_ticks']} ticks at {cfg['workers']} workers; "
        f"submitted {s['submitted']} completed {s['completed']} shed "
        f"{s['shed']} rejected {s['rejected']} evicted {s['evicted']} "
        f"requeued {s['requeued']}; "
        f"reference replay of the first {w.checked_chunks} chunks "
        f"{t_ref:.1f} s")
    for line in cmp.first:
        log(f"bench: differs: {line}")
    for k, (v, lim) in checks.items():
        log(f"check {k} {v!r} limit {lim!r}")
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    run()
