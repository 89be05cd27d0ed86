#!/usr/bin/env python3
"""Bring-up smoke for the fused fleet serve scan on a TPU.

Drives the streaming serve through the normal entry point
(``repro.launch.fleet.main --backend jax --scheduler on --stream``) at
131,072 workers, four equal 500-tick chunks with causal forecaster refits
between them, and checks what comes out.

    python chip_smoke.py            # one chip: --kernel q32, pallas, xla
    python chip_smoke.py --mesh 4   # four chips: the sharded q32 serve

One chip: first the dispatch rank sort (``sched._argsort``) runs jitted
on the chip over near-tie float64 keys and must order them as NumPy
does. Then each kernel must complete requests and conserve energy over
four chunk records; ``q32`` and ``pallas`` must agree with each other and
with the NumPy host reference (``--backend numpy``, same arguments). The
float64 ``xla`` kernel must run; whether it agrees with its NumPy
reference is reported (XLA:TPU emulates float64).
``--mesh 4`` runs only the sharded serve (``--mesh-fleet 4``, rebalance
on) on a real four-device mesh and checks it against the NumPy host twin
of the same four-shard program (``--backend numpy``), which the
three-evaluation contract holds bit-equal; the rebalance must move
requests.

Agreement means every discrete summary field (counters, histograms,
labels) is equal and every float field is within a relative 1e-9 (float
accumulators are summed in another order on the device); the per-chunk
wall-clock ``stream`` block is left out, as in the tests. The timings
printed are smoke timings, not benchmark numbers.

Exits non-zero, with no result line, when JAX finds no TPU or any phase
fails. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

WORKERS = 131072
CHUNK_TICKS = 500
N_CHUNKS = 4
SERVE = ["--workers", str(WORKERS), "--scheduler", "on", "--stream",
         "--chunk-ticks", str(CHUNK_TICKS), "--duration", "20",
         "--traces", "RF,SOM,SIM,SOR,SIR", "--sched", "forecast",
         "--forecaster", "auto", "--forecaster-fit", "causal",
         "--refit-every", "5"]
# run labels that differ between the runs being compared
LABELS = ("backend", "kernel")
FLOAT_RTOL = 1e-9


def tpu_device() -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{d.platform!r})", file=sys.stderr)
        sys.exit(1)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def say(msg: str) -> None:
    """One line on the real stdout: the launcher's JSON dumps are
    redirected away while the phases run."""
    print(msg, file=sys.__stdout__, flush=True)


def serve(*extra: str) -> dict:
    """One streaming serve through the launcher; returns its summary."""
    from repro.launch.fleet import main
    return main(SERVE + list(extra))["scheduled"]


def check_run(name: str, s: dict) -> None:
    chunks = s["stream"]["chunks"]
    walls = [c["wall_s"] for c in chunks]
    say(f"{name}: completed={s['completed']} submitted={s['submitted']} "
        f"smoke timing (not a benchmark): first chunk {walls[0]:.2f} s "
        f"(with any compile), later ones "
        f"{sum(walls[1:]) / max(len(walls) - 1, 1):.3f} s/chunk "
        f"of {CHUNK_TICKS} ticks")
    if s["completed"] <= 0:
        raise SystemExit(f"{name}: no request completed")
    if not s["energy"]["conservation_ok"]:
        raise SystemExit(f"{name}: energy conservation failed")
    if len(chunks) != N_CHUNKS or any(c["ticks"] != CHUNK_TICKS
                                      for c in chunks):
        raise SystemExit(f"{name}: expected {N_CHUNKS} chunks of "
                         f"{CHUNK_TICKS} ticks, got "
                         f"{[c['ticks'] for c in chunks]}")


def _leaves(x, path=""):
    if isinstance(x, dict):
        for k in sorted(x):
            yield from _leaves(x[k], f"{path}/{k}")
    elif isinstance(x, (list, tuple)):
        for i, v in enumerate(x):
            yield from _leaves(v, f"{path}[{i}]")
    else:
        yield path, x


def differences(a: dict, b: dict) -> tuple[list[str], float]:
    """The summary fields where ``a`` and ``b`` disagree (discrete fields
    unequal, float fields beyond FLOAT_RTOL), and the largest relative
    float deviation."""
    a = {k: v for k, v in a.items() if k not in LABELS + ("stream",)}
    b = {k: v for k, v in b.items() if k not in LABELS + ("stream",)}
    la, lb = dict(_leaves(a)), dict(_leaves(b))
    bad = [f"{k}: only in one summary" for k in sorted(la.keys() ^ lb.keys())]
    worst = 0.0
    for k in sorted(la.keys() & lb.keys()):
        x, y = la[k], lb[k]
        if isinstance(x, float) or isinstance(y, float):
            if x == y:
                continue
            rel = abs(x - y) / max(abs(x), abs(y))
            worst = max(worst, rel)
            if not rel <= FLOAT_RTOL:
                bad.append(f"{k}: {x!r} != {y!r} (rel {rel:.3g})")
        elif x != y or type(x) is not type(y):
            bad.append(f"{k}: {x!r} != {y!r}")
    return bad, worst


def compare(name_a: str, a: dict, name_b: str, b: dict) -> None:
    """Fails unless the two summaries agree."""
    bad, worst = differences(a, b)
    if bad:
        raise SystemExit(f"{name_a} vs {name_b} differ:\n  "
                         + "\n  ".join(bad))
    say(f"{name_a} == {name_b}: discrete summary fields equal, largest "
        f"float deviation {worst:.3g}")


def timed_serve(name: str, *extra: str) -> dict:
    say(f"{name}: starting")
    t0 = time.perf_counter()
    s = serve(*extra)
    say(f"{name}: {time.perf_counter() - t0:.1f} s in all")
    check_run(name, s)
    return s


def order_key_check() -> None:
    """The fleet-wide dispatch sort on the chip: ``sched._argsort``
    jitted over WORKERS near-tie float64 keys (one-ulp neighbours, exact
    ties, 0.0 against -0.0, invalid entries) must give NumPy's stable
    argsort of the values the device holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fleet import sched as S
    rng = np.random.default_rng(0)
    n = WORKERS
    x = rng.uniform(0, 1e-2, n) * 10.0 ** rng.integers(-8, 3, n)
    k = n // 3
    x[rng.integers(0, n, k)] = np.nextafter(
        x[rng.integers(0, n, k)], np.inf * rng.choice([-1, 1], k))
    x[rng.integers(0, n, 50)] = 0.0
    x[rng.integers(0, n, 50)] = -0.0
    x[rng.integers(0, n, 100)] = x[rng.integers(0, n, 100)]
    x = np.where(rng.random(n) < 0.5, x, -x)
    valid = rng.random(n) < 0.8
    with jax.enable_x64(True):
        xd = jnp.asarray(x)
        got = np.asarray(jax.jit(lambda a, v: S._argsort(a, v, jnp))(
            xd, jnp.asarray(valid)))
        held = np.asarray(xd)
    if not np.array_equal(got, S._argsort(held, valid, np)):
        raise SystemExit("order key: the chip's argsort differs from "
                         "NumPy's on near-tie float64 keys")
    same = np.array_equal(held.view(np.int64), x.view(np.int64))
    say(f"order key: chip argsort == NumPy stable argsort on {n} near-tie "
        f"float64 keys; float64 host->chip->host round trip "
        f"{'bit-exact' if same else 'NOT bit-exact'}")


def one_chip() -> None:
    order_key_check()
    q32 = timed_serve("jax/q32", "--backend", "jax", "--kernel", "q32")
    pallas = timed_serve("jax/pallas", "--backend", "jax", "--kernel",
                         "pallas")
    compare("jax/q32", q32, "jax/pallas", pallas)
    ref = timed_serve("numpy/q32", "--backend", "numpy", "--kernel", "q32")
    compare("jax/q32", q32, "numpy/q32", ref)
    # the float64 chain: XLA:TPU emulates float64, so agreement with the
    # IEEE host reference is reported, not required
    f64 = timed_serve("jax/xla", "--backend", "jax", "--kernel", "xla")
    ref = timed_serve("numpy/xla", "--backend", "numpy", "--kernel", "xla")
    bad, worst = differences(f64, ref)
    say(f"float64 verdict: jax/xla {'differs from' if bad else 'equals'} "
        f"numpy/xla ({len(bad)} fields differ, largest float deviation "
        f"{worst:.3g})")
    for line in bad[:20]:
        say(f"  {line}")


def four_chips(k: int) -> None:
    sharded = ["--kernel", "q32", "--mesh-fleet", str(k),
               "--rebalance-every", "1"]
    # the backend raises if the mesh placement's outputs span fewer than
    # k devices
    mesh = timed_serve(f"jax/q32/mesh-fleet {k}", "--backend", "jax",
                       *sharded, "--fleet-placement", "mesh")
    if mesh["rebalanced"] <= 0:
        raise SystemExit("the rebalance moved no requests")
    twin = timed_serve(f"numpy/q32/mesh-fleet {k}", "--backend", "numpy",
                       *sharded)
    compare(f"jax/q32/mesh-fleet {k}", mesh, f"numpy/q32/mesh-fleet {k}",
            twin)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", type=int, default=0,
                    help="run only the sharded serve over this many chips "
                         "(4 on a v5e host), against its NumPy host twin")
    args = ap.parse_args(argv)
    dev = tpu_device()
    say(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})")
    from repro.launch.fleet import init_compile_cache
    say(f"compile cache: {init_compile_cache()}")
    if args.mesh and dev["count"] < args.mesh:
        raise SystemExit(f"--mesh {args.mesh} needs {args.mesh} chips, "
                         f"found {dev['count']}")
    with contextlib.redirect_stdout(io.StringIO()):
        if args.mesh:
            four_chips(args.mesh)
        else:
            one_chip()
    say(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
