"""Shared benchmark fixtures: trained HAR model, cost/accuracy tables."""
from __future__ import annotations

import functools
import time

import numpy as np

import jax.numpy as jnp


@functools.lru_cache(maxsize=1)
def har_fixture(n_train: int = 120, n_test: int = 60, seed: int = 0):
    """(model, F_test, y_test, cost_table, accuracy_table, classify_ok)."""
    from repro.core import anytime_svm as asvm
    from repro.core import profile_tables as pt
    from repro.data import har

    Xw_tr, ytr = har.generate_windows(n_train, seed=seed)
    Xw_te, yte = har.generate_windows(n_test, seed=seed + 1)
    Ftr = np.asarray(har.extract_features(jnp.asarray(Xw_tr)))
    Fte = np.asarray(har.extract_features(jnp.asarray(Xw_te)))
    model = asvm.train_ovr_svm(Ftr, ytr, 6)
    costs = pt.har_cost_table(har.FEATURE_FAMILIES, model.order, scale=90.0)
    acc_tab = asvm.accuracy_table(model, Fte, yte, np.arange(141))
    Xo = model.standardize(Fte)[:, model.order]
    Wo = model.W[:, model.order]

    def classify_ok(sample_id: int, p: int) -> bool:
        i = sample_id % len(yte)
        return bool((Xo[i, :p] @ Wo[:, :p].T + model.b).argmax() == yte[i])

    return model, Fte, yte, costs, acc_tab, classify_ok


def timeit(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median wall-clock microseconds per call (jax arrays blocked)."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def timed(fn, *args):
    """``(result, wall_seconds)`` for one call (jax results blocked)."""
    import jax

    t0 = time.perf_counter()
    res = fn(*args)
    try:
        jax.block_until_ready(res)
    except TypeError:  # plain-python result (dicts of host scalars)
        pass
    return res, time.perf_counter() - t0


def timeit_split(fn, *args, iters: int = 5) -> dict:
    """Cold/warm wall-clock split for a compiled callable.

    The first call (compile + run) is reported as ``cold_s``; the
    subsequent ``iters`` calls give ``warm_s`` (median) plus the
    per-repeat spread — ``warm_s_min``/``warm_s_mean``/``warm_s_std``
    (population std-dev) — the uniform shape every fleet benchmark
    reports (see docs/benchmarks.md). The min is the least-noise
    estimate on a shared machine; median vs mean exposes stragglers.
    """
    _, cold = timed(fn, *args)
    ws = [timed(fn, *args)[1] for _ in range(iters)]
    import statistics

    return {"cold_s": cold, "warm_s": float(np.median(ws)),
            "warm_s_min": float(np.min(ws)),
            "warm_s_mean": float(np.mean(ws)),
            "warm_s_std": (statistics.pstdev(ws) if len(ws) > 1 else 0.0),
            "iters": iters}


def host_metadata() -> dict:
    """Host/device provenance block stamped into every committed
    ``experiments/*.json`` artifact (see docs/experiments.md): numbers
    from two machines are only comparable when this block matches."""
    import os
    import platform

    import jax

    cpu_model = ""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.lower().startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "cpu_model": cpu_model,
        "cpu_count": os.cpu_count(),
        "jax_version": jax.__version__,
        "jax_backend": jax.default_backend(),
        "jax_device_count": jax.device_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def require_pallas_target(interpret: bool) -> None:
    """Refuse to run the compiled Pallas kernels where JAX finds no TPU.
    Timing the Pallas interpreter is asked for with ``--interpret``;
    it is never chosen for the caller."""
    import jax
    if not interpret and jax.default_backend() != "tpu":
        raise SystemExit(
            f"the Pallas kernels are compiled for the TPU and JAX runs on "
            f"{jax.default_backend()!r}; pass --interpret to run them "
            f"through the Pallas interpreter instead")


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
