"""The reference of a window, and the comparison that decides ``correct``.

The reference is ``bench/plain.py``, a plain implementation of the serve's
semantics that imports nothing of the program. It is built from the
configuration file and the seed alone (``bench/deploy.py``: the same
trace bank, worker phases, initial charge and arrival rows the program
is given, and workload tables built from the definitions in the
configuration), and replays the window's first ``reference_ticks`` ticks
(all of a shorter window).

The comparison covers those ticks: every per-chunk record the program's
loop returned (less its wall-clock ``wall_s``), and everything the
window left behind: every worker's device state, each queue's requests
in order (arrival time and retries), each worker's assignment and every
counter and histogram of the control plane. Discrete values must be
equal; floats (times, accuracy sums, the records' rates and latencies)
may differ by rounding. The field walk is that of
``chip_smoke.differences`` at the root of the repository, copied and
extended.

The control is the reference computed with float32 in place of float64
(``replay(..., ft=np.float32)``), the next precision below the one the
configuration states.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import deploy
from plain import PlainFleet

# the limit of each number compared (PERF.md gives the readings each was
# set from): no compile in the window, every discrete value equal, and
# floats equal to rounding
LIMITS = {"compiles": 0, "counter_mismatches": 0, "float_rel_dev": 1e-9}


def replay(config: dict, seed: int, rows: np.ndarray, n_ticks: int,
           ft=np.float64, power: np.ndarray | None = None):
    """The reference's (chunk records, state) after the window's first
    ``n_ticks`` ticks."""
    if power is None:
        power = deploy.power_matrix(config)
    fleet = PlainFleet(config, power, deploy.phases(config, seed),
                       deploy.initial_quanta(config, seed), ft=ft)
    records = fleet.serve(rows, n_ticks, int(config["chunk_ticks"]))
    return records, fleet.state()


def program_state(fleet, sched) -> dict:
    """The program's state after the window, under the reference's names:
    the device state's fields, each queue's logical contents read off
    its ring buffer, the assignments and the counters."""
    out = {f"fleet.{f.name}": np.asarray(getattr(fleet, f.name))
           for f in dataclasses.fields(fleet)}
    q_t, q_r = np.asarray(sched.q_t), np.asarray(sched.q_r)
    head, n = np.asarray(sched.q_head), np.asarray(sched.q_len)
    Q = q_t.shape[1]
    for w in range(q_t.shape[0]):
        idx = (int(head[w]) + np.arange(int(n[w]))) % Q
        out[f"queue[{w}].t"] = q_t[w, idx]
        out[f"queue[{w}].retries"] = q_r[w, idx]
    for f in dataclasses.fields(sched):
        if f.name not in ("q_t", "q_r", "q_head", "q_len"):
            out[f"sched.{f.name}"] = np.asarray(getattr(sched, f.name))
    return out


def program_records(summary: dict, n_chunks: int) -> list[dict]:
    return [{k: v for k, v in c.items() if k != "wall_s"}
            for c in summary["stream"]["chunks"][:n_chunks]]


def _rel(x, y) -> float:
    return float(abs(x - y) / max(abs(x), abs(y)))


@dataclasses.dataclass
class Comparison:
    counter_mismatches: int  # discrete values that differ
    float_rel_dev: float  # largest relative float deviation
    first: list[str]  # the first few differences, for the log


def compare(got, ref, dt: float, keep: int = 12) -> Comparison:
    """``got`` and ``ref``: (chunk records, state by name). An integer
    array held against a float one is a time stamp kept in ticks on one
    side and in seconds on the other: it is compared in seconds, ticks x
    ``dt``."""
    bad: list[str] = []
    worst = 0.0
    n_bad = 0
    ra, rb = got[0], ref[0]
    if len(ra) != len(rb):
        n_bad += 1
        bad.append(f"records: {len(ra)} chunks vs {len(rb)}")
    for c, (x, y) in enumerate(zip(ra, rb)):
        for k in sorted(x.keys() | y.keys()):
            a, b = x.get(k), y.get(k)
            if a is None or b is None:
                n_bad += 1
                bad.append(f"record[{c}].{k}: only on one side")
            elif isinstance(a, float) or isinstance(b, float):
                if a != b:
                    rel = _rel(a, b) if np.isfinite([a, b]).all() else np.inf
                    worst = max(worst, rel)
                    if len(bad) < keep:
                        bad.append(f"record[{c}].{k}: {a!r} != {b!r} "
                                   f"(rel {rel:.3g})")
            elif a != b:
                n_bad += 1
                if len(bad) < keep:
                    bad.append(f"record[{c}].{k}: {a!r} != {b!r}")
    sa, sb = got[1], ref[1]
    for k in sorted(sa.keys() | sb.keys()):
        a, b = sa.get(k), sb.get(k)
        if a is None or b is None or a.shape != b.shape:
            n_bad += 1
            bad.append(f"{k}: shape {getattr(a, 'shape', None)} vs "
                       f"{getattr(b, 'shape', None)}")
            continue
        fa = np.issubdtype(a.dtype, np.floating)
        fb = np.issubdtype(b.dtype, np.floating)
        if fa or fb:
            a64 = a.astype(np.float64) * (1.0 if fa else dt)
            b64 = b.astype(np.float64) * (1.0 if fb else dt)
            diff = a64 != b64
            if diff.any():
                den = np.maximum(np.abs(a64[diff]), np.abs(b64[diff]))
                rel = float(np.nan_to_num(
                    np.abs(a64[diff] - b64[diff]) / den, nan=np.inf).max())
                worst = max(worst, rel)
                if len(bad) < keep:
                    bad.append(f"{k}: {int(diff.sum())} of {a.size} floats "
                               f"differ (rel up to {rel:.3g})")
        else:
            nd = int(np.count_nonzero(a.astype(np.int64)
                                      != b.astype(np.int64)))
            if nd:
                n_bad += nd
                if len(bad) < keep:
                    bad.append(f"{k}: {nd} of {a.size} values differ")
    return Comparison(n_bad, worst, bad[:keep])
