"""Fleet-level accounting: request lifecycle counters + energy books.

Two accounting surfaces, one summary dict:

- :func:`sched_summary` — the array-native control plane's aggregate
  counters (``SchedState``): completions, every other way a request can
  leave the system (rejected at admission, shed while queued, lost to
  brown-outs past the retry budget, evicted by the straggler deadline),
  per-workload units/accuracy sums, and a fixed-bin latency histogram
  (the fused JAX scan returns no per-request records, so percentiles
  come from the bins). Folds in the worker pool's energy ledger so a
  single dict answers throughput / latency / accuracy / energy — the
  four axes the paper trades against each other.
- ``RequestRecord`` / ``FleetMetrics`` — the per-request record surface,
  kept for host-side tooling that wants individual lifecycles.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class RequestRecord:
    rid: int
    workload: int
    t_arrival: float
    t_assigned: float
    t_done: float
    units: int
    worker: int
    batch: int
    expected_accuracy: float

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_arrival


def _energy_block(pool, completed: int) -> dict:
    # quantized pools (kernel="q32"/"pallas") accumulate integer energy
    # quanta; convert back to joules at this reporting boundary
    q = getattr(pool.params, "quantum_j", None)
    e_scale = 1.0 if q is None else q
    harvested = float(pool.e_harvest.sum()) * e_scale
    work = float(pool.e_work.sum()) * e_scale
    # approximate runtime: structurally 0.0 (no NVM state machine);
    # persist=ckpt/undolog: measured FRAM checkpoint/commit/restore
    # joules. Summed on the host: per-worker entries are bit-equal
    # across backends, and a device-side reduction would reassociate
    # them — the ledger is compared for exact equality in CI
    nvm = float(np.asarray(pool.e_persist).sum()) * e_scale
    return {
        "harvested_j": harvested,
        "work_j": work,
        "nvm_j": nvm,
        "sleep_j": 0.0,
        "persists": int(np.asarray(pool.persists).sum()),
        "restores": int(np.asarray(pool.restores).sum()),
        "j_per_completed": ((work + nvm) / completed if completed
                            else float("inf")),
        # harvested >= work + nvm + sleep: nothing comes from thin air;
        # the remainder is banked charge + booster losses
        "conservation_ok": bool(harvested + 1e-9 >= work + nvm),
    }


def quality_block(sp, ss) -> dict:
    """The summary's quality-plane block: fleet-wide measured accuracy,
    the proxy-vs-measured gap, and the ledgered spend, all derived from
    the control plane's bit-exact integer counters (``meas_wl``,
    ``joules_nj_wl`` — see ``repro.quality.ledger`` for the richer
    per-workload record views over the same arrays)."""
    completed = int(np.asarray(ss.completed_wl).sum())
    correct = int(np.asarray(ss.meas_wl).sum())
    joules = float(np.asarray(ss.joules_nj_wl).sum()) * 1e-9
    proxy = float(np.asarray(ss.acc_wl).sum()) / max(completed, 1)
    measured = correct / max(completed, 1)
    return {
        "tables": sp.quality,  # "proxy" | "measured"
        "measured_correct": correct,
        "mean_measured_accuracy": measured,
        "proxy_minus_measured": proxy - measured,
        "ledger_joules": joules,
        "j_per_completed_ledger": joules / max(completed, 1),
    }


def _hist_percentile(hist: np.ndarray, lat_max_s: float, q: float) -> float:
    """Percentile estimate from the fixed-bin latency histogram (bin
    centers; the fused scan's records-free substitute for exact order
    statistics)."""
    total = int(hist.sum())
    if total == 0:
        return 0.0
    cum = np.cumsum(hist)
    # searchsorted(cum, 0) would land on leading *empty* bins; clamp the
    # rank strictly above zero so small q still finds occupied mass
    rank = max(q * total, np.finfo(np.float64).tiny)
    b = int(np.searchsorted(cum, rank))
    return (min(b, hist.shape[0] - 1) + 0.5) * lat_max_s / hist.shape[0]


def latency_bin_edges_s(sp) -> list[float]:
    """The ``lat_bins + 1`` edges of the fixed-bin latency histogram, in
    seconds — exposed so summary consumers can reconstruct the bins the
    percentiles were read from."""
    return [float(x) for x in
            np.linspace(0.0, sp.lat_max_s, sp.lat_bins + 1)]


def sched_summary(sp, ss, duration_s: float, pool=None,
                  workload_names: list[str] | None = None) -> dict:
    """Summary dict from the array control plane's aggregate counters
    (``sp``/``ss``: SchedParams/SchedState). Same keys as the historical
    per-record summary so launchers and benchmarks are agnostic."""
    completed = int(ss.completed)
    out: dict = {
        "submitted": int(ss.submitted),
        "completed": completed,
        "rejected": int(ss.rejected),
        "shed": int(ss.shed),
        "lost": int(ss.lost),
        "evicted": int(ss.evicted),
        "requeued": int(ss.requeued),
        # requests moved between shards by the work-stealing exchange
        # (0 on unsharded runs; see docs/sharded_fleet.md)
        "rebalanced": int(np.asarray(ss.rebalanced).sum()),
        "throughput_rps": completed / max(duration_s, 1e-9),
        "latency_mean_s": int(ss.lat_sum) * sp.dt / max(completed, 1),
        "latency_p50_s": _hist_percentile(np.asarray(ss.lat_hist),
                                          sp.lat_max_s, 0.50),
        "latency_p95_s": _hist_percentile(np.asarray(ss.lat_hist),
                                          sp.lat_max_s, 0.95),
        "latency_p99_s": _hist_percentile(np.asarray(ss.lat_hist),
                                          sp.lat_max_s, 0.99),
        "latency_bin_edges_s": latency_bin_edges_s(sp),
        "mean_units": float(ss.units_wl.sum()) / max(completed, 1),
        "mean_expected_accuracy": (float(ss.acc_wl.sum())
                                   / max(completed, 1)),
        "batch_hist": [int(x) for x in np.asarray(ss.batch_hist)],
    }
    # the quality plane's ledgered counters (measured correctness +
    # table-priced spend; see repro.quality.ledger)
    out["quality"] = quality_block(sp, ss)
    out["per_workload"] = {}
    for w in range(sp.W):
        c = int(ss.completed_wl[w])
        if c == 0:
            continue
        name = workload_names[w] if workload_names else str(w)
        out["per_workload"][name] = {
            "completed": c,
            "mean_units": float(ss.units_wl[w]) / c,
            "mean_expected_accuracy": float(ss.acc_wl[w]) / c,
            "mean_measured_accuracy": float(ss.meas_wl[w]) / c,
            "ledger_joules": float(ss.joules_nj_wl[w]) * 1e-9,
        }
    if pool is not None:
        out["energy"] = _energy_block(pool, completed)
    return out


@dataclasses.dataclass
class FleetMetrics:
    completed: list[RequestRecord] = dataclasses.field(default_factory=list)
    submitted: int = 0
    rejected: int = 0  # admission control (queue full)
    shed: int = 0  # stale in queue past shed_after_s
    lost: int = 0  # brown-out losses past the retry budget
    evicted: int = 0  # straggler-deadline evictions
    requeued: int = 0  # retries granted after a loss/eviction

    def observe_completion(self, rec: RequestRecord) -> None:
        self.completed.append(rec)

    def summary(self, duration_s: float, pool=None,
                workload_names: list[str] | None = None) -> dict:
        lat = np.array([r.latency_s for r in self.completed])
        out: dict = {
            "submitted": self.submitted,
            "completed": len(self.completed),
            "rejected": self.rejected,
            "shed": self.shed,
            "lost": self.lost,
            "evicted": self.evicted,
            "requeued": self.requeued,
            "throughput_rps": len(self.completed) / max(duration_s, 1e-9),
            "latency_mean_s": float(lat.mean()) if lat.size else 0.0,
            "latency_p50_s": float(np.percentile(lat, 50)) if lat.size else 0.0,
            "latency_p95_s": float(np.percentile(lat, 95)) if lat.size else 0.0,
            "latency_p99_s": float(np.percentile(lat, 99)) if lat.size else 0.0,
            "mean_units": (float(np.mean([r.units for r in self.completed]))
                           if self.completed else 0.0),
            "mean_expected_accuracy": (
                float(np.mean([r.expected_accuracy for r in self.completed]))
                if self.completed else 0.0),
        }
        by_wl: dict[int, list[RequestRecord]] = {}
        for r in self.completed:
            by_wl.setdefault(r.workload, []).append(r)
        out["per_workload"] = {}
        for wl, recs in sorted(by_wl.items()):
            name = (workload_names[wl] if workload_names else str(wl))
            out["per_workload"][name] = {
                "completed": len(recs),
                "mean_units": float(np.mean([r.units for r in recs])),
                "mean_expected_accuracy": float(
                    np.mean([r.expected_accuracy for r in recs])),
            }
        if pool is not None:
            out["energy"] = _energy_block(pool, len(self.completed))
        return out
