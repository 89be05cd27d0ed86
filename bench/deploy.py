"""The data of a deployment, made by the benchmark from its configuration
file and ``--seed``, and handed to the program and to the plain reference
alike: the harvest trace bank, each worker's trace row and phase, the
capacitors' charge at the window's start, and the workload cost and
accuracy tables built from the workload definitions in the configuration.

The trace families are the paper's (arXiv:2111.10726): Mementos-style RF
bursts and the four EPIC solar traces. Their arithmetic is copied from
the program's trace synthesis, so that a later change to the program
cannot move the yardstick; a test pins the copy to the original.
"""
from __future__ import annotations

import numpy as np

# ---------------------------------------------------------------------------
# harvest traces (watts on a dt grid), one row per trace
# ---------------------------------------------------------------------------

# family -> (mean power in uW, variability, occlusion events per second)
SOLAR = {"SOM": (900.0, 1.0, 0.05), "SIM": (450.0, 2.0, 0.2),
         "SOR": (650.0, 0.3, 0.0), "SIR": (220.0, 0.4, 0.0)}
RF_MEAN_UW = 220.0


def _ou(rng, n: int, mean: float, theta: float, sigma: float) -> np.ndarray:
    x = np.empty(n)
    x[0] = mean
    for i in range(1, n):
        x[i] = x[i - 1] + theta * (mean - x[i - 1]) + sigma * rng.standard_normal()
    return x


def _occlusion(rng, n: int, dt: float, rate_hz: float) -> np.ndarray:
    occl = np.ones(n)
    t = 0
    while t < n:
        nxt = t + int(rng.exponential(1.0 / rate_hz) / dt) + 1
        dur = int(rng.uniform(0.2, 3.0) / dt)
        occl[nxt:nxt + dur] = rng.uniform(0.05, 0.5)
        t = nxt + dur
    return occl


def _solar(family: str, seed: int, n: int, dt: float) -> np.ndarray:
    mean_uw, variability, rate_hz = SOLAR[family]
    rng = np.random.default_rng(seed)
    base = _ou(rng, n, 1.0, theta=0.002, sigma=0.002 * variability)
    if rate_hz > 0:
        base = base * _occlusion(rng, n, dt, rate_hz)
    p = np.clip(base, 0.0, None)
    p *= (mean_uw * 1e-6) / max(p.mean(), 1e-12)
    return p


def _rf(seed: int, n: int, dt: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    p = np.zeros(n)
    i = 0
    while i < n:
        burst = int(rng.exponential(0.35) / dt) + 1
        gap = int(rng.pareto(1.5) * 0.3 / dt) + 1
        amp = RF_MEAN_UW * 1e-6 * rng.uniform(2.0, 6.0)
        p[i:i + burst] = amp * (1.0 + 0.3 * rng.standard_normal(
            min(burst, n - i)))
        i += burst + gap
    np.clip(p, 0.0, None, out=p)
    p *= (RF_MEAN_UW * 1e-6) / max(p.mean(), 1e-12)
    return p


def power_matrix(config: dict) -> np.ndarray:
    """(rows, T) harvested power: row r of family ``families[r % F]``,
    synthesized from the seed ``trace_seed + r``."""
    fams = config["trace_families"]
    dt = float(config["dt_s"])
    n = int(float(config["bank_s"]) / dt)
    rows = []
    for r in range(int(config["trace_rows"])):
        fam = fams[r % len(fams)]
        seed = int(config["trace_seed"]) + r
        rows.append(_rf(seed, n, dt) if fam == "RF"
                    else _solar(fam, seed, n, dt))
    return np.stack(rows).astype(np.float64)


def bank_ticks(config: dict) -> int:
    return int(float(config["bank_s"]) / float(config["dt_s"]))


# ---------------------------------------------------------------------------
# the fleet: trace rows, phases and initial charge
# ---------------------------------------------------------------------------


def trace_rows(config: dict) -> np.ndarray:
    """Worker w harvests trace row ``w % rows``."""
    return np.arange(int(config["workers"])) % int(config["trace_rows"])


def phases(config: dict, seed: int) -> np.ndarray:
    """Each worker's tick offset into its row, uniform over the bank."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, bank_ticks(config), int(config["workers"]))


def initial_quanta(config: dict, seed: int) -> np.ndarray:
    """(N,) int32 stored energy at the window's start, in quanta.
    ``initial_charge`` ``"uniform"``: uniform between the brown-out level
    and full charge, from the seed; ``"empty"``: zero."""
    d = config["device"]
    n = int(config["workers"])
    if config["initial_charge"] == "empty":
        return np.zeros(n, np.int32)
    if config["initial_charge"] != "uniform":
        raise ValueError(f"unknown initial_charge "
                         f"{config['initial_charge']!r}")
    u = np.random.default_rng(seed + 2).random(n)
    c, v_off, v_max = d["capacitance_f"], d["v_off"], d["v_max"]
    e = 0.5 * c * (v_off ** 2 + u * (v_max ** 2 - v_off ** 2))
    return np.floor(e / d["quantum_j"]).astype(np.int32)


# ---------------------------------------------------------------------------
# workloads: joules per knob unit, fixed and emission costs, accuracy
# ---------------------------------------------------------------------------


def workload_tables(config: dict) -> list[dict]:
    """Per workload of the configuration, in its order: ``units`` (J per
    knob unit), ``fixed`` and ``emit`` (J), ``acc`` (expected accuracy
    with k units, k = 0..units) and ``floor`` (the SMART admission
    floor), from the definitions in the configuration file."""
    d = config["device"]
    hz, p_w = d["mcu_hz"], d["active_power_w"]
    out = []
    for name in config["workloads"]:
        w = config["workload_defs"][name]
        if name == "har":
            fam = []
            for _ in range(w["feature_blocks"]):
                fam += w["block_families"]
            fam += w["tail_families"]
            cyc = np.array([w["family_cycles"][f] for f in fam])
            units = w["scale"] * (cyc / hz * p_w)
            k = np.arange(len(fam) + 1) / len(fam)
            lo, hi = w["acc_chance"], w["acc_plateau"]
            acc = lo + (hi - lo) * k ** w["acc_exponent"]
            fixed, emit = d["sample_window_j"], d["ble_packet_j"]
        elif name == "harris":
            n, px = w["taps"], w["image_px"]
            per_tap = w["cycles_per_px_tap"] * px / hz * p_w
            units = np.full(n, per_tap)
            fixed = w["fixed_cycles_per_px"] * px / hz * p_w \
                + d["image_load_j"]
            emit = d["ble_packet_j"]
            k = np.arange(n + 1) / n
            acc = 1.0 / (1.0 + np.exp(-(k - w["acc_mid"]) / w["acc_width"]))
            acc[-1] = 1.0
        elif name == "lm":
            dm, h, kv, ff = (w["d_model"], w["n_heads"], w["n_kv_heads"],
                             w["d_ff"])
            dh = dm // h
            layer = float(2 * dm * (2 * h * dh + 2 * kv * dh)
                          + 2 * 2 * h * w["kv_len"] * dh + 2 * 3 * dm * ff)
            head = 2 * dm * w["vocab_size"]
            sec = np.full(w["n_layers"], layer / w["edge_flops"])
            units = sec * p_w
            emit = head / w["edge_flops"] * p_w
            fixed = w["fixed_j"]
            depth = np.arange(w["n_layers"] + 1)
            acc = np.clip((depth / w["n_layers"]) ** 0.5, 1e-3, 1.0)
            acc[0] = 1e-3
        else:
            raise ValueError(f"no definition of workload {name!r}")
        out.append({"name": name, "units": np.asarray(units, np.float64),
                    "fixed": float(fixed), "emit": float(emit),
                    "acc": np.asarray(acc, np.float64),
                    "floor": float(w["floor"])})
    return out
