"""The deployment data the benchmark makes equals what the program's own
builders make from the same settings: the trace bank (the arithmetic is
copied), and the workload tables (built again from the definitions in
the configuration file). The initial charge lies between brown-out and
full charge and is the same for the same seed."""
import json

import numpy as np
import pytest

import deploy
from conftest import BENCH, tiny_config


def test_trace_bank_is_the_launchers():
    from repro.launch.fleet import make_power_matrix
    c = tiny_config(bank_s=30.0, trace_rows=10)
    got = deploy.power_matrix(c)
    ref = make_power_matrix(c["trace_families"], 10, 30.0, c["dt_s"],
                            c["trace_seed"])
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("name", ["fleet131k_q32_cold", "fleet131k_q32",
                                  "fleet1k_q32"])
def test_workload_tables_are_the_launchers(name):
    from repro.launch.fleet import WORKLOAD_FACTORIES
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    for tab in deploy.workload_tables(c):
        prog = WORKLOAD_FACTORIES[tab["name"]]()
        np.testing.assert_array_equal(tab["units"], prog.costs.unit_costs)
        assert tab["fixed"] == prog.costs.fixed_cost
        assert tab["emit"] == prog.costs.emit_cost
        np.testing.assert_array_equal(tab["acc"], prog.accuracy)
        assert tab["floor"] == prog.floor


def test_cold_window_ends_before_any_worker_can_wake():
    """From empty capacitors, no trace row of ``fleet131k_q32_cold``
    harvests the energy to switch on within ``max_window_ticks`` from any
    phase, so its window dispatches nothing, whatever the seed."""
    from plain import PlainFleet
    c = json.loads((BENCH / "configs" / "fleet131k_q32_cold.json")
                   .read_text())
    assert c["initial_charge"] == "empty"
    c.update(workers=c["trace_rows"])  # one worker per row suffices
    n = c["workers"]
    fleet = PlainFleet(c, deploy.power_matrix(c), np.zeros(n, np.int64),
                       deploy.initial_quanta(c, 0))
    h = fleet.harvest_q.astype(np.int64)
    T = h.shape[1]
    cs = np.concatenate([np.zeros((h.shape[0], 1), np.int64),
                         np.cumsum(np.concatenate([h, h], axis=1), axis=1)],
                        axis=1)
    k = c["max_window_ticks"]
    most = (cs[:, k:k + T] - cs[:, :T]).max()  # any row, any phase
    assert most < fleet.e_on


def test_initial_charge_is_uniform_between_brown_out_and_full():
    c = tiny_config(workers=4096)
    d = c["device"]
    e = deploy.initial_quanta(c, 5) * d["quantum_j"]
    lo = 0.5 * d["capacitance_f"] * d["v_off"] ** 2
    hi = 0.5 * d["capacitance_f"] * d["v_max"] ** 2
    assert (e >= lo - d["quantum_j"]).all() and (e <= hi).all()
    assert np.quantile((e - lo) / (hi - lo), 0.5) == pytest.approx(
        0.5, abs=0.05)
    np.testing.assert_array_equal(deploy.initial_quanta(c, 5),
                                  deploy.initial_quanta(c, 5))
    assert not np.array_equal(deploy.initial_quanta(c, 5),
                              deploy.initial_quanta(c, 6))
