import numpy as np
import pytest

from traffic import ArrivalSource, arrival_rows, rate_per_tick


def test_steady_rows_are_the_programs_poisson_stream():
    from repro.fleet.scheduler import RequestStream
    mix = [0.4, 0.3, 0.3]
    rows = arrival_rows({"period_s": 10.0}, 1024, mix, 500, 0.01,
                        seed=2 ** 31 + 7)
    ref = RequestStream(1024 / 10.0, np.array(mix), 500, 0.01,
                        seed=2 ** 31 + 7).counts_matrix(3)
    np.testing.assert_array_equal(rows, ref)


def test_phases_modulate_the_rate():
    lam = rate_per_tick({"period_s": 10.0, "phases": [[1.0, 3.0],
                                                      [1.0, 0.0]]},
                        1000, 400, 0.01)
    assert lam[:100] == pytest.approx(np.full(100, 3.0))
    assert lam[100:200] == pytest.approx(np.zeros(100))
    assert lam[200:300] == pytest.approx(np.full(100, 3.0))


def test_source_hands_out_rows_in_order_and_stamps():
    rows = np.arange(12).reshape(6, 2)
    src = ArrivalSource(rows)
    np.testing.assert_array_equal(src.take(4), rows[:4])
    np.testing.assert_array_equal(src.take(2), rows[4:])
    assert len(src.stamps) == 2 and src.stamps[0] <= src.stamps[1]
    with pytest.raises(ValueError):
        src.take(1)


def test_peaks_unknown_kind_is_an_error():
    from peaks import peaks
    assert peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
