import types

import pytest

from cell import reader
from trace_reduce import Event, reduce, self_times

MS = 1e6  # ns


def synthetic():
    """A window of two chunks: take, run_serve (device busy inside),
    host code; and one refit between them."""
    spans = [Event("bench.window", 0, 100 * MS),
             Event("bench.take", 0, 1 * MS),
             Event("bench.run_serve", 2 * MS, 40 * MS),
             Event("bench.refit_forecast", 42 * MS, 50 * MS),
             Event("bench.take", 50 * MS, 51 * MS),
             Event("bench.run_serve", 52 * MS, 90 * MS)]
    ops = [Event("fusion.1", 5 * MS, 20 * MS),
           Event("fusion.2", 15 * MS, 35 * MS),  # overlaps fusion.1
           Event("copy.3", 60 * MS, 85 * MS),
           Event("copy.3", 95 * MS, 120 * MS)]  # runs past the window
    return {"/device:TPU:0": ops}, spans


def test_busy_union_gaps_and_spans():
    r = reduce(*synthetic())
    assert r.window_s == pytest.approx(0.1)
    # union: 5-35, 60-85, 95-100 ms
    assert r.busy_s == pytest.approx(0.060)
    serves = [s for s in r.spans if s.name == "bench.run_serve"]
    assert [s.busy for s in serves] == pytest.approx([30 * MS, 25 * MS])
    gaps = dict((round(t * 1e3), n) for n, t in r.idle_gaps)
    assert gaps == {25: "bench.refit_forecast", 10: "bench.run_serve",
                    5: "bench.run_serve"}
    assert r.device_ops[0] == ("copy.3", pytest.approx(0.030))


def test_nested_ops_count_their_own_time():
    """A TPU trace names each operation by its HLO instruction and lists
    a scan's ``while`` on the same line as the operations of its body."""
    ops = [Event("%while.1 = (u32[]) while(u32[] %a), body=%b", 0, 100 * MS),
           Event("%fusion.2 = s32[8] fusion(s32[8] %x)", 10 * MS, 30 * MS),
           Event("%conditional.3 = () conditional(pred[] %p)", 40 * MS,
                 60 * MS),
           Event("%fusion.4 = s32[8] fusion(s32[8] %y)", 45 * MS, 50 * MS)]
    got = self_times(ops, 0, 90 * MS)
    assert got == pytest.approx({"while.1": 50 * MS, "fusion.2": 20 * MS,
                                 "conditional.3": 15 * MS,
                                 "fusion.4": 5 * MS})
    assert sum(got.values()) == pytest.approx(90 * MS)


def test_layer_readers():
    r = reduce(*synthetic())
    run = types.SimpleNamespace(trace=r, chunk_ticks=10)
    # chunk intervals 0-50 and 50-100 ms, less 38 ms of run_serve each
    assert reader("stream_host_ms.rate")(run) == pytest.approx(12.0)
    assert reader("launch_host_ms.rate")(run) == pytest.approx(
        ((38 - 30) + (38 - 25)) / 2)
    assert reader("device_us_per_tick.rate")(run) == pytest.approx(
        55e3 / 20)
    assert reader("device_idle_share.rate")(run) == pytest.approx(40.0)


def test_no_device_ops_reads_nothing():
    _, spans = synthetic()
    assert reduce({}, spans) is None
    run = types.SimpleNamespace(trace=None, chunk_ticks=10)
    for name in ("stream_host_ms", "launch_host_ms", "device_us_per_tick",
                 "device_idle_share"):
        assert reader(name)(run) is None


def test_end_to_end_readers():
    run = types.SimpleNamespace(workers=100, ticks=30, t_process=0.0,
                                stamps=[1.0, 1.5, 2.5], t_end=3.0)
    assert reader("worker_ticks_per_s")(run) == pytest.approx(1500.0)
    assert reader("setup_s")(run) == 1.0
    assert reader("chunk_latency_p95_ms")(run) == pytest.approx(950.0)
