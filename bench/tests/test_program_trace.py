"""The reduction of the program's own spans and scopes
(``bench/program_trace.py``) on a synthetic trace, and the benchmark's
accepted readers on a trace that holds the program's spans."""
import types

import pytest

from cell import reader
from program_trace import (CHUNK, UNSCOPED, Span, figures, hlo_scopes,
                           innermost_scope, reduce)
from test_trace_reduce import synthetic
from trace_reduce import Event
from trace_reduce import reduce as reduce_bench

MS = 1e6  # ns

HLO = """HloModule jit_serve_fn, is_scheduled=true

%fused_computation.4 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %neg.1 = s32[8]{0} negate(%param_0)
  ROOT %add.1 = s32[8]{0} add(%neg.1, %param_0), metadata={op_name="jit(serve_fn)/while/body/cond/branch_1_fun/fleet.dispatch/fleet.dispatch.queues/add"}
}

ENTRY %main.9 (p: s32[8]) -> (s32[]) {
  %p = s32[8]{0} parameter(0)
  %fusion.2 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(serve_fn)/while/body/fleet.tick/mul"}
  %fusion.4 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.4
  %conditional.3 = () conditional(pred[] %c), branch_computations={%a, %b}, metadata={op_name="jit(serve_fn)/while/body/cond"}
  ROOT %while.1 = (s32[]) while(s32[] %i), condition=%c, body=%b, metadata={op_name="jit(serve_fn)/while"}
}
"""


def program_trace():
    """Two chunks of 10 ticks (dispatch every 5) inside a benchmark
    window: each chunk's launch runs a scan (``while.1``) holding a tick
    fusion, then a dispatch branch (``conditional.3``) holding a queue
    fusion. The first chunk compiles, the second calls."""
    spans = [Span("bench.window", 0, 100 * MS)]
    ops = []
    for c, t0 in enumerate((0, 50 * MS)):
        def at(a, b):
            return t0 + a * MS, t0 + b * MS
        launch = ("fleet.serve.compile", {"chunk": c, "n_ticks": 10,
                                          "dispatch_every": 5, "builds": 1}
                  ) if c == 0 else ("fleet.serve.call", {"chunk": c})
        spans += [Span(CHUNK, *at(0, 50), {"chunk": c, "step_num": c}),
                  Span("fleet.stream.take", *at(0, 1), {"chunk": c}),
                  Span("fleet.stream.snapshot", *at(1, 2), {"chunk": c}),
                  Span("bench.run_serve", *at(2, 45)),
                  Span("fleet.serve.upload", *at(3, 7),
                       {"chunk": c, "bytes": 3_000_000}),
                  Span(launch[0], *at(7, 8), launch[1]),
                  Span("fleet.serve.readback", *at(8, 40 + 6 * c),
                       {"chunk": c, "bytes": 2_000_000}),
                  Span("fleet.stream.snapshot", *at(45, 46), {"chunk": c}),
                  Span("fleet.stream.record", *at(46, 47), {"chunk": c})]
        ops += [Event("%while.1 = (s32[]) while(s32[] %i)", *at(8, 38)),
                Event("%fusion.2 = s32[8] fusion(s32[8] %p)", *at(10, 20)),
                Event("%conditional.3 = () conditional(pred[] %c)",
                      *at(20, 36)),
                Event("%fusion.4 = s32[8] fusion(s32[8] %p)", *at(22, 34))]
    return {"/device:TPU:0": ops}, spans


def test_hlo_scopes_take_the_innermost_and_the_fused_scope():
    got = hlo_scopes(HLO)
    assert got == {"add.1": "fleet.dispatch.queues",
                   "fusion.2": "fleet.tick",
                   "fusion.4": "fleet.dispatch.queues"}
    assert innermost_scope("jit(f)/while/body/fleet.tick/mul") == \
        "fleet.tick"
    assert innermost_scope("jit(f)/while") is None


def test_device_time_by_scope_sums_to_busy():
    r = reduce(*program_trace(), hlo_scopes(HLO))
    # each chunk: busy 30 ms; tick 10, queues 12, the rest (the scan's
    # and the branch's own time) 4 + 4
    assert r.busy_s == pytest.approx(0.060)
    assert dict(r.device_scopes) == pytest.approx(
        {"fleet.tick": 0.020, "fleet.dispatch.queues": 0.024,
         UNSCOPED: 0.016})
    assert sum(t for _, t in r.device_scopes) == pytest.approx(r.busy_s)
    assert r.device_scopes[0][0] == "fleet.dispatch.queues"


def test_gaps_are_named_by_the_program_spans():
    r = reduce(*program_trace(), hlo_scopes(HLO))
    gaps = dict((round(t * 1e3), n) for n, t in r.idle_gaps)
    # 0-8 ms inside the first upload; 38-58 ms between the chunks'
    # launches, after the first readback; 88-100 ms inside the second
    # readback, which waits past the device's end
    assert gaps == {8: "fleet.serve.upload", 20: CHUNK,
                    12: "fleet.serve.readback"}


def test_figures():
    r = reduce(*program_trace(), hlo_scopes(HLO))
    f = figures(r, chunk_ticks=10, dispatch_every=5)
    assert f["serve_upload_ms"] == pytest.approx(4.0)
    # readbacks 8-40 and 8-46 ms of each chunk, 30 ms busy inside each
    assert f["serve_readback_ms"] == pytest.approx(((32 - 30) + (38 - 30))
                                                   / 2)
    assert f["serve_call_ms"] == pytest.approx(1.0)
    assert f["serve_transfer_mb"] == pytest.approx(5.0)
    # 24 ms of queue passes over 2 chunks x 2 dispatch ticks
    assert f["dispatch_device_ms"] == pytest.approx(6.0)
    # 20 ms of tick over 20 ticks
    assert f["tick_device_us"] == pytest.approx(1000.0)


def test_launcher_trace_window_is_the_chunks():
    devices, spans = program_trace()
    spans = [s for s in spans if not s.name.startswith("bench.")]
    r = reduce(devices, spans, hlo_scopes(HLO))
    assert (r.window.start, r.window.end) == (0, 100 * MS)
    assert r.busy_s == pytest.approx(0.060)


def test_no_window_or_no_ops_reads_nothing():
    devices, spans = program_trace()
    assert reduce({}, spans, {}) is None
    assert reduce(devices, [s for s in spans if s.name != CHUNK
                            and s.name != "bench.window"], {}) is None
    r = reduce(devices, [s for s in spans if s.name == "bench.window"], {})
    assert figures(r, 10, 5) == {
        "serve_upload_ms": None, "serve_readback_ms": None,
        "serve_call_ms": None, "serve_transfer_mb": None,
        "dispatch_device_ms": None, "tick_device_us": None}


@pytest.mark.parametrize("name", ["stream_host_ms.rate",
                                  "launch_host_ms.rate",
                                  "device_us_per_tick.rate",
                                  "device_idle_share.rate"])
def test_accepted_readers_ignore_the_program_spans(name):
    """The benchmark's per-layer readers select their spans by name, so
    the program's spans nested in them change none of their values."""
    devices, spans = synthetic()
    program = [Event(CHUNK, 0, 50 * MS),
               Event("fleet.stream.take", 0, 1 * MS),
               Event("fleet.serve.upload", 2 * MS, 4 * MS),
               Event("fleet.serve.call", 4 * MS, 5 * MS),
               Event("fleet.serve.readback", 5 * MS, 40 * MS),
               Event("fleet.stream.record", 40 * MS, 41 * MS),
               Event(CHUNK, 50 * MS, 100 * MS),
               Event("fleet.serve.upload", 52 * MS, 58 * MS),
               Event("fleet.serve.readback", 60 * MS, 90 * MS)]
    runs = [types.SimpleNamespace(trace=reduce_bench(devices, s),
                                  chunk_ticks=10)
            for s in (spans, spans + program)]
    assert reader(name)(runs[1]) == reader(name)(runs[0])
    assert reader(name)(runs[0]) is not None
