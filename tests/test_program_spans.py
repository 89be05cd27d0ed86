"""Program tracing: the ``fleet.*`` host spans of the streaming serve and
the ``fleet.*`` device scopes of its chunk program, read back from a real
``jax.profiler`` trace (docs/observability.md, "Program spans").

A 64-worker q32 fleet serves two chunks and a shorter third under the
profiler: every chunk is one ``fleet.stream.chunk`` holding its take,
snapshots, upload, launch and read-back, all tagged with the chunk's
index; the byte counters equal what crosses the host boundary; the third
chunk's new length shows as the one rebuild. The scopes leave the
optimized program unchanged but for metadata, and name every pass in it.
"""
from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet.scheduler import (FleetScheduler, RequestStream,
                                   run_fleet_stream)
from repro.fleet.state import sched_state_as_tuple, state_as_tuple
from repro.fleet.workloads import har_workload, lm_workload
from repro.launch.fleet import build_dispatch_pool, make_power_matrix
from repro.obs.profile import profiled, scope

DT = 0.01
N = 64
CK = 20
TAIL = 7
N_STEPS = 2 * CK + TAIL
CHUNK_CHILDREN = ("fleet.stream.take", "fleet.stream.snapshot",
                  "fleet.serve.upload", "fleet.serve.readback",
                  "fleet.stream.record")
SCOPES = ("fleet.admit", "fleet.shed", "fleet.plan", "fleet.dispatch",
          "fleet.dispatch.rank", "fleet.dispatch.queues",
          "fleet.dispatch.afford", "fleet.dispatch.scatter", "fleet.assign", "fleet.tick",
          "fleet.collect", "fleet.evict")

Ev = collections.namedtuple("Ev", "name start end stats")


def _serve(n_steps=N_STEPS, shards=1, rebalance_every=0):
    power = make_power_matrix(["SOR", "RF", "SOM", "SIM"], 4, 2.0, DT, 0)
    wls = [har_workload(), lm_workload()]
    pool = build_dispatch_pool(power, DT, N, wls, 0, backend="jax",
                               kernel="q32", fleet_placement="single")
    sch = FleetScheduler(pool, wls, sched="forecast", shards=shards,
                         rebalance_every=rebalance_every)
    stream = RequestStream(8.0 * N, np.array([0.6, 0.4]), n_steps, DT,
                           seed=1)
    return pool, sch, stream


def _host_spans(trace_dir) -> list[Ev]:
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted((Ev(e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for plane in pd.planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("fleet.")),
                  key=lambda e: e.start)


def _nbytes(arrays) -> int:
    return sum(np.asarray(a).nbytes for a in jax.tree.leaves(arrays))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    pool, sch, stream = _serve()
    d = str(tmp_path_factory.mktemp("profile"))
    with profiled(d):
        summary = run_fleet_stream(pool, sch, stream, N_STEPS,
                                   chunk_ticks=CK, dispatch_every=10)
    assert summary["stream"]["n_chunks"] == 3
    return pool, sch, _host_spans(d)


def test_each_chunk_holds_its_spans(traced):
    _, _, spans = traced
    chunks = [s for s in spans if s.name == "fleet.stream.chunk"]
    assert [s.stats["step_num"] for s in chunks] == [0, 1, 2]
    assert [s.stats["chunk"] for s in chunks] == [0, 1, 2]
    for c, ch in enumerate(chunks):
        inner = [s for s in spans if s is not ch
                 and ch.start <= s.start and s.end <= ch.end]
        names = collections.Counter(s.name for s in inner)
        assert {s.stats["chunk"] for s in inner} == {c}
        for name in CHUNK_CHILDREN:
            assert names[name] == (2 if name.endswith("snapshot") else 1)
        assert names["fleet.serve.call"] + names["fleet.serve.compile"] == 1
        order = [s.name for s in inner if s.name.startswith("fleet.serve")]
        assert order[0] == "fleet.serve.upload"
        assert order[-1] == "fleet.serve.readback"


def test_byte_counters_equal_the_arrays_that_cross(traced):
    pool, sch, spans = traced
    state = _nbytes(state_as_tuple(pool.state))
    sched = _nbytes(sched_state_as_tuple(sch.state))
    inputs = _nbytes(pool._jax._worker_inputs(sch.params))
    w = sch.params.W
    up = [s.stats["bytes"] for s in spans if s.name == "fleet.serve.upload"]
    down = [s.stats["bytes"] for s in spans
            if s.name == "fleet.serve.readback"]
    # arrivals: (ticks, W) int64; the start tick: one int64
    assert up == [state + sched + inputs + k * w * 8 + 8
                  for k in (CK, CK, TAIL)]
    assert down == [state + sched] * 3


def test_one_rebuild_for_the_shorter_last_chunk(traced):
    _, _, spans = traced
    builds = [s.stats for s in spans if s.name == "fleet.serve.compile"]
    assert [(b["chunk"], b["n_ticks"], b["dispatch_every"], b["builds"])
            for b in builds] == [(0, CK, 10, 1), (2, TAIL, 10, 2)]
    calls = [s.stats["chunk"] for s in spans if s.name == "fleet.serve.call"]
    assert calls == [1]
    assert [b for b in builds if b["chunk"] == 2] == [builds[1]]


def _chunk_hlo(pool, sch, sharded: bool) -> str:
    """The optimized HLO of the pool's compiled CK-tick chunk program."""
    bk = pool._jax
    (key, fn), = [(k, f) for k, f in bk._serve_compiled.items()
                  if k[0] == CK]
    sp = sch.params
    K = sp.shards

    def resh(x):
        a = np.asarray(x)
        return a.reshape((K, N // K) + a.shape[1:])

    with jax.enable_x64(True):
        if sharded:
            from repro.fleet import sched as S
            host = ({"fs": tuple(resh(x) for x in state_as_tuple(pool.state)),
                     "ss": sched_state_as_tuple(sch.state),
                     "arr": S.split_counts(np.zeros((CK, sp.W), np.int64),
                                           K),
                     **bk._worker_inputs(sp, resh)}, np.int64(0))
        else:
            host = (state_as_tuple(pool.state),
                    sched_state_as_tuple(sch.state), bk._worker_inputs(sp),
                    np.zeros((CK, sp.W), np.int64), np.int64(0))
        return fn.lower(*jax.tree.map(jnp.asarray, host)).compile().as_text()


def _scopes_in(hlo: str) -> set[str]:
    return {m for path in re.findall(r'op_name="([^"]*)"', hlo)
            for m in path.split("/") if m.startswith("fleet.")}


def test_every_pass_is_scoped_in_the_chunk_program(traced):
    pool, sch, _ = traced
    assert _scopes_in(_chunk_hlo(pool, sch, sharded=False)) == set(SCOPES)


def test_sharded_program_scopes_the_rebalance():
    pool, sch, stream = _serve(n_steps=CK, shards=2, rebalance_every=10)
    run_fleet_stream(pool, sch, stream, CK, chunk_ticks=CK,
                     dispatch_every=10)
    assert (_scopes_in(_chunk_hlo(pool, sch, sharded=True))
            == set(SCOPES) | {"fleet.rebalance"})


def test_scopes_change_only_metadata():
    """The same program traced with and without a scope: the optimized
    HLO differs in ``op_name`` metadata alone."""
    def f(x, named):
        with scope("fleet.a", jnp if named else np):
            y = jnp.sin(x) * 2.0
        return jnp.cumsum(y)

    x = jnp.ones(256)
    texts = [jax.jit(lambda x, n=n: f(x, n)).lower(x).compile().as_text()
             for n in (False, True)]
    assert "fleet.a" in texts[1] and "fleet.a" not in texts[0]

    def strip(t):
        t = t[t.index("\n%"):]
        return re.sub(r", metadata=\{[^}]*\}", "", t)
    assert strip(texts[0]) == strip(texts[1])


def test_launcher_profile_dir_records_the_serve(tmp_path):
    from repro.launch.fleet import main
    main(["--workers", "16", "--duration", "2", "--backend", "jax",
          "--kernel", "q32", "--scheduler", "on", "--stream",
          "--chunk-ticks", "100", "--profile-dir", str(tmp_path)])
    spans = _host_spans(str(tmp_path))
    names = collections.Counter(s.name for s in spans)
    # the scheduler's dispatch thresholds: (1 + 2 B) (W, K) tables, for
    # the three workloads' 142-wide knob tables at the default batch of 4
    thr, = [s for s in spans if s.name == "fleet.sched.thresholds"]
    assert thr.stats["entries"] == 9 * 3 * 142
    assert names["fleet.stream.chunk"] == 2
    assert names["fleet.serve.compile"] == 1
    assert names["fleet.serve.call"] == 1
