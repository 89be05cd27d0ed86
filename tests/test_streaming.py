"""Streaming online serve: causal (prefix-only) forecaster fitting.

Two families of guarantees pinned here:

- sufficient-statistics equivalence: the incrementally-updated
  :class:`repro.core.forecast.CausalFitState` — fed the observed harvest
  prefix in any chunking, including single-column updates that straddle
  the AR(p) regression-row boundary — compiles to the same
  :class:`RowForecast` as a one-shot batch fit on the concatenated
  prefix;
- causality: a refit at tick k reads only ``power[:, :k]``. Mutating
  every sample at tick >= k changes nothing — not the compiled tables,
  not ``plan_budget``'s routing budget.

The chunked-vs-whole-trace differential suite for the streaming serve
loop itself lives further down (tests the `--stream` serve path).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core.forecast import (CausalFitState, FORECASTER_MODES,
                                 RowForecast, fit_causal_forecast,
                                 fit_row_forecast, zero_row_forecast)
from repro.fleet import sched as _sched
from repro.fleet.scheduler import FleetScheduler
from repro.fleet.workloads import har_workload, lm_workload
from repro.launch.fleet import (build_dispatch_pool, make_power_matrix,
                                trace_family_labels)

DT = 0.01
TRACES = ["SOR", "SIR", "RF", "SOM", "SIM"]


def _bank(duration_s: float = 6.0, rows: int = 5, seed: int = 0):
    return make_power_matrix(TRACES[:rows], rows, duration_s, DT, seed)


def _chunkings(m: int, seed: int = 0):
    """A few partitions of m columns: one shot, single columns, and a
    random mixed chunking (sizes 1..17, exercising sub-order chunks)."""
    rng = np.random.default_rng(seed)
    mixed = []
    left = m
    while left > 0:
        k = int(min(left, rng.integers(1, 18)))
        mixed.append(k)
        left -= k
    return [[m], [1] * m, mixed]


def _assert_rf_close(a: RowForecast, b: RowForecast, rtol=1e-7,
                     atol=1e-10, exact=False):
    assert a.order == b.order
    for f in ("MU", "W", "THRESH", "HI", "LO"):
        if exact:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                          err_msg=f)
        else:
            np.testing.assert_allclose(getattr(a, f), getattr(b, f),
                                       rtol=rtol, atol=atol, err_msg=f)
    np.testing.assert_array_equal(a.model, b.model)


# ---------------------------------------------------------------------------
# windowed sufficient statistics == batch fit on the same prefix
# ---------------------------------------------------------------------------


class TestCausalSufficientStats:

    @pytest.mark.parametrize("m", [64, 317])
    def test_ou_chunked_matches_batch(self, m):
        power = _bank()
        prefix = power[:, :m]
        batch = fit_row_forecast(prefix, "ou", 50)
        for chunks in _chunkings(m, seed=m):
            st = CausalFitState("ou", power.shape[0])
            j = 0
            for k in chunks:
                st.update(prefix[:, j:j + k])
                j += k
            assert st.m == m
            _assert_rf_close(st.compile(50), batch)

    @pytest.mark.parametrize("order", [1, 3])
    def test_arp_chunked_matches_batch(self, order):
        power = _bank()
        m = 201
        prefix = power[:, :m]
        batch = fit_row_forecast(prefix, "arp", 50, arp_order=order)
        for chunks in _chunkings(m, seed=order):
            st = CausalFitState("arp", power.shape[0], arp_order=order)
            j = 0
            for k in chunks:
                st.update(prefix[:, j:j + k])
                j += k
            # raw-moment accumulation reassociates the sums, so demand
            # tight agreement rather than bit equality
            _assert_rf_close(st.compile(50), batch, rtol=1e-7, atol=1e-9)

    @pytest.mark.parametrize("mode", ["occlusion", "burst", "auto"])
    def test_buffered_modes_match_batch_exactly(self, mode):
        power = _bank()
        m = 150
        prefix = power[:, :m]
        families = trace_family_labels(TRACES, power.shape[0])
        batch = fit_row_forecast(prefix, mode, 50, families=families)
        st = CausalFitState(mode, power.shape[0], families=families)
        for j in range(0, m, 13):
            st.update(prefix[:, j:j + 13])
        _assert_rf_close(st.compile(50), batch, exact=True)

    def test_one_shot_wrapper_matches_state(self):
        power = _bank()
        prefix = power[:, :99]
        for mode in FORECASTER_MODES:
            a = fit_causal_forecast(prefix, mode, 25)
            st = CausalFitState(mode, power.shape[0])
            b = st.update(prefix).compile(25)
            _assert_rf_close(a, b, exact=True)

    def test_zero_prior_below_min_ticks(self):
        power = _bank()
        st = CausalFitState("ou", power.shape[0])
        st.update(power[:, :st.min_ticks - 1])
        rf = st.compile(50)
        _assert_rf_close(rf, zero_row_forecast(power.shape[0], 1),
                         exact=True)
        # ... and one more column crosses the threshold
        st.update(power[:, st.min_ticks - 1:st.min_ticks])
        assert (st.compile(50).MU > 0).any()

    def test_arp_min_ticks_scales_with_order(self):
        st = CausalFitState("arp", 3, arp_order=9)
        assert st.order == 9 and st.min_ticks == 11
        assert CausalFitState("ou", 3).order == 1

    def test_update_copies_its_input(self):
        """The state must survive callers mutating the columns after
        ``update`` — the streaming loop hands it views into the live
        power bank."""
        power = _bank()
        cols = power[:, :64].copy()
        for mode in ("ou", "arp", "auto"):
            st = CausalFitState(mode, power.shape[0])
            st.update(cols[:, :40])
            st.update(cols[:, 40:])
            before = st.compile(50)
            cols *= 7.0
            _assert_rf_close(st.compile(50), before, exact=True)
            cols[:] = power[:, :64]

    def test_update_validates_shape(self):
        st = CausalFitState("ou", 4)
        with pytest.raises(ValueError, match="columns"):
            st.update(np.zeros((3, 10)))
        with pytest.raises(ValueError, match="forecaster mode"):
            CausalFitState("nope", 4)


# ---------------------------------------------------------------------------
# causality: a refit at tick k never reads power[:, k:]
# ---------------------------------------------------------------------------


def _causal_sched(power, n_workers=32, seed=0, forecaster="ou", **kw):
    wls = [har_workload(), lm_workload()]
    pool = build_dispatch_pool(power, DT, n_workers, wls, seed)
    return pool, FleetScheduler(pool, wls, sched="forecast",
                                forecaster=forecaster,
                                forecaster_fit="causal", **kw)


class TestCausalityRegression:

    def test_causal_prior_is_zero_table(self):
        power = _bank()
        _, s = _causal_sched(power)
        n = s.pool.params.n
        np.testing.assert_array_equal(s.params.FC_MU, np.zeros(n))
        np.testing.assert_array_equal(s.params.FC_W, np.zeros((n, 1)))
        assert np.isinf(s.params.FC_THRESH).all()
        np.testing.assert_array_equal(s.params.FC_HI, np.zeros(n))
        np.testing.assert_array_equal(s.params.FC_LO, np.zeros(n))

    @pytest.mark.parametrize("forecaster", ["ou", "arp", "auto"])
    def test_refit_ignores_future_samples(self, forecaster):
        """Two fleets whose banks agree on [:, :k] and disagree
        everywhere after: after a causal refit at k, the compiled tables
        and the planning budget must be exactly identical."""
        power_a = _bank(duration_s=8.0)
        k = 400
        rng = np.random.default_rng(7)
        power_b = power_a.copy()
        power_b[:, k:] = rng.uniform(0.0, 1.0, power_b[:, k:].shape) \
            * (3.0 * power_a.max())
        fam = trace_family_labels(TRACES, power_a.shape[0])
        pool_a, sa = _causal_sched(power_a, forecaster=forecaster,
                                   trace_families=fam)
        pool_b, sb = _causal_sched(power_b, forecaster=forecaster,
                                   trace_families=fam)
        assert sa.refit_forecast(k) and sb.refit_forecast(k)
        for f in _sched.FC_FIELDS:
            np.testing.assert_array_equal(getattr(sa.params, f),
                                          getattr(sb.params, f),
                                          err_msg=f)
        # ... and so must the budget the dispatcher plans against
        # (lags drawn from the observed prefix — phase=None keeps the
        # cyclic gather inside [:, :k])
        p = pool_a.params
        budget = np.random.default_rng(1).uniform(
            0.0, 1.0, p.n) * np.asarray(sa.params.ECAP)
        out = []
        for pool, s in ((pool_a, sa), (pool_b, sb)):
            lags = _sched.power_lags(pool.params.power,
                                     pool.params.trace_index, k - 1,
                                     pool.params.T, s.params.fc_order)
            out.append(np.asarray(_sched.plan_budget(s.params, budget,
                                                     lags, p.eff)))
        np.testing.assert_array_equal(out[0], out[1])

    def test_full_fit_does_peek(self):
        """The inverse control: with the offline ``full`` fit the same
        future mutation DOES move the tables — the peeking the causal
        path exists to remove (and what makes the test above falsifiable).
        """
        power_a = _bank(duration_s=8.0)
        power_b = power_a.copy()
        power_b[:, 400:] *= 5.0
        wls = [har_workload()]
        mu = []
        for power in (power_a, power_b):
            pool = build_dispatch_pool(power, DT, 16, wls, 0)
            mu.append(FleetScheduler(pool, wls, sched="forecast",
                                     forecaster_fit="full").params.FC_MU)
        assert not np.array_equal(mu[0], mu[1])

    def test_refit_matches_one_shot_prefix_fit(self):
        power = _bank(duration_s=8.0)
        pool, s = _causal_sched(power)
        s.refit_forecast(150)
        s.refit_forecast(390)  # incremental: absorbs [150, 390)
        want = fit_causal_forecast(power[:, :390], "ou",
                                   s.params.lookahead_ticks)
        got = want.take(pool.params.trace_index)
        np.testing.assert_allclose(s.params.FC_MU, got.MU, rtol=1e-9)
        np.testing.assert_allclose(s.params.FC_W, got.W, rtol=1e-9)
        # a second refit at the same tick is a no-op
        fc = s.params.FC_W.copy()
        s.refit_forecast(390)
        np.testing.assert_array_equal(s.params.FC_W, fc)
        assert s.observed_ticks == 390

    def test_refit_clamps_to_trace_length(self):
        power = _bank(duration_s=2.0)
        _, s = _causal_sched(power)
        assert s.refit_forecast(10 * power.shape[1])
        assert s.observed_ticks == power.shape[1]

    def test_refit_noop_without_causal_fit(self):
        power = _bank()
        wls = [har_workload()]
        pool = build_dispatch_pool(power, DT, 16, wls, 0)
        s = FleetScheduler(pool, wls, sched="forecast",
                           forecaster_fit="full")
        fc = s.params.FC_MU.copy()
        assert not s.refit_forecast(200)
        np.testing.assert_array_equal(s.params.FC_MU, fc)

    def test_refit_keeps_compiled_scan_compatible(self):
        """A refit must only rebind the FC tables — every other field
        (identity for arrays, equality for scalars) stays put, which is
        what lets the fused serve scan keep its compiled functions."""
        power = _bank()
        _, s = _causal_sched(power)
        old = s.params
        s.refit_forecast(300)
        assert s.params is not old
        assert _sched.sched_params_compatible(old, s.params)
        assert not _sched.sched_params_compatible(None, s.params)
        # genuinely different geometry is incompatible
        other = dataclasses.replace(s.params, B=s.params.B + 1)
        assert not _sched.sched_params_compatible(s.params, other)
        # an FC table of different order (shape) is incompatible too
        wider = dataclasses.replace(
            s.params, FC_W=np.zeros((s.params.FC_W.shape[0], 4)))
        assert not _sched.sched_params_compatible(s.params, wider)

    def test_make_sched_params_rejects_unknown_fit(self):
        power = _bank()
        wls = [har_workload()]
        pool = build_dispatch_pool(power, DT, 8, wls, 0)
        with pytest.raises(ValueError, match="forecaster_fit"):
            FleetScheduler(pool, wls, sched="forecast",
                           forecaster_fit="clairvoyant")


# ---------------------------------------------------------------------------
# streaming serve differential suite: chunked == whole-trace, everywhere
# ---------------------------------------------------------------------------

import json

from repro.fleet.scheduler import (RequestStream, StreamClient,
                                   run_fleet, run_fleet_stream)

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:
    _HAS_HYPOTHESIS = False


def _mk_serve(backend, n_workers=16, duration_s=8.0, seed=0, shards=1,
              kernel="xla", placement="mesh", rebalance_every=0,
              forecaster="ou", forecaster_fit="full", arrival_seed=1,
              rate_scale=8.0, persist="none", grace_s=20.0):
    """One (pool, scheduler, stream, n_steps) serve fixture. Separate
    calls with the same arguments are bit-identical initial states, so
    a whole-trace run and a chunked run start from the same world."""
    n_steps = int(round(duration_s / DT))
    n_rows = min(8, n_workers)
    power = make_power_matrix(TRACES, n_rows, duration_s, DT, seed)
    wls = [har_workload(), lm_workload()]
    pool = build_dispatch_pool(power, DT, n_workers, wls, seed,
                               backend=backend, kernel=kernel,
                               fleet_placement=placement, persist=persist)
    sch = FleetScheduler(
        pool, wls, sched="forecast", forecaster=forecaster,
        trace_families=trace_family_labels(TRACES, n_rows),
        forecaster_fit=forecaster_fit, shards=shards,
        rebalance_every=rebalance_every, grace_s=grace_s)
    stream = RequestStream(rate_scale * n_workers,
                           np.array([0.6, 0.4]), n_steps, DT,
                           seed=arrival_seed)
    return pool, sch, stream, n_steps


def _blob(summary: dict) -> str:
    """Canonical full-summary comparison string. Only the "stream"
    block (per-chunk wall clocks are nondeterministic) is stripped —
    every counter, histogram, energy and quality field must match."""
    s = dict(summary)
    s.pop("stream", None)
    return json.dumps(s, sort_keys=True, default=str)


def _assert_backend_agreement(a: dict, b: dict):
    """Cross-backend (numpy vs jax) agreement: every discrete field —
    counters, histograms, latency percentiles, quality ledger — must be
    bit-equal; the reported energy sums only to float tolerance (XLA
    fuses/vectorizes the per-tick ``eff*pw*dt`` accumulation, so
    per-worker ``e_harvest`` carries compiler-dependent ULPs — a
    pre-existing property of the fused scan, orthogonal to chunking)."""
    a, b = dict(a), dict(b)
    ea, eb = a.pop("energy"), b.pop("energy")
    a.pop("stream", None)
    b.pop("stream", None)
    assert (json.dumps(a, sort_keys=True, default=str)
            == json.dumps(b, sort_keys=True, default=str))
    assert ea.keys() == eb.keys()
    for k in ("harvested_j", "work_j", "j_per_completed"):
        np.testing.assert_allclose(float(ea[k]), float(eb[k]),
                                   rtol=1e-9)


class TestStreamingServe:
    """The tentpole gate: a chunked steady-state run fed the identical
    arrival stream is bit-exact with the whole-trace launch on the full
    summary — for every backend, kernel, shard layout, and obs mode."""

    @pytest.mark.parametrize("n_workers", [1, 256])
    def test_chunked_equals_whole_trace_jax(self, n_workers):
        pool_w, sch_w, st_w, n_steps = _mk_serve("jax", n_workers)
        whole = run_fleet(pool_w, sch_w, st_w, n_steps)
        pool_c, sch_c, st_c, _ = _mk_serve("jax", n_workers)
        # 700 does not divide 800: the final chunk covers the remainder
        client = StreamClient(st_c, sch_c.params.W, n_steps)
        chunked = run_fleet_stream(pool_c, sch_c, client, n_steps,
                                   chunk_ticks=700)
        assert chunked["stream"]["n_chunks"] == 2
        assert chunked["stream"]["chunks"][-1]["ticks"] == 100
        assert _blob(whole) == _blob(chunked)

    def test_chunked_numpy_equals_jax(self):
        pool_w, sch_w, st_w, n_steps = _mk_serve("numpy")
        whole = run_fleet(pool_w, sch_w, st_w, n_steps)
        pool_n, sch_n, st_n, _ = _mk_serve("numpy")
        ch_np = run_fleet_stream(pool_n, sch_n, st_n, n_steps,
                                 chunk_ticks=333)
        pool_j, sch_j, st_j, _ = _mk_serve("jax")
        ch_jax = run_fleet_stream(pool_j, sch_j, st_j, n_steps,
                                  chunk_ticks=333)
        # the hard gate is same-backend: chunked == whole bit-exact
        assert _blob(whole) == _blob(ch_np)
        _assert_backend_agreement(ch_np, ch_jax)

    @pytest.mark.parametrize("chunk", [1, 7, 160, 999, 5000])
    def test_any_chunk_size_matches_whole_numpy(self, chunk):
        # the host reference loop: every chunking of the tick axis —
        # single ticks, sizes that straddle dispatch/evict boundaries,
        # chunks longer than the trace — reproduces the offline run
        pool_w, sch_w, st_w, n_steps = _mk_serve("numpy", 8,
                                                 duration_s=4.0)
        whole = run_fleet(pool_w, sch_w, st_w, n_steps)
        pool_c, sch_c, st_c, _ = _mk_serve("numpy", 8, duration_s=4.0)
        chunked = run_fleet_stream(pool_c, sch_c, st_c, n_steps,
                                   chunk_ticks=chunk)
        assert _blob(whole) == _blob(chunked)

    if _HAS_HYPOTHESIS:
        @given(chunk=st.integers(1, 500),
               arrival_seed=st.integers(0, 4),
               forecaster=st.sampled_from(["ou", "arp", "auto"]))
        @settings(max_examples=8, deadline=None)
        def test_property_chunking_invariance(self, chunk,
                                              arrival_seed,
                                              forecaster):
            pool_w, sch_w, st_w, n_steps = _mk_serve(
                "numpy", 8, duration_s=3.0, forecaster=forecaster,
                arrival_seed=arrival_seed)
            whole = run_fleet(pool_w, sch_w, st_w, n_steps)
            pool_c, sch_c, st_c, _ = _mk_serve(
                "numpy", 8, duration_s=3.0, forecaster=forecaster,
                arrival_seed=arrival_seed)
            chunked = run_fleet_stream(pool_c, sch_c, st_c, n_steps,
                                       chunk_ticks=chunk)
            assert _blob(whole) == _blob(chunked)

    def test_mesh_fleet_composition(self):
        # --mesh-fleet 8 with work stealing ON: the sharded host twin,
        # chunked host twin, and the single-device vmap of the K-shard
        # program all land on the same summary
        kw = dict(n_workers=32, shards=8, rebalance_every=20)
        pool_w, sch_w, st_w, n_steps = _mk_serve("numpy", **kw)
        whole = run_fleet(pool_w, sch_w, st_w, n_steps)
        pool_n, sch_n, st_n, _ = _mk_serve("numpy", **kw)
        ch_np = run_fleet_stream(pool_n, sch_n, st_n, n_steps,
                                 chunk_ticks=300)
        pool_wj, sch_wj, st_wj, _ = _mk_serve("jax",
                                              placement="single", **kw)
        whole_jax = run_fleet(pool_wj, sch_wj, st_wj, n_steps)
        pool_j, sch_j, st_j, _ = _mk_serve("jax", placement="single",
                                           **kw)
        ch_jax = run_fleet_stream(pool_j, sch_j, st_j, n_steps,
                                  chunk_ticks=300)
        assert _blob(whole) == _blob(ch_np)
        assert _blob(whole_jax) == _blob(ch_jax)
        _assert_backend_agreement(ch_np, ch_jax)

    @pytest.mark.slow
    def test_mesh_fleet_real_device_mesh(self, tmp_path):
        # the same gate over a real 8-device host-platform mesh: the
        # chunked stream on shard_map must equal the whole-trace run
        # (subprocess: device count is fixed at jax import)
        import os
        import subprocess
        import sys
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        base = [sys.executable, "-m", "repro.launch.fleet",
                "--workers", "32", "--duration", "8", "--scheduler",
                "on", "--backend", "jax", "--sched", "forecast",
                "--mesh-fleet", "8", "--fleet-placement", "mesh",
                "--rebalance-every", "0.2"]
        out_w = tmp_path / "whole.json"
        out_c = tmp_path / "chunk.json"
        subprocess.run(base + ["--json", str(out_w)], check=True,
                       env=env, capture_output=True)
        subprocess.run(base + ["--stream", "--chunk-ticks", "300",
                               "--json", str(out_c)], check=True,
                       env=env, capture_output=True)
        a = json.loads(out_w.read_text())["scheduled"]
        b = json.loads(out_c.read_text())["scheduled"]
        assert _blob(a) == _blob(b)

    def test_q32_kernel_composition(self):
        pool_w, sch_w, st_w, n_steps = _mk_serve("jax", kernel="q32")
        whole = run_fleet(pool_w, sch_w, st_w, n_steps)
        pool_c, sch_c, st_c, _ = _mk_serve("jax", kernel="q32")
        chunked = run_fleet_stream(pool_c, sch_c, st_c, n_steps,
                                   chunk_ticks=300)
        assert _blob(whole) == _blob(chunked)

    @pytest.mark.parametrize("mode", ["tele", "trace"])
    def test_obs_tele_chunked_equality(self, mode):
        # the in-scan telemetry plane sees GLOBAL tick indices from
        # every chunk: windowed channels (and, in trace mode, the event
        # ring) fill identically whether the trace runs as one launch,
        # many launches, or the host window across chunks
        from repro.obs import make_fleet_obs
        from repro.obs.state import ring_as_tuple, tele_as_tuple

        def run(backend, chunk):
            pool, sch, stream, n_steps = _mk_serve(backend)
            obs = make_fleet_obs(mode, pool.params, sch.params,
                                 n_steps, window=100)
            if chunk:
                run_fleet_stream(pool, sch, stream, n_steps,
                                 chunk_ticks=chunk, obs=obs)
            else:
                run_fleet(pool, sch, stream, n_steps, obs=obs)
            ring = () if obs.ring is None else ring_as_tuple(obs.ring)
            return tele_as_tuple(obs.tele) + tuple(ring)

        whole = run("jax", 0)
        if mode == "trace":
            assert int(np.asarray(whole[-1]).sum()) > 0  # events pushed
        for got in (run("jax", 300), run("numpy", 300)):
            assert len(got) == len(whole)
            for a, b in zip(whole, got):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))

    def test_causal_refit_stream_backend_agreement(self):
        # live causal refits between chunks: both backends refit from
        # the same observed prefix and stay bit-equal — and the fused
        # scan keeps ONE compiled function across refits (the new
        # tables flow in as runtime arguments, no re-trace)
        kw = dict(forecaster="arp", forecaster_fit="causal")
        pool_j, sch_j, st_j, n_steps = _mk_serve("jax", **kw)
        r_jax = run_fleet_stream(pool_j, sch_j, st_j, n_steps,
                                 chunk_ticks=200, refit_every=200)
        pool_n, sch_n, st_n, _ = _mk_serve("numpy", **kw)
        r_np = run_fleet_stream(pool_n, sch_n, st_n, n_steps,
                                chunk_ticks=200, refit_every=200)
        assert r_jax["stream"]["refits"] == 3
        assert r_np["stream"]["refits"] == 3
        _assert_backend_agreement(r_jax, r_np)
        assert len(pool_j._jax._serve_compiled) == 1

    def test_stream_block_records(self):
        pool, sch, stream, n_steps = _mk_serve("numpy", 8,
                                               duration_s=4.0)
        out = run_fleet_stream(pool, sch, stream, n_steps,
                               chunk_ticks=150, slo_p95_s=2.0)
        blk = out["stream"]
        chunks = blk["chunks"]
        assert blk["n_chunks"] == len(chunks) == 3
        assert [c["tick0"] for c in chunks] == [0, 150, 300]
        assert sum(c["ticks"] for c in chunks) == n_steps
        # chunk counter deltas tile the whole-run counters exactly
        for f in ("submitted", "completed", "shed", "rejected",
                  "lost", "evicted"):
            assert sum(c[f] for c in chunks) == out[f]
        assert blk["slo_p95_s"] == 2.0
        assert blk["slo_violations"] == sum(
            not c["slo_ok"] for c in chunks)

    def test_live_client_matches_offline_rows(self):
        stream = RequestStream(50.0, np.array([0.5, 0.5]), 200, DT,
                               seed=3)
        client = StreamClient(stream, 2, 200)
        got = np.concatenate([client.take(77), client.take(123)])
        np.testing.assert_array_equal(got, stream.counts_matrix(2))

    def test_chunk_ticks_must_be_positive(self):
        pool, sch, stream, n_steps = _mk_serve("numpy", 8,
                                               duration_s=1.0)
        with pytest.raises(ValueError, match="chunk_ticks"):
            run_fleet_stream(pool, sch, stream, n_steps, chunk_ticks=0)


# ---------------------------------------------------------------------------
# persistence plane x streaming: the exact disciplines under chunking
# ---------------------------------------------------------------------------


class TestPersistStreaming:
    """The exact ckpt/undolog disciplines (docs/persistence_plane.md)
    obey the same chunking-invariance gate as the approximate runtime:
    a chunked steady-state run is bit-exact with the whole-trace launch
    — including every persist-ledger field (FRAM joules, checkpoint or
    commit count, restore count) — and the NumPy per-tick reference
    agrees with the fused JAX launch on all of it."""

    # 30 s horizon with grace 60: long enough for energy-rich rows to
    # boot from the discharged capacitor, brown out mid-request, and
    # restore — the nonvacuousness assertions below depend on it
    _KW = dict(n_workers=16, duration_s=30.0, grace_s=60.0)

    @pytest.mark.parametrize("persist", ["ckpt", "undolog"])
    @pytest.mark.parametrize("backend,kernel",
                             [("numpy", "xla"), ("jax", "xla"),
                              ("jax", "q32")])
    def test_persist_chunked_equals_whole(self, persist, backend,
                                          kernel):
        kw = dict(self._KW, persist=persist, kernel=kernel)
        pool_w, sch_w, st_w, n_steps = _mk_serve(backend, **kw)
        whole = run_fleet(pool_w, sch_w, st_w, n_steps)
        pool_c, sch_c, st_c, _ = _mk_serve(backend, **kw)
        chunked = run_fleet_stream(pool_c, sch_c, st_c, n_steps,
                                   chunk_ticks=700)
        assert _blob(whole) == _blob(chunked)
        # nonvacuous: the run actually persisted state to NVM and
        # restored through at least one mid-request power failure
        e = whole["energy"]
        assert e["persists"] > 0 and e["restores"] > 0
        assert e["nvm_j"] > 0.0
        # exactness contract: power failures never lose a request
        assert whole["lost"] == 0

    @pytest.mark.parametrize("persist", ["ckpt", "undolog"])
    def test_persist_stream_backend_agreement(self, persist):
        kw = dict(self._KW, persist=persist)
        pool_n, sch_n, st_n, n_steps = _mk_serve("numpy", **kw)
        r_np = run_fleet_stream(pool_n, sch_n, st_n, n_steps,
                                chunk_ticks=700)
        pool_j, sch_j, st_j, _ = _mk_serve("jax", **kw)
        r_jax = run_fleet_stream(pool_j, sch_j, st_j, n_steps,
                                 chunk_ticks=700)
        _assert_backend_agreement(r_np, r_jax)
        # the persist ledger must agree bit-exactly — the persist-path
        # joule adds are data-dependent gathers of precomputed table
        # entries, identical in both evaluation orders
        for k in ("persists", "restores", "nvm_j"):
            assert r_np["energy"][k] == r_jax["energy"][k], k
        assert r_np["energy"]["restores"] > 0

    def test_persist_none_blob_unchanged(self):
        # persist="none" is the PR-9 streaming serve verbatim: the
        # explicit default compiles the identical program
        pool_a, sch_a, st_a, n_steps = _mk_serve("jax", 8,
                                                 duration_s=4.0)
        pool_b, sch_b, st_b, _ = _mk_serve("jax", 8, duration_s=4.0,
                                           persist="none")
        a = run_fleet(pool_a, sch_a, st_a, n_steps)
        b = run_fleet(pool_b, sch_b, st_b, n_steps)
        assert _blob(a) == _blob(b)

    if _HAS_HYPOTHESIS:
        @given(chunk=st.sampled_from([250, 700, 1300]),
               persist=st.sampled_from(["ckpt", "undolog"]),
               arrival_seed=st.integers(0, 3))
        @settings(max_examples=6, deadline=None)
        def test_property_power_failure_resume(self, chunk, persist,
                                               arrival_seed):
            """Mid-request power failure under the exact disciplines:
            whatever the chunking and arrival pattern, a worker that
            browns out mid-request restores from NVM, no request is
            ever LOST, and the completion counters land bit-identically
            in the host reference and the fused scan."""
            kw = dict(self._KW, persist=persist,
                      arrival_seed=arrival_seed)
            pool_w, sch_w, st_w, n_steps = _mk_serve("numpy", **kw)
            whole = run_fleet(pool_w, sch_w, st_w, n_steps)
            pool_c, sch_c, st_c, _ = _mk_serve("numpy", **kw)
            chunked = run_fleet_stream(pool_c, sch_c, st_c, n_steps,
                                       chunk_ticks=chunk)
            pool_j, sch_j, st_j, _ = _mk_serve("jax", **kw)
            r_jax = run_fleet_stream(pool_j, sch_j, st_j, n_steps,
                                     chunk_ticks=chunk)
            assert _blob(whole) == _blob(chunked)
            for k in ("submitted", "completed", "shed", "rejected",
                      "lost", "evicted"):
                assert whole[k] == r_jax[k], k
            assert whole["energy"]["restores"] > 0
            assert whole["lost"] == 0


class TestStreamBoundaries:
    """Satellite boundary pins: the arrival split below shard count,
    the admission ring wrapping its physical capacity, and the summary
    on an empty latency histogram."""

    def test_split_counts_fewer_than_shards(self):
        # 3 arrivals over 4 shards: low shards get the remainder, the
        # last gets none — and the split always sums to the stream
        np.testing.assert_array_equal(
            _sched.split_counts(np.array([3]), 4),
            np.array([[1], [1], [1], [0]]))
        np.testing.assert_array_equal(
            _sched.split_counts(np.array([4]), 4), np.ones((4, 1)))
        rng = np.random.default_rng(0)
        counts = rng.integers(0, 7, size=(50, 3))
        split = _sched.split_counts(counts, 8)
        np.testing.assert_array_equal(split.sum(axis=0), counts)

    def test_ring_wraparound_at_capacity(self):
        # Q = max_queue + n*max_batch physical slots; drive head/tail
        # around the modulus and check the stamped arrival times land
        # in the wrapped slots with exact admission accounting
        power = _bank()
        wls = [har_workload(), lm_workload()]
        pool = build_dispatch_pool(power, DT, 2, wls, 0)
        sch = FleetScheduler(pool, wls, max_queue=4, max_batch=1,
                             shed_after_s=0.5)
        sp = sch.params
        assert sp.Q == 4 + 2 * 1
        ss = sch._ss()
        ss = _sched.admit(sp, ss, np.array([4, 0]), 0.0, np)
        assert int(ss.q_len[0]) == 4
        # exactly at max_queue: further arrivals are rejected
        ss = _sched.admit(sp, ss, np.array([3, 0]), 0.01, np)
        assert int(ss.rejected) == 3 and int(ss.q_len[0]) == 4
        ss = _sched.shed(sp, ss, 1.0, np)
        assert int(ss.shed) == 4 and int(ss.q_len[0]) == 0
        assert int(ss.q_head[0]) == 4
        # refill: slots (4+j) % 6 = [4, 5, 0, 1] wrap the ring
        ss = _sched.admit(sp, ss, np.array([4, 0]), 2.0, np)
        np.testing.assert_array_equal(
            np.asarray(ss.q_t)[0, [4, 5, 0, 1]], np.full(4, 2.0))
        assert int(ss.submitted) == 11 and int(ss.q_len[0]) == 4
        # shedding reads the wrapped logical segment correctly too
        ss = _sched.shed(sp, ss, 3.0, np)
        assert int(ss.shed) == 8 and int(ss.q_head[0]) == 2

    def test_sched_summary_empty_latency_histogram(self):
        power = _bank()
        wls = [har_workload(), lm_workload()]
        pool = build_dispatch_pool(power, DT, 2, wls, 0)
        sch = FleetScheduler(pool, wls)
        out = sch.summary(1.0)
        assert out["completed"] == 0
        assert out["latency_mean_s"] == 0.0
        assert out["latency_p50_s"] == 0.0
        assert out["latency_p95_s"] == 0.0
        assert out["latency_p99_s"] == 0.0
        assert out["throughput_rps"] == 0.0
