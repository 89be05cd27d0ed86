"""The comparison that decides ``correct``: a sound run passes, and the
control and each fault the one-chip cells can have fail it.

Each run here drives the whole harness (``run.run``) on the CPU with the
look for a chip skipped, at the tiny configuration of ``conftest.py``.
"""
import json

import numpy as np
import pytest

import run as bench_run
from conftest import BENCH, tiny_config


def one_run(spec, seed=11):
    return bench_run.run(["--workload", "tiny.poisson10s", "--seed",
                          str(seed), "--seconds", "0.5", "--trace", "0"],
                         spec_path=spec, need_chip=False)


@pytest.mark.parametrize("spec", ["tiny_spec", "tiny_spec_whole",
                                  "tiny_spec_cold"])
def test_sound_run_is_correct(spec, request, capsys):
    out = one_run(request.getfixturevalue(spec), seed=2 ** 31 + 11)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"worker_ticks_per_s", "setup_s"}
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert list(json.loads(line))[-1] == "checks"


def test_sound_run_with_evictions_is_correct(tiny_spec_evict):
    import deploy
    import reference as R
    from traffic import arrival_rows
    out = one_run(tiny_spec_evict, seed=3)
    assert out["correct"], out["checks"]
    c = tiny_config(grace_s=-1.5, max_retries=1)
    rows = arrival_rows({"period_s": 10.0}, c["workers"], c["mix"],
                        deploy.bank_ticks(c), c["dt_s"], 4)
    st = R.replay(c, 3, rows, 4000)[1]
    assert min(st["sched.evicted"], st["sched.requeued"],
               st["sched.lost"]) > 0


def _unchanged(orig):
    def run_serve(self, sched, arrivals, **kw):
        self.steps_done += int(np.asarray(arrivals).shape[0])
    return run_serve


def _half_traffic(orig):
    def run_serve(self, sched, arrivals, **kw):
        arrivals = np.array(arrivals)
        arrivals[1::2] = 0
        return orig(self, sched, arrivals, **kw)
    return run_serve


def _altered_answer(orig):
    def run_serve(self, sched, arrivals, **kw):
        orig(self, sched, arrivals, **kw)
        emit = np.array(self.state.emit_count)
        emit[3] += 1
        self.state.emit_count = emit
    return run_serve


@pytest.mark.parametrize("spec", ["tiny_spec", "tiny_spec_cold"])
@pytest.mark.parametrize("fault", [_unchanged, _half_traffic,
                                   _altered_answer],
                         ids=["state_unchanged", "half_traffic",
                              "answer_altered"])
def test_fault_in_the_timed_path_is_not_correct(spec, request, monkeypatch,
                                                fault):
    from repro.fleet.worker import FleetWorkerPool
    monkeypatch.setattr(FleetWorkerPool, "run_serve",
                        fault(FleetWorkerPool.run_serve))
    out = one_run(request.getfixturevalue(spec))
    assert not out["correct"]
    assert out["checks"]["counter_mismatches"]["value"] > 0


@pytest.mark.parametrize("charge,ticks", [("uniform", 3000), ("empty", 1100)])
def test_control_fails(charge, ticks):
    """The reference computed with float32 (the control) against the
    reference fails the comparison on every seed, charged or from empty
    capacitors (where only arrival times are floats)."""
    import numpy as np

    import deploy
    import reference as R
    from traffic import arrival_rows
    c = tiny_config(initial_charge=charge)
    traffic = json.loads((BENCH / "traffic" / "poisson10s.json").read_text())
    power = deploy.power_matrix(c)
    for seed in (1, 2, 3):
        rows = arrival_rows(traffic, c["workers"], c["mix"],
                            deploy.bank_ticks(c), c["dt_s"], seed + 1)
        ref = R.replay(c, seed, rows, ticks, power=power)
        assert (ref[1]["sched.completed"] > 0) == (charge == "uniform")
        ctl = R.replay(c, seed, rows, ticks, ft=np.float32, power=power)
        cmp = R.compare(ctl, ref, c["dt_s"])
        assert (cmp.counter_mismatches > 0
                or cmp.float_rel_dev > 3 * R.LIMITS["float_rel_dev"]), cmp


def test_traced_run_without_device_ops_fails(tiny_spec):
    """On the CPU the trace holds no TPU operation: the traced run exits
    with an error instead of reporting no per-layer metric."""
    with pytest.raises(SystemExit) as e:
        bench_run.run(["--workload", "tiny.poisson10s", "--seed", "5",
                       "--seconds", "0.5", "--trace", "1"],
                      spec_path=tiny_spec, need_chip=False)
    assert "no device operation" in str(e.value)
