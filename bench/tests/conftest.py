"""Tests of the benchmark itself, on the CPU at tiny sizes: the trace
reduction, the traffic generator, the comparison that decides
``correct``, its control and the faults it must catch.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def tiny_config(**over) -> dict:
    """fleet1k_q32 at 64 workers: a window of 30 s simulated, of which
    the reference replays the first 20 s."""
    c = json.loads((BENCH / "configs" / "fleet1k_q32.json").read_text())
    c.update(name="tiny", workers=64, trace_rows=8, bank_s=60.0,
             min_window_chunks=300, trace_chunks=6, reference_ticks=2000)
    c.update(over)
    return c


def write_spec(root: Path, **over) -> Path:
    """A BENCHMARK.json with one cell, ``tiny.poisson10s``, beside its
    configuration and traffic files."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config(**over)))
    (root / "bench" / "traffic" / "poisson10s.json").write_text(
        (BENCH / "traffic" / "poisson10s.json").read_text())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tiny.poisson10s", "config": "tiny",
                          "traffic": "poisson10s", "chips": 1,
                          "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.poisson10s"]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


@pytest.fixture(scope="session")
def tiny_spec(tmp_path_factory) -> Path:
    """The tiny cell, whose reference replays a prefix of the window."""
    return write_spec(tmp_path_factory.mktemp("spec"))


@pytest.fixture(scope="session")
def tiny_spec_whole(tmp_path_factory) -> Path:
    """The tiny cell, whose reference replays the whole window."""
    return write_spec(tmp_path_factory.mktemp("spec"),
                      reference_ticks=10 ** 6)


@pytest.fixture(scope="session")
def tiny_spec_cold(tmp_path_factory) -> Path:
    """The tiny cell from empty capacitors, its window capped as
    ``fleet131k_q32_cold``'s is."""
    return write_spec(tmp_path_factory.mktemp("spec"),
                      initial_charge="empty", min_window_chunks=110,
                      max_window_ticks=1100)


@pytest.fixture(scope="session")
def tiny_spec_evict(tmp_path_factory) -> Path:
    """The tiny cell with a negative grace and one retry, so that
    assignments are evicted, requeued and lost: paths the benchmark's
    cells do not fire."""
    return write_spec(tmp_path_factory.mktemp("spec"), grace_s=-1.5,
                      max_retries=1, min_window_chunks=400,
                      reference_ticks=4000)
