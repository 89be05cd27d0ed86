#!/usr/bin/env python3
"""Readings of the control at a cell's own size: the reference computed
with float32 in place of float64 (``reference.replay(..., ft=float32)``),
put in the program's place and compared with the reference, as a run
compares the program.

    python bench/control.py --workload fleet131k_q32_cold.poisson10s \
        --ticks 650 --seeds 1,2,3

Prints one JSON line per seed with the numbers compared. Both sides run
on the host; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ticks", type=int, required=True,
                    help="ticks replayed, as a run of the cell compares")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import numpy as np

    import deploy
    import reference as R
    from cell import load_cell
    from traffic import arrival_rows
    cell = load_cell(args.workload)
    c = cell.config
    power = deploy.power_matrix(c)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        rows = arrival_rows(cell.traffic, int(c["workers"]), c["mix"],
                            deploy.bank_ticks(c), float(c["dt_s"]),
                            seed + 1)
        ref = R.replay(c, seed, rows, args.ticks, power=power)
        t_ref = time.perf_counter() - t0
        ctl = R.replay(c, seed, rows, args.ticks, ft=np.float32,
                       power=power)
        cmp = R.compare(ctl, ref, float(c["dt_s"]))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "ticks": args.ticks,
                          "completed": int(ref[1]["sched.completed"]),
                          "counter_mismatches": cmp.counter_mismatches,
                          "float_rel_dev": cmp.float_rel_dev,
                          "first": cmp.first[:4],
                          "reference_s": t_ref,
                          "seconds": time.perf_counter() - t0}),
              flush=True)


if __name__ == "__main__":
    main()
