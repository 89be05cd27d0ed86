"""Simulated worker-ticks served per wall second over the whole window:
workers x ticks, from the first chunk's arrival take to the return of
the chunk loop (snapshots and records included)."""


def read(run):
    return run.workers * run.ticks / (run.t_end - run.stamps[0])
