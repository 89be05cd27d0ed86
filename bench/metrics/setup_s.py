"""Process start to window start: imports, the trace bank, the pool and
scheduler, tracing and compiling (or loading) the chunk program, the
warm-up chunks and the sizing of the window."""


def read(run):
    return run.stamps[0] - run.t_process
