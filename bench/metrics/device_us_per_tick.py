"""Device-busy time inside the ``bench.run_serve`` spans of the traced
stretch, over the ticks they served: the scan body (control-plane passes
and the tick) per tick, on the device."""


def read(run):
    t = run.trace
    if t is None:
        return None
    serves = [s for s in t.spans if s.name == "bench.run_serve"]
    busy = sum(s.busy for s in serves)
    if not serves or busy <= 0:
        return None
    return busy * 1e-3 / (len(serves) * run.chunk_ticks)
