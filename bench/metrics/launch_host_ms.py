"""Mean per chunk, in the traced stretch, of the ``bench.run_serve`` span
less the device-busy time inside it: the host side of a chunk launch
(uploads of the state and the per-worker inputs, dispatch, read-back)."""


def read(run):
    t = run.trace
    if t is None:
        return None
    serves = [s for s in t.spans if s.name == "bench.run_serve"]
    if not serves:
        return None
    return sum((s.end - s.start) - s.busy for s in serves) / len(serves) \
        * 1e-6
