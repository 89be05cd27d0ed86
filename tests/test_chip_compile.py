"""Compile the serve path for a described TPU v5e, with no chip attached.

The TPU compiler is installed wherever libtpu is, and it refuses at
compile time what interpret mode never sees: Pallas block shapes off the
(8, 128) tiling, Mosaic ops it cannot legalize, 64-bit ops XLA:TPU does
not emulate. The serve programs themselves take minutes to compile at
fleet sizes, so they are covered by ``chip_smoke.py`` on the chip; these
tests keep the kernel and the control plane's TPU forms, each a few
seconds to compile.
"""
from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for(one_chip):
    """``compile_for(fn, *shape_dtype_pairs)`` compiles ``fn`` for one v5e
    chip and returns the compiled program. The persistent compilation
    cache is off meanwhile: an entry written for a described chip cannot
    be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_for(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        with jax.enable_x64(True):
            return jax.jit(fn).lower(*args).compile()

    yield compile_for
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_serve_tick_kernel_compiles_at_131072_workers(compile_for):
    """The Pallas serve megakernel at the smoke's fleet size: its ledger
    block is (8, 128)-tiled and its while-loop carries int32 masks, the
    two forms Mosaic accepts."""
    from repro.fleet import qtick as Q
    from repro.kernels import serve_tick as K
    from repro.launch.fleet import (WORKLOAD_FACTORIES, build_dispatch_pool,
                                    make_power_matrix)
    n = 131072
    wls = [WORKLOAD_FACTORIES[k]() for k in ("har", "harris", "lm")]
    pool = build_dispatch_pool(make_power_matrix(["RF"], 1, 1.0), 0.01, 8,
                               wls, kernel="pallas")
    qp = Q.quantize_fleet(pool.params)
    w, u = qp.UCQ.shape
    pad8 = lambda k: -(-k // 8) * 8  # noqa: E731
    i32 = jnp.int32
    rw = [((n,), jnp.bool_ if f in K.BOOL_FIELDS else i32)
          for f in K.RW_FIELDS]
    specs = (rw + [((n,), i32)] * (len(K.RO_FIELDS) + 4)
             + [((pad8(w * u), 128), i32), ((pad8(w), 128), i32),
                ((pad8(w), 128), i32), ((n,), i32), ((), i32)])
    n_rw, n_ro = len(K.RW_FIELDS), len(K.RO_FIELDS)

    def tick(*a):
        rw = dict(zip(K.RW_FIELDS, a[:n_rw]))
        ro = dict(zip(K.RO_FIELDS, a[n_rw:n_rw + n_ro]))
        consts = dict(zip(("e_on", "e_off", "e_max", "estep"),
                          a[n_rw + n_ro:n_rw + n_ro + 4]))
        tables = dict(zip(("uc", "fix", "emitc"),
                          a[n_rw + n_ro + 4:n_rw + n_ro + 7]))
        return K.serve_tick(rw, ro, consts, tables, a[-2], a[-1],
                            u_max=int(u), interpret=False)

    compiled = compile_for(tick, *specs)
    assert "tpu_custom_call" in compiled.as_text()


def test_control_plane_forms_compile_inside_a_scan(compile_for):
    """The sorts, prefix sums, knob lookups (float64 costs and int32
    quanta thresholds), table reads, bin counts, the inverse permutation,
    gathers, scatters and whole-tick latency sum of the array control
    plane (``repro.fleet.sched``) in the
    forms the serve scan uses, fused into one scan body: XLA:TPU refused
    the float64 bitcast a sort key would otherwise need, and ran out of
    scoped VMEM on emulated-int64 prefix sums fused inside the serve
    scan."""
    from repro.fleet import sched as S
    n, b = 1024, 4

    def body(c, _):
        key, valid, cnt, slots, ring, lat_sum, us, thr = c
        order = S._argsort(-key, valid, jnp)
        # a knob table's width; the compile needs only its shape
        csum = S._cumsum(cnt, jnp) + S._searchsorted_right(ring[:142], key,
                                                            jnp)
        # quantized dispatch: the rank on usable quanta and a count of a
        # row of int32 thresholds (sched.quanta_thresholds)
        q_order = S._argsort(-us, valid, jnp)
        csum = csum + S._searchsorted_right(thr[1], S._take(us, q_order,
                                                            jnp), jnp)
        us = (us + csum.astype(jnp.int32)) % 7_000_000
        # collect's knob-row read and latency-bin count (no gather, no
        # scatter)
        csum = csum + S._lookup(np.arange(142) * 3, us % 142, jnp)
        lat_sum = lat_sum + jnp.sum(S._bincount(slots % 65, 64, jnp))
        rank = S._cumsum_slots(slots, jnp)
        phys = rank % ring.shape[0]
        got = S._take(ring, phys, jnp)
        ring = S._scatter_set(ring, phys, got + 1.0, jnp)
        key = key + S._take(key, order, jnp) * 1e-3
        slots = slots + S._rows(slots, S._inverse_permutation(order, jnp),
                                jnp) % 3
        cnt = (cnt + csum) % 5
        ticks = jnp.rint(key / 0.01).astype(jnp.int64)
        lat_sum = lat_sum + jnp.sum(jnp.where(valid, ticks, 0))
        return (key, valid, cnt, slots, ring, lat_sum, us, thr), None

    def fn(*c):
        return lax.scan(body, c, None, length=4)[0]

    compiled = compile_for(fn, ((n,), jnp.float64), ((n,), jnp.bool_),
                           ((n,), jnp.int64), ((n, b), jnp.int64),
                           ((4096,), jnp.float64), ((), jnp.int64),
                           ((n,), jnp.int32), ((b, 142), jnp.int32))
    assert compiled.memory_analysis() is not None


def test_shed_compiles_without_a_gather_at_131072_workers(compile_for):
    """Shed over the 131,072-worker queue rings (3 queues of 4,096 +
    131,072 * 4 slots of float64 arrival times) reads them in physical
    order: the optimized program holds no gather, which XLA:TPU would
    run as two 32-bit gathers of 1.59 M indices per dispatch tick."""
    import dataclasses
    from repro.fleet import sched as S
    from repro.fleet.worker import FleetWorkerPool
    from repro.fleet.workloads import har_workload, harris_workload, \
        lm_workload
    wls = [har_workload(), harris_workload(), lm_workload()]
    pool = FleetWorkerPool(np.full((1, 100), 1e-3), 0.01,
                           workloads=[w.costs for w in wls],
                           mode="dispatch", n_workers=8)
    sp = S.make_sched_params(pool.params, wls, max_batch=4)
    ss = S.make_sched_state(sp)
    ss = S.SS(*(getattr(ss, f) for f in S.SS._fields))
    q = 4096 + 131_072 * 4
    sp = dataclasses.replace(sp, Q=q)

    def fn(q_t, q_head, q_len, t):
        out = S.shed(sp, ss._replace(q_t=q_t, q_head=q_head, q_len=q_len),
                     t, jnp)
        return out.q_head, out.q_len, out.shed

    compiled = compile_for(fn, ((sp.W, q), jnp.float64),
                           ((sp.W,), jnp.int64), ((sp.W,), jnp.int64),
                           ((), jnp.float64))
    text = compiled.as_text()
    assert re.search(r"\breduce\(", text)  # the row min, so it is shed
    assert not re.search(r"\bgather\(", text)
