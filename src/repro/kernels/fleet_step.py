"""Fleet capacitor-bank harvest update — Pallas TPU kernel.

The hot inner stage of the fleet scan (`repro.fleet.backend_jax`): charge
N capacitors by one trace tick, ``v' = min(sqrt(2 e / C), v_max)`` with
``e = 0.5 C v^2 + eff p dt``. Pure VPU work: the (N,) worker axis is
reshaped into (rows, 128) lanes and tiled (block_rows, 128) per grid step
via the shared ``repro.kernels.tiling`` helpers; C and v_max ride along
as per-worker arrays so heterogeneous fleets pay nothing extra.
``interpret=True`` runs the same kernel through the Pallas interpreter
for CPU-only CI environments.

This is the TPU fast path; the jnp expression in ``core.energy`` is the
float64 reference the tests compare against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANES, pad_to_tiles, tile_rows, untile


def _harvest_kernel(v_ref, p_ref, c_ref, vmax_ref, o_ref, *,
                    eff: float, dt: float):
    v = v_ref[...]
    c = c_ref[...]
    e = 0.5 * c * v * v + eff * p_ref[...] * dt
    o_ref[...] = jnp.minimum(jnp.sqrt(2.0 * e / c), vmax_ref[...])


@functools.partial(jax.jit, static_argnames=("eff", "dt", "block_rows",
                                             "interpret"))
def harvest_step(v, power_w, capacitance_f, v_max, *, eff: float, dt: float,
                 block_rows: int = 8, interpret: bool = False):
    """One harvest tick for N capacitors; all array args are (N,).

    Returns the (N,) post-harvest voltages. N is padded up to a whole
    (block_rows, 128) tile grid internally; pad lanes use C=1 so the
    padded sqrt stays finite (their output is sliced off).
    """
    n = v.shape[0]
    dtype = v.dtype
    rows, _ = tile_rows(n, block_rows)

    def prep(x, fill):
        return pad_to_tiles(x, n, rows, fill, dtype)

    spec = pl.BlockSpec((block_rows, LANES), lambda g: (g, 0))
    out = pl.pallas_call(
        functools.partial(_harvest_kernel, eff=eff, dt=dt),
        grid=(rows // block_rows,),
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(prep(v, 0.0), prep(power_w, 0.0), prep(capacitance_f, 1.0),
      prep(v_max, 0.0))
    return untile(out, n)
