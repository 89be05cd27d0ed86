"""Anytime-SVM prefix scoring — Pallas TPU kernel.

The TPU-native grain of the paper's per-feature refinement is a *feature
block* of 128 lanes (DESIGN.md): scores = X[:, :p] @ W[:, :p]^T + b with p
a runtime scalar rounded into block space. Feature blocks beyond p are
skipped entirely (@pl.when on the prefetched scalar), so refinement cost
is proportional to ceil(p/128) — the incremental-accumulation trick of
§3.2 with MXU-shaped units. A partial-block tail is lane-masked.

Grid: (batch_blocks, feature_blocks), feature innermost, accumulating the
(bq, C) score tile in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(p_ref,  # scalar prefetch: (1,) int32 = feature prefix length
            x_ref, w_ref, b_ref, o_ref, acc_ref,
            *, block_f: int, n_f: int):
    jf = pl.program_id(1)

    @pl.when(jf == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(
            b_ref[...].astype(jnp.float32), acc_ref.shape)

    p = p_ref[0]

    @pl.when(jf * block_f < p)
    def _step():
        x = x_ref[...].astype(jnp.float32)  # (bq, bf)
        w = w_ref[...].astype(jnp.float32)  # (C, bf)
        # lane-mask the partial tail block (features >= p contribute 0)
        col = jf * block_f + jax.lax.broadcasted_iota(
            jnp.int32, x.shape, 1)
        x = jnp.where(col < p, x, 0.0)
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jf == n_f - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_f", "interpret"))
def anytime_svm_scores(x, w, b, p_features, *, block_b: int = 8,
                       block_f: int = 128, interpret: bool = False):
    """x: (B, F) ordered/standardized; w: (C, F) ordered; b: (C,);
    p_features: scalar int32. Returns (B, C) prefix scores."""
    B, F = x.shape
    C = w.shape[0]
    assert B % block_b == 0 and F % block_f == 0
    n_b = B // block_b
    n_f = F // block_f
    kernel = functools.partial(_kernel, block_f=block_f, n_f=n_f)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_b, n_f),
        in_specs=[
            pl.BlockSpec((block_b, block_f), lambda ib, jf, p: (ib, jf)),
            pl.BlockSpec((C, block_f), lambda ib, jf, p: (0, jf)),
            pl.BlockSpec((1, C), lambda ib, jf, p: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, C), lambda ib, jf, p: (ib, 0)),
        scratch_shapes=[pltpu.VMEM((block_b, C), jnp.float32)],
    )
    p_arr = jnp.asarray(p_features, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(p_arr, x, w, b.reshape(1, C))
