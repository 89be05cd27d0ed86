"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid (batch*head, chunks) with the (N, P) state carried in VMEM scratch.
Per-head scalar decay makes the intra-chunk decay a (Q, Q) matrix (cheaper
than RWKV6's per-channel case); everything lands on the MXU as (Q, Q) x
(Q, P) and (N, Q) x (Q, P) mat muls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref,
            *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)  # (Q, 1)
    a = a_ref[0].astype(jnp.float32)  # (Q, 1) <= 0
    bm = b_ref[0].astype(jnp.float32)  # (Q, N)
    cm = c_ref[0].astype(jnp.float32)  # (Q, N)
    cum = jnp.cumsum(a, axis=0)  # (Q, 1)
    decay = jnp.exp(cum - cum.T)  # (Q, Q); <=1 on/below diagonal
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(q_idx == s_idx, 1.0,
                      jnp.where(q_idx > s_idx, decay, 0.0))
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    m = cb * decay * dt.T  # (Q, Q) x dt_s
    y = jax.lax.dot(m.astype(x.dtype), x,
                    preferred_element_type=jnp.float32)  # (Q, P)
    # state contribution: y_t += exp(cum_t) * C_t . h0
    y = y + jnp.exp(cum) * jax.lax.dot(cm, h_ref[...],
                                       preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    # chunk-end state: h = exp(cum_last) h0 + sum_s exp(cum_last-cum_s)
    #                   dt_s B_s x_s^T
    last = cum[-1:, :]  # (1, 1)
    sdecay = jnp.exp(last - cum)  # (Q, 1)
    bw = bm * (sdecay * dt)  # (Q, N)
    h_ref[...] = (jnp.exp(last) * h_ref[...]
                  + jax.lax.dot_general(
                      bw, x, (((0,), (0,)), ((), ())),
                      preferred_element_type=jnp.float32))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, B_mat, C_mat, *, chunk: int = 64,
                    interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); A: (H,) > 0; B/C: (B, L, N).

    Returns y: (B, L, H, P) fp32. Matches models.ssm.ssd_scan (h0 = 0).
    """
    Bsz, L, H, P = x.shape
    N = B_mat.shape[-1]
    assert L % chunk == 0
    n_chunks = L // chunk
    a = (-A[None, None, :] * dt)  # (B, L, H)
    # lay out as (B*H, L, ...) streams
    xf = x.transpose(0, 2, 1, 3).reshape(Bsz * H, L, P)
    dtf = dt.transpose(0, 2, 1).reshape(Bsz * H, L, 1)
    af = a.transpose(0, 2, 1).reshape(Bsz * H, L, 1)
    bf = jnp.broadcast_to(B_mat[:, None], (Bsz, H, L, N)).reshape(
        Bsz * H, L, N)
    cf = jnp.broadcast_to(C_mat[:, None], (Bsz, H, L, N)).reshape(
        Bsz * H, L, N)
    kernel = functools.partial(_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(Bsz * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda bh, ic: (bh, ic, 0)),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((Bsz * H, L, P), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xf, dtf, af, bf, cf)
    return out.reshape(Bsz, H, L, P).transpose(0, 2, 1, 3)
