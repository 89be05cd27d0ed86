"""RWKV6 WKV recurrence — Pallas TPU chunked kernel.

One (batch*head) stream per grid row; the time dimension is chunked with
the (N, N) WKV state carried in VMEM scratch across sequential grid steps.
Within a chunk the recurrence is evaluated in its stable closed form (all
decay exponents <= 0, see models/rwkv.py): an O(Q^2 N) intra-chunk matrix
+ a state term — MXU work instead of a scalar time loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref,
            *, chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)  # (Q, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    logw = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, N) bonus
    cum = jnp.cumsum(logw, axis=0)  # (Q, N) decreasing
    cum_prev = cum - logw
    # A[t,s] = sum_n r_t k_s exp(cum_prev_t - cum_s), strictly causal
    rd = r * jnp.exp(cum_prev)  # stable: exponents <= 0 after product
    # NOTE: exp(cum_prev_t - cum_s) does not factor exactly; evaluate the
    # O(Q^2 N) sum via a masked loop over N-blocks is overkill at N<=64,
    # so materialise (Q, Q, N) in registers/VMEM: chunk=16/32 keeps it tiny.
    diff = cum_prev[:, None, :] - cum[None, :, :]  # (Q, Q, N) <= 0 (causal)
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    strict = (q_idx > s_idx)[:, :, None]
    amat = jnp.sum(jnp.where(strict, jnp.exp(diff), 0.0)
                   * r[:, None, :] * k[None, :, :], axis=-1)  # (Q, Q)
    y = jax.lax.dot(amat.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)  # (Q, 1)
    y = y + bonus * v
    y = y + jax.lax.dot(rd.astype(jnp.float32), s_ref[...],
                        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    # chunk-end state update
    last = cum[-1:, :]  # (1, N)
    sdecay = jnp.exp(last - cum)  # (Q, N) <= 1
    ks = k * sdecay
    s_ref[...] = (jnp.exp(last).T * s_ref[...]
                  + jax.lax.dot_general(
                      ks, v, (((0,), (0,)), ((), ())),
                      preferred_element_type=jnp.float32))
    del n_chunks


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, logw, u, *, chunk: int = 32,
              interpret: bool = False):
    """r/k/v/logw: (B, H, L, N); u: (H, N). Returns y (B, H, L, N)."""
    B, H, L, N = r.shape
    assert L % chunk == 0
    n_chunks = L // chunk
    rf = r.reshape(B * H, L, N)
    kf = k.reshape(B * H, L, N)
    vf = v.reshape(B * H, L, N)
    wf = logw.reshape(B * H, L, N)
    uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, N), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B * H, L, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return out.reshape(B, H, L, N)
