"""Perforated flash attention — Pallas TPU kernel.

The paper's loop perforation adapted to the TPU memory hierarchy: the
flash-attention KV loop skips whole KV *tiles* (VMEM-block grain) under a
keep mask, so the skipped work is never streamed from HBM or issued to the
MXU — the perforation saves real bandwidth and MXU cycles, not just lanes
(DESIGN.md "Hardware-adaptation notes").

Grid: (batch*heads, q_blocks, kv_blocks), kv innermost ("arbitrary"
semantics) with running (m, l, acc) in VMEM scratch. Block shapes default
to (128, head_dim): MXU-aligned on the 128 lane dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(keep_ref,  # scalar-prefetch: (n_kv,) int32 keep mask
            q_ref, k_ref, v_ref,  # VMEM blocks
            o_ref,  # output block
            m_ref, l_ref, acc_ref,  # VMEM scratch
            *, causal: bool, block_q: int, block_k: int, n_kv: int,
            scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = keep_ref[ik] > 0
    if causal:  # static branch: add the block-level causal skip predicate
        live = jnp.logical_and(
            live, ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, 1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def perforated_attention(q, k, v, block_keep, *, causal: bool = True,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """q: (B, H, Sq, Dh); k/v: (B, H, Sk, Dh); block_keep: (Sk//block_k,)
    int32/bool. Returns (B, H, Sq, Dh).
    """
    B, H, Sq, Dh = q.shape
    Sk = k.shape[2]
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_q = Sq // block_q
    n_kv = Sk // block_k
    scale = 1.0 / (Dh ** 0.5)
    qf = q.reshape(B * H, Sq, Dh)
    kf = k.reshape(B * H, Sk, Dh)
    vf = v.reshape(B * H, Sk, Dh)
    keep = block_keep.astype(jnp.int32)

    kernel = functools.partial(_kernel, causal=causal, block_q=block_q,
                               block_k=block_k, n_kv=n_kv, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, n_q, n_kv),
        in_specs=[
            # index maps receive the scalar-prefetch ref as a trailing arg
            pl.BlockSpec((1, block_q, Dh),
                         lambda bh, iq, ik, keep: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda bh, iq, ik, keep: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda bh, iq, ik, keep: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh),
                               lambda bh, iq, ik, keep: (bh, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(keep, qf, kf, vf)
    return out.reshape(B, H, Sq, Dh)
