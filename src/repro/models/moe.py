"""Mixture-of-Experts with sort-based token dispatch + expert parallelism.

Dense one-hot (GShard-style) dispatch masks are O(tokens * experts *
capacity) and blow up at 384-expert/1M-token scale, so dispatch here is
sort-based: token copies are argsorted by expert id, slotted into per-expert
capacity buffers with pure gathers (TPU-friendly; the scatter is over int32
slot maps only). Expert parallelism runs inside shard_map: capacity buffers
are exchanged across the ``model`` mesh axis with two all_to_alls, the
classic GShard EP schedule.

Capacity overflow drops token copies (they contribute zero); this is the
paper's token-grain perforation knob for MoE archs — ``capacity_factor`` is
an approximation lever the anytime runtime can lower under budget pressure
(DESIGN.md §Arch-applicability, llama4 row).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import fanin_init, silu


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype,
             stack: tuple[int, ...] = (), shared_expert: bool = False):
    ks = jax.random.split(key, 5)
    p = {
        "router": fanin_init(ks[0], (*stack, d_model, n_experts),
                             jnp.float32),  # router always fp32
        "wi": fanin_init(ks[1], (*stack, n_experts, d_model, 2 * d_ff), dtype),
        "wo": fanin_init(ks[2], (*stack, n_experts, d_ff, d_model), dtype),
    }
    if shared_expert:
        p["shared_wi"] = fanin_init(ks[3], (*stack, d_model, 2 * d_ff), dtype)
        p["shared_wo"] = fanin_init(ks[4], (*stack, d_ff, d_model), dtype)
    return p


def _dispatch_indices(ids_f: jax.Array, n_experts: int, capacity: int):
    """Sort-based slotting. ids_f: (T*k,) expert ids per token copy.

    Returns (slot_for_copy (T*k,) int32 with capacity-dropped copies mapped
    to the sentinel slot E*C, keep mask (T*k,)).
    """
    n_copies = ids_f.shape[0]
    perm = jnp.argsort(ids_f)  # stable
    sid = ids_f[perm]
    counts = jnp.bincount(ids_f, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n_copies) - starts[sid]
    keep_sorted = pos < capacity
    slot_sorted = jnp.where(keep_sorted, sid * capacity + pos,
                            n_experts * capacity)
    inv = jnp.argsort(perm)
    return slot_sorted[inv].astype(jnp.int32), keep_sorted[inv]


def _expert_ffn(buf: jax.Array, wi: jax.Array, wo: jax.Array,
                compute_dtype) -> jax.Array:
    """buf: (E, C, D); wi: (E, D, 2F); wo: (E, F, D)."""
    h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(compute_dtype))
    g, u = jnp.split(h, 2, axis=-1)
    return jnp.einsum("ecf,efd->ecd", silu(g) * u, wo.astype(compute_dtype))


def moe_ffn(x: jax.Array, p, *, n_experts: int, topk: int,
            capacity_factor: float, compute_dtype,
            ep_axis: str | None = None, ep_size: int = 1,
            topk_override: int | None = None):
    """MoE feed-forward. x: (B, S, D) (local shard when inside shard_map).

    ``ep_axis``: mesh axis name for expert parallelism (None: all experts
    local — single-device smoke tests). ``topk_override`` is the anytime
    runtime's knob (use fewer experts per token under budget pressure).
    Returns (y, aux_loss_terms) where aux is the load-balancing loss value.
    """
    B, S, D = x.shape
    k = topk_override if topk_override is not None else topk
    T = B * S
    xf = x.reshape(T, D)
    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    topw, topi = jax.lax.top_k(probs, k)  # (T, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    capacity = max(int(math.ceil(T * k * capacity_factor / n_experts)), 1)
    ids_f = topi.reshape(-1)  # (T*k,)
    slot, keep = _dispatch_indices(ids_f, n_experts, capacity)

    # slot -> source token row (int scatter), then gather embeddings
    tok_idx = (jnp.arange(T * k) // k).astype(jnp.int32)
    slot_map = jnp.full((n_experts * capacity + 1,), T, jnp.int32)
    slot_map = slot_map.at[slot].set(jnp.where(keep, tok_idx, T))
    x_pad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], 0)
    buf = x_pad[slot_map[:-1]].reshape(n_experts, capacity, D)

    if ep_axis is not None and ep_size > 1:
        # EP exchange: every device keeps E/ep experts, receives all their
        # capacity slots -> (E_local, ep*C, D)
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        y = _expert_ffn(buf, p["wi"], p["wo"], compute_dtype)
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                               tiled=True)
    else:
        y = _expert_ffn(buf, p["wi"], p["wo"], compute_dtype)

    y_pad = jnp.concatenate([y.reshape(n_experts * capacity, D),
                             jnp.zeros((1, D), y.dtype)], 0)
    y_copies = y_pad[jnp.minimum(slot, n_experts * capacity)]
    y_copies = jnp.where(keep[:, None], y_copies, 0.0)
    y_tok = jnp.sum(y_copies.reshape(T, k, D)
                    * topw[..., None].astype(y_copies.dtype), axis=1)

    if "shared_wi" in p:
        h = xf @ p["shared_wi"].astype(compute_dtype)
        g, u = jnp.split(h, 2, axis=-1)
        y_tok = y_tok + (silu(g) * u) @ p["shared_wo"].astype(compute_dtype)

    # Switch-style load-balancing aux: E * sum_e f_e * P_e
    assign = jnp.zeros((n_experts,), jnp.float32).at[ids_f].add(
        keep.astype(jnp.float32))
    f_e = assign / jnp.maximum(assign.sum(), 1.0)
    p_e = probs.mean(0)
    aux = n_experts * jnp.sum(f_e * p_e)
    return y_tok.reshape(B, S, D).astype(x.dtype), aux


def _moe_replicated_ep(x, router, wi, wo, shared, *, n_experts, topk,
                       capacity_factor, compute_dtype, tp_axis,
                       topk_override=None, dp_axes=None):
    """Decode-path EP: activations replicated across the tp axis, each rank
    computes its local experts and the outputs are psum-combined. Avoids
    all_to_all on tiny token counts (single-token decode).

    2-D EP (``dp_axes`` given): expert hidden dims are additionally sharded
    over the data axes (wi: (E_l, D/dp, 2F), wo: (E_l, F/dp, D)); partial
    contractions are psum'ed over dp before the nonlinearity / after the
    down-projection. Cuts resident+streamed expert bytes by dp_size — the
    1T-MoE decode memory fix.
    """
    e_local = wi.shape[0]  # already the local shard
    B, S, D = x.shape
    k = topk_override if topk_override is not None else topk
    T = B * S
    xf = x.reshape(T, D)
    logits = (xf.astype(jnp.float32) @ router)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    capacity = max(int(math.ceil(T * k * capacity_factor / n_experts)), 1)
    ids_f = topi.reshape(-1)
    slot, keep = _dispatch_indices(ids_f, n_experts, capacity)
    tok_idx = (jnp.arange(T * k) // k).astype(jnp.int32)
    slot_map = jnp.full((n_experts * capacity + 1,), T, jnp.int32)
    slot_map = slot_map.at[slot].set(jnp.where(keep, tok_idx, T))
    x_pad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], 0)
    buf = x_pad[slot_map[:-1]].reshape(n_experts, capacity, D)
    j = jax.lax.axis_index(tp_axis)
    buf_l = jax.lax.dynamic_slice_in_dim(buf, j * e_local, e_local, 0)
    if dp_axes:
        # 2-D EP: this rank holds a D-slice of its experts' up-projection
        # and an F-slice of the down-projection
        d_shard = wi.shape[1]
        r = jax.lax.axis_index(dp_axes)
        buf_d = jax.lax.dynamic_slice_in_dim(buf_l, r * d_shard, d_shard, 2)
        h = jnp.einsum("ecd,edf->ecf", buf_d, wi.astype(compute_dtype))
        h = jax.lax.psum(h, dp_axes)  # complete the D contraction
        g, u = jnp.split(h, 2, axis=-1)
        h = silu(g) * u
        f_shard = wo.shape[1]
        h_f = jax.lax.dynamic_slice_in_dim(h, r * f_shard, f_shard, 2)
        y_l = jnp.einsum("ecf,efd->ecd", h_f, wo.astype(compute_dtype))
        y_l = jax.lax.psum(y_l, dp_axes)  # complete the F contraction
    else:
        y_l = _expert_ffn(buf_l, wi, wo, compute_dtype)
    # partial token-level combine: each rank maps its own experts' outputs
    # back to token copies and contributes zeros elsewhere; the psum moves
    # (T, D) tokens instead of the (E, C, D) capacity buffer (§Perf: the
    # buffer-psum variant moved ~12x more bytes — measured, refuted)
    slots_l = e_local * capacity
    y_pad_l = jnp.concatenate([y_l.reshape(slots_l, D),
                               jnp.zeros((1, D), y_l.dtype)], 0)
    slot_rel = slot - j * slots_l
    in_range = jnp.logical_and(keep,
                               jnp.logical_and(slot_rel >= 0,
                                               slot_rel < slots_l))
    y_copies = jnp.where(in_range[:, None],
                         y_pad_l[jnp.clip(slot_rel, 0, slots_l)], 0.0)
    y_tok = jnp.sum(y_copies.reshape(T, k, D)
                    * topw[..., None].astype(y_copies.dtype), axis=1)
    y_tok = jax.lax.psum(y_tok, tp_axis)
    if shared is not None:
        swi, swo = shared
        h = xf @ swi.astype(compute_dtype)
        g, u = jnp.split(h, 2, axis=-1)
        y_tok = y_tok + (silu(g) * u) @ swo.astype(compute_dtype)
    assign = jnp.zeros((n_experts,), jnp.float32).at[ids_f].add(
        keep.astype(jnp.float32))
    f_e = assign / jnp.maximum(assign.sum(), 1.0)
    aux = n_experts * jnp.sum(f_e * probs.mean(0))
    return y_tok.reshape(B, S, D).astype(x.dtype), aux


def moe_ffn_distributed(x, p, cfg, *, compute_dtype, topk_override=None):
    """Mesh-aware MoE: shard_map EP when a mesh context is active, plain
    local computation otherwise. x: (B, S, D) global."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import current_mesh_context

    ctx = current_mesh_context()
    kw = dict(n_experts=cfg.n_experts, topk=cfg.moe_topk,
              capacity_factor=cfg.capacity_factor,
              compute_dtype=compute_dtype, topk_override=topk_override)
    # The shared expert is an ordinary dense MLP: compute it OUTSIDE the
    # shard_map as a plain TP matmul. Passing its weights into the
    # shard_map with a replicated in_spec all-gathers the full (D, 2F)
    # matrices every invocation (~170 MB/layer for llama4 — measured,
    # EXPERIMENTS.md cell D).
    shared_out = None
    if "shared_wi" in p:
        h = jnp.einsum("bsd,df->bsf", x,
                       p["shared_wi"].astype(compute_dtype))
        g, u = jnp.split(h, 2, axis=-1)
        shared_out = jnp.einsum("bsf,fd->bsd", silu(g) * u,
                                p["shared_wo"].astype(compute_dtype))
        p = {k: v for k, v in p.items() if not k.startswith("shared")}

    def _with_shared(y):
        return y if shared_out is None else y + shared_out.astype(y.dtype)

    if ctx is None or ctx.tp_size == 1:
        y, aux = moe_ffn(x, p, ep_axis=None, **kw)
        return _with_shared(y), aux

    mesh, dp, tp = ctx.mesh, ctx.dp_axes, ctx.tp_axis
    seq_shardable = x.shape[1] % ctx.tp_size == 0 and x.shape[1] > 1
    shared = False
    shared_in = (P(),)
    shared_args = (jnp.zeros((), x.dtype),)

    if seq_shardable:
        def local_fn(x_l, router, wi_l, wo_l, *sh):
            pl = {"router": router, "wi": wi_l, "wo": wo_l}
            if shared:
                pl["shared_wi"], pl["shared_wo"] = sh
            y, aux = moe_ffn(x_l, pl, ep_axis=tp,
                             ep_size=ctx.tp_size, **kw)
            return y, jax.lax.pmean(aux, ctx.all_axes)

        fn = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(dp, tp, None), P(None, None),
                      P(tp, None, None), P(tp, None, None), *shared_in),
            out_specs=(P(dp, tp, None), P()),
            check_vma=False)
        y, aux = fn(x, p["router"], p["wi"], p["wo"], *shared_args)
        return _with_shared(y), aux

    ep2d = getattr(cfg, "ep_dp_shard", False)

    def local_fn(x_l, router, wi_l, wo_l, *sh):
        sh_t = sh if shared else None
        return _moe_replicated_ep(
            x_l, router, wi_l, wo_l, sh_t, n_experts=cfg.n_experts,
            topk=cfg.moe_topk, capacity_factor=cfg.capacity_factor,
            compute_dtype=compute_dtype, tp_axis=tp,
            topk_override=topk_override, dp_axes=dp if ep2d else None)

    def wrapped(x_l, router, wi_l, wo_l, *sh):
        y, aux = local_fn(x_l, router, wi_l, wo_l, *sh)
        return y, jax.lax.pmean(aux, ctx.all_axes)

    wi_spec = P(tp, dp, None) if ep2d else P(tp, None, None)
    # note: in decode mode x is NOT batch-sharded over dp when ep2d is on
    # (every dp rank needs all tokens for its partial contraction)
    x_spec = P(None, None, None) if ep2d else P(dp, None, None)
    fn = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(x_spec, P(None, None), wi_spec, wi_spec, *shared_in),
        out_specs=(x_spec, P()),
        check_vma=False)
    y, aux = fn(x, p["router"], p["wi"], p["wo"], *shared_args)
    return _with_shared(y), aux
