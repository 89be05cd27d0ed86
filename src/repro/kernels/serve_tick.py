"""Fleet serve-tick megakernel — one VMEM-resident Pallas pass per tick.

The whole quantized dispatch-mode device tick of ``repro.fleet.qtick``
— capacitor harvest update, turn-on threshold crossing, pending-work
acquisition, the data-dependent ``while_loop`` unit progression with
brown-out detection, and emission — fused into a single Pallas kernel
over (block_rows, 128) worker tiles. The float64 scan round-trips every
(N,) state array through HBM once per jnp op; here each tile is read
once, advanced entirely in VMEM/registers, and written once, plus a
per-block int32 event/ledger partial reduction (one (1, 128) row per
grid step) so callers can cross-check activity without re-reducing the
full state.

Numerics: int32 energy quanta throughout (the ``qtick`` contract —
Pallas TPU cannot compile the float64 reference). Workload-table
gathers (unit cost / fixed / emit cost by workload id) run as one-hot
reductions against lane-replicated (K, 128) tables — Mosaic has no
per-lane dynamic gather — which stays cheap because the progression
loop retires after at most a couple of iterations per tick (every unit
costs more than one tick of active draw).

``interpret=True`` traces the same kernel through the Pallas
interpreter (pure XLA ops), which is how CPU CI pins this kernel
bit-exact against ``qtick.tick_q``; compiled mode is the TPU fast path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANES, pad_to_tiles, tile_rows, untile

# event codes (match repro.fleet.qtick / backend_jax)
EV_NONE, EV_EMIT, EV_LOST = 0, 1, 2
BIG_Q = 2 ** 30

# mutated state fields, in kernel argument order (a subset of
# repro.fleet.state.STATE_FIELDS: the dispatch tick's read-write set)
RW_FIELDS = ("v", "on", "cycles", "acquired", "e_work", "e_harvest",
             "has_work", "w_ticket", "w_t_acq", "w_cycle_acq",
             "w_units_done", "w_left", "w_target", "w_tile", "w_wl",
             "w_batch", "p_pending", "emit_count", "emit_units_sum")
# read-only pending-assignment fields
RO_FIELDS = ("p_ticket", "p_wl", "p_units", "p_batch")
# bool-typed fields ride through the kernel as int32 0/1
BOOL_FIELDS = ("on", "has_work", "p_pending")

# per-block ledger lanes (first 8 lanes of each (1, 128) output row)
LEDGER_SLOTS = ("n_emit", "n_lost", "units_emitted", "n_wake",
                "n_acquired", "qh_quanta", "e_work_quanta", "reserved")

LED_ROWS = 8  # ledger tile height; only row 0 carries the slots

_N_RW = len(RW_FIELDS)
_N_RO = len(RO_FIELDS)


def replicate_table(vals, k_pad: int):
    """Lane-replicate a 1-D int32 table to (k_pad, 128) for the one-hot
    in-kernel gathers (row r holds vals[r] in every lane)."""
    v = jnp.asarray(vals, jnp.int32).reshape(-1)
    v = jnp.pad(v, (0, k_pad - v.shape[0]))
    return jnp.tile(v[:, None], (1, LANES))


def _gather(tab, idx):
    """tab (K, 128) lane-replicated, idx (bm, 128) int32 -> (bm, 128):
    one-hot reduction standing in for a per-lane dynamic gather."""
    k = tab.shape[0]
    kv = lax.broadcasted_iota(jnp.int32, (k,) + idx.shape, 0)
    # dtype pinned: with x64 enabled jnp.sum would widen int32 to int64,
    # which Mosaic rejects and the int32 carry contract forbids
    return jnp.sum(jnp.where(kv == idx[None], tab[:, None, :], 0), axis=0,
                   dtype=jnp.int32)


def _rec(ev, mask, code, ti, ticket, units):
    """First event per worker per tick wins (same log invariant as the
    scan backends)."""
    evc, evt, evtk, evu = ev
    new = mask & (evc == EV_NONE)
    return (jnp.where(new, code, evc), jnp.where(new, ti, evt),
            jnp.where(new, ticket, evtk), jnp.where(new, units, evu))


def _serve_tick_kernel(*refs, u_max: int):
    ins, outs = refs[:_N_RW + _N_RO + 9], refs[_N_RW + _N_RO + 9:]
    s = dict(zip(RW_FIELDS + RO_FIELDS, ins))
    (qh_ref, ti_ref, e_on_ref, e_off_ref, e_max_ref, estep_ref,
     uc_ref, fix_ref, emitc_ref) = ins[_N_RW + _N_RO:]
    out = dict(zip(RW_FIELDS, outs[:_N_RW]))
    ev_refs = outs[_N_RW:_N_RW + 4]
    led_ref = outs[_N_RW + 4]

    i32 = jnp.int32
    ld = lambda f: s[f][...]  # noqa: E731
    bl = lambda f: s[f][...] != 0  # noqa: E731
    E = ld("v")
    on0, has_work0, p_pending0 = bl("on"), bl("has_work"), bl("p_pending")
    qh, ti = qh_ref[...], ti_ref[...]
    e_on, e_off, e_max = e_on_ref[...], e_off_ref[...], e_max_ref[...]
    e_work_in = ld("e_work")
    zeros = jnp.zeros_like(E)
    ev = (zeros, zeros, zeros, zeros)

    # 1. harvest: bank quanta, saturate at the capacitor ceiling
    e_harvest = ld("e_harvest") + qh
    E = jnp.minimum(E + qh, e_max)

    # 2. turn on at E_ON
    waking = jnp.logical_and(~on0, E >= e_on)
    on = on0 | waking
    cycles = ld("cycles") + waking.astype(i32)
    working = on & has_work0
    idle = on & ~has_work0

    # 3. acquisition: claim the pending assignment
    p_wl = ld("p_wl")
    due = idle & p_pending0
    usable = jnp.maximum(E - e_off, 0)
    fixed = _gather(fix_ref[...], p_wl)
    take = jnp.minimum(fixed, usable)
    okA = ~((E - take) < e_off)
    E = jnp.where(due, jnp.where(okA, E - take, e_off), E)
    p_pending = p_pending0 & ~due
    fail = due & ~okA
    on = on & ~fail
    ev = _rec(ev, fail, EV_LOST, ti, ld("p_ticket"), 0)
    succ = due & okA
    e_work = e_work_in + jnp.where(succ, fixed, 0)
    acquired = ld("acquired") + succ.astype(i32)
    has_work = has_work0 | succ
    w_ticket = jnp.where(succ, ld("p_ticket"), ld("w_ticket"))
    w_t_acq = jnp.where(succ, ti, ld("w_t_acq"))
    w_cycle_acq = jnp.where(succ, cycles, ld("w_cycle_acq"))
    w_units_done = jnp.where(succ, 0, ld("w_units_done"))
    w_left = jnp.where(succ, 0, ld("w_left"))
    w_tile = jnp.where(succ, ld("p_units"), ld("w_tile"))
    w_batch = jnp.where(succ, ld("p_batch"), ld("w_batch"))
    w_target = jnp.where(succ, ld("p_units") * ld("p_batch"),
                         ld("w_target"))
    w_wl = jnp.where(succ, p_wl, ld("w_wl"))

    # 4. progress in-flight work by one tick of active draw
    emitc_w = _gather(emitc_ref[...], w_wl)
    uc_tab = uc_ref[...]
    e_step = jnp.where(working, estep_ref[...], 0)
    run = working & (w_units_done < w_target)
    emit_now = jnp.zeros_like(run)

    # the loop carries its masks as int32 0/1: Mosaic cannot legalize a
    # while-loop yield of i1 vectors
    as_i32 = lambda m: m.astype(i32)  # noqa: E731

    def cond(c):
        return jnp.max(c[7]) > 0

    def body(c):
        (E, on, has_work, e_work, w_left, w_units_done, e_step, run,
         emit_now, ev) = c
        on, has_work, run, emit_now = (
            x != 0 for x in (on, has_work, run, emit_now))
        # unit boundary: start the next unit only if unit + emit-reserve
        # are affordable now (the paper's BLE-packet reserve)
        starting = run & (w_left <= 0)
        gidx = jnp.where(w_tile > 0,
                         w_units_done % jnp.maximum(w_tile, 1),
                         w_units_done)
        nc = _gather(uc_tab, w_wl * u_max + jnp.clip(gidx, 0, u_max - 1))
        usable = jnp.maximum(E - e_off, 0)
        cant = starting & (usable < nc + emitc_w)
        emit_now = emit_now | cant
        run = run & ~cant
        w_left = jnp.where(starting & ~cant, nc, w_left)
        take = jnp.minimum(e_step, w_left)
        ok = ~((E - take) < e_off)
        E = jnp.where(run, jnp.where(ok, E - take, e_off), E)
        fail = run & ~ok
        # power failure mid-work: volatile by design; work lost
        on = on & ~fail
        has_work = has_work & ~fail
        ev = _rec(ev, fail, EV_LOST, ti, w_ticket, 0)
        run = run & ok
        e_work = e_work + jnp.where(run, take, 0)
        w_left = jnp.where(run, w_left - take, w_left)
        e_step = jnp.where(run, e_step - take, e_step)
        fin = run & (w_left <= 0)
        w_units_done = w_units_done + fin.astype(i32)
        run = run & (e_step > 0) & (w_units_done < w_target)
        return (E, as_i32(on), as_i32(has_work), e_work, w_left,
                w_units_done, e_step, as_i32(run), as_i32(emit_now), ev)

    carry = (E, as_i32(on), as_i32(has_work), e_work, w_left, w_units_done,
             e_step, as_i32(run), as_i32(emit_now), ev)
    (E, on, has_work, e_work, w_left, w_units_done, _, _, emit_now,
     ev) = lax.while_loop(cond, body, carry)
    on, has_work, emit_now = on != 0, has_work != 0, emit_now != 0

    # 5. emission (BLE packet / host transfer)
    finish = (working & has_work & on
              & ((w_units_done >= w_target) | emit_now))
    ec = _gather(emitc_ref[...], w_wl)
    okE = ~((E - ec) < e_off)
    E = jnp.where(finish, jnp.where(okE, E - ec, e_off), E)
    efail = finish & ~okE
    esucc = finish & okE
    on = on & ~efail
    has_work = has_work & ~finish  # volatile: failed emission loses it
    ev = _rec(ev, efail, EV_LOST, ti, w_ticket, 0)
    ev = _rec(ev, esucc, EV_EMIT, ti, w_ticket, w_units_done)
    e_work = e_work + jnp.where(esucc, ec, 0)
    emit_count = ld("emit_count") + esucc.astype(i32)
    emit_units_sum = ld("emit_units_sum") + jnp.where(
        esucc, w_units_done, 0)

    res = dict(
        v=E, on=on.astype(i32), cycles=cycles, acquired=acquired,
        e_work=e_work, e_harvest=e_harvest,
        has_work=has_work.astype(i32), w_ticket=w_ticket,
        w_t_acq=w_t_acq, w_cycle_acq=w_cycle_acq,
        w_units_done=w_units_done, w_left=w_left, w_target=w_target,
        w_tile=w_tile, w_wl=w_wl, w_batch=w_batch,
        p_pending=p_pending.astype(i32), emit_count=emit_count,
        emit_units_sum=emit_units_sum)
    for f in RW_FIELDS:
        out[f][...] = res[f]
    evc = ev[0]
    for r, x in zip(ev_refs, ev):
        r[...] = x

    # per-block event/ledger partial reduction, 8 int32 lanes in row 0
    # of the block's (8, 128) ledger tile (Mosaic tiles are 8 rows high)
    lane = lax.broadcasted_iota(jnp.int32, (LED_ROWS, LANES), 1)
    row0 = lax.broadcasted_iota(jnp.int32, (LED_ROWS, LANES), 0) == 0

    def tot(x):
        # (bm, 128) -> (1, 1) one axis at a time: Mosaic lowers a
        # reduction to a scalar through a jnp.sum that widens int32 to
        # int64 when the caller traces under x64
        col = jnp.sum(x, axis=0, keepdims=True, dtype=i32)
        return jnp.sum(col, axis=1, keepdims=True, dtype=i32)

    put = lambda slot, val: jnp.where(row0 & (lane == slot), val,  # noqa: E731
                                      0)
    led_ref[...] = (
        put(0, tot(esucc.astype(i32)))
        + put(1, tot((evc == EV_LOST).astype(i32)))
        + put(2, tot(jnp.where(esucc, w_units_done, 0)))
        + put(3, tot(waking.astype(i32)))
        + put(4, tot(succ.astype(i32)))
        + put(5, tot(qh))
        + put(6, tot(e_work - e_work_in)))


@functools.partial(jax.jit,
                   static_argnames=("u_max", "block_rows", "interpret"))
def serve_tick(rw, ro, consts, tables, qh, i, *, u_max: int,
               block_rows: int = 8, interpret: bool = False):
    """One quantized dispatch tick for N workers, fused in Pallas.

    - ``rw``: dict of the 19 ``RW_FIELDS`` (N,) arrays (int32 quanta /
      counters; ``BOOL_FIELDS`` may be bool — converted both ways here)
    - ``ro``: dict of the 4 ``RO_FIELDS`` pending-assignment arrays
    - ``consts``: dict with per-worker int32 ``e_on``/``e_off``/
      ``e_max``/``estep``
    - ``tables``: dict with lane-replicated int32 ``uc`` (W*u_max rows,
      flattened row-major, padded), ``fix`` and ``emitc`` (W rows,
      padded) from :func:`replicate_table`
    - ``qh``: (N,) int32 banked harvest quanta this tick
    - ``i``: tick index (int32 range); ``u_max`` the static UC row width

    Returns ``(rw_out, ev, ledger)``: the updated field dict (bools
    restored), the 4-tuple int32 event log, and the (grid, 128) int32
    per-block ledger whose first 8 lanes are ``LEDGER_SLOTS``.
    """
    n = qh.shape[0]
    rows, _ = tile_rows(n, block_rows)
    grid = rows // block_rows

    def prep(x, fill=0):
        return pad_to_tiles(x, n, rows, fill, jnp.int32)

    tile = pl.BlockSpec((block_rows, LANES), lambda g: (g, 0))
    full = lambda t: pl.BlockSpec(t.shape, lambda g: (0, 0))  # noqa: E731
    args = ([prep(rw[f]) for f in RW_FIELDS]
            + [prep(ro[f]) for f in RO_FIELDS]
            + [prep(qh), prep(jnp.full((n,), i, jnp.int32)),
               prep(consts["e_on"], BIG_Q), prep(consts["e_off"]),
               prep(consts["e_max"]), prep(consts["estep"])]
            + [tables["uc"], tables["fix"], tables["emitc"]])
    in_specs = ([tile] * (_N_RW + _N_RO + 6)
                + [full(tables["uc"]), full(tables["fix"]),
                   full(tables["emitc"])])
    i32 = jnp.int32
    out_shape = ([jax.ShapeDtypeStruct((rows, LANES), i32)] * (_N_RW + 4)
                 + [jax.ShapeDtypeStruct((grid * LED_ROWS, LANES), i32)])
    out_specs = ([tile] * (_N_RW + 4)
                 + [pl.BlockSpec((LED_ROWS, LANES), lambda g: (g, 0))])
    # the kernel is int32 end to end; traced under the caller's x64 mode
    # its Python literals would be int64 constants that Mosaic cannot
    # narrow, so trace and lower it with x64 off
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            functools.partial(_serve_tick_kernel, u_max=u_max),
            grid=(grid,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(*args)
    rw_out = {}
    for f, y in zip(RW_FIELDS, outs[:_N_RW]):
        y = untile(y, n)
        rw_out[f] = (y != 0) if f in BOOL_FIELDS else y
    ev = tuple(untile(y, n) for y in outs[_N_RW:_N_RW + 4])
    return rw_out, ev, outs[_N_RW + 4][::LED_ROWS]
