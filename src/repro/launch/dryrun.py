"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be imported/run fresh: the first two lines force 512 host platform
devices before jax locks the device count. Never set this flag globally —
smoke tests and benchmarks see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b \
        --shape train_4k --mesh single
Results (memory analysis, cost analysis, collective-bytes parse) are
written incrementally to experiments/dryrun/*.json — resumable.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, SHAPES, cell_is_skipped, get_config,
                           get_shape)
from repro.launch.mesh import make_context
from repro.models import model_zoo as zoo
from repro.models.transformer import Knobs
from repro.sharding import mesh_context
from repro.sharding.partition import (batch_shardings,
                                      decode_state_shardings,
                                      params_shardings, state_shardings)
from repro.train.optimizer import adamw
from repro.train.schedule import warmup_cosine
from repro.train.train_step import abstract_train_state, build_train_step

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(pred|[a-z]+[0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective family, from the post-SPMD
    HLO. all-reduce counts 2x (reduce-scatter + all-gather equivalent)."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        rhs = line.split("=", 1)[1]
        m = re.match(r"\s*(\([^)]*\)|\S+)\s+([a-z0-9-]+)", rhs)
        if not m:
            continue
        op = m.group(2)
        base = op.rstrip("-start").rstrip(".0123456789")
        for c in _COLLECTIVES:
            if op.startswith(c) and not op.startswith(c + "-done"):
                factor = 2 if c == "all-reduce" else 1
                out[c] += factor * _shape_bytes(m.group(1))
                counts[c] += 1
        del base
    out_total = sum(out.values())
    return {"per_op_bytes": out, "counts": counts, "total_bytes": out_total}


def _jsonable(x):
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (np.floating, np.integer)):
        return float(x)
    return x


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


VARIANTS = ("baseline", "fsdp", "pure_dp", "kv_perforate", "moe_topk2",
            "no_remat", "bf16_params", "moe_ep2d", "pure_dp_bf16")


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path, reduced: bool = False,
             fsdp: bool = False, donate: bool = True,
             variant: str = "baseline") -> dict:
    """Lower + compile one cell; returns the result record.

    §Perf variants (hillclimbing levers, see EXPERIMENTS.md):
    - fsdp: TP rules + big params additionally sharded over data axes,
    - pure_dp: the model axis is folded into data parallelism; params
      FSDP-sharded over all 256/512 devices (dense archs only),
    - kv_perforate: decode with a 25% KV-block keep mask (the paper's
      technique as a perf lever),
    - moe_topk2: MoE decode with the anytime top-k knob at 2 (vs 8),
    - no_remat: disable activation rematerialisation.
    """
    if variant == "fsdp":
        fsdp = True
    mesh_name = "multipod" if multi_pod else "single"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    if variant != "baseline":
        tag += f"__{variant}"
    elif fsdp:
        tag += "__fsdp"
    out_path = out_dir / f"{tag}.json"
    if out_path.exists():
        prev = json.loads(out_path.read_text())
        if prev.get("status") != "error":  # errors retry after fixes
            return prev
    skip = cell_is_skipped(arch, shape_name)
    if skip and variant == "kv_perforate":
        # the beyond-paper exception promised in DESIGN.md: perforated
        # (sub-quadratic-traffic) long-context decode for a dense arch
        skip = None
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "fsdp": fsdp, "variant": variant}
    if skip:
        rec.update({"status": "skipped", "reason": skip})
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    cfg = get_config(arch, reduced=reduced)
    if variant == "no_remat":
        cfg = cfg.scaled(remat=False)
    if variant in ("bf16_params", "pure_dp_bf16"):
        cfg = cfg.scaled(param_dtype="bfloat16")
    if variant == "moe_ep2d":
        cfg = cfg.scaled(ep_dp_shard=True)
        fsdp = True  # store expert weights in the 2-D (tp x dp) layout
    shape = get_shape(shape_name)
    ctx = make_context(multi_pod=multi_pod)
    if variant in ("pure_dp", "pure_dp_bf16"):
        import dataclasses as _dc

        ctx = _dc.replace(ctx, dp_axes=ctx.dp_axes + (ctx.tp_axis,),
                          tp_enabled=False)
        fsdp = True
    knobs = Knobs()
    kv_keep_idx = None
    if variant == "kv_perforate":
        # The anytime runtime attends to a static 25% subset of KV blocks
        # (newest + strided history). A masked softmax alone saves nothing
        # (measured: §Perf iteration 1 — refuted); the win comes from
        # GATHERING the kept blocks so dropped pages are never streamed.
        from repro.serve.kvcache import keep_mask_for_rate

        n_blocks = shape.seq_len // cfg.attn_chunk
        kv_keep_idx = np.nonzero(
            np.asarray(keep_mask_for_rate(n_blocks, 0.25)))[0]
    if variant == "moe_topk2":
        knobs = Knobs(moe_topk=2)
    t0 = time.time()
    try:
        with mesh_context(ctx):
            specs = zoo.input_specs(cfg, shape)
            if shape.kind == "train":
                opt = adamw(warmup_cosine(3e-4, 100, 10000),
                            moment_dtype=(jnp.bfloat16 if cfg.param_dtype
                                          == "bfloat16" else jnp.float32))
                step_fn = build_train_step(cfg, opt, knobs=knobs)
                state_sds = abstract_train_state(cfg, opt)
                state_sh = state_shardings(state_sds, ctx, fsdp)
                batch_sh = batch_shardings(specs["batch"], ctx)
                jfn = jax.jit(
                    step_fn,
                    in_shardings=(state_sh, batch_sh),
                    donate_argnums=(0,) if donate else ())
                lowered = jfn.lower(state_sds, specs["batch"])
            elif shape.kind == "prefill":
                params_sds = zoo.abstract_params(cfg)
                params_sh = params_shardings(params_sds, ctx, fsdp)
                batch_sh = batch_shardings(specs["batch"], ctx)

                def prefill_fn(params, batch):
                    return zoo.prefill(params, batch, cfg, shape.seq_len)

                jfn = jax.jit(prefill_fn,
                              in_shardings=(params_sh, batch_sh))
                lowered = jfn.lower(params_sds, specs["batch"])
            else:  # decode
                params_sds = zoo.abstract_params(cfg)
                params_sh = params_shardings(params_sds, ctx, fsdp)
                state_sh = decode_state_shardings(specs["state"], ctx,
                                                  shape.global_batch)
                tok_sh = batch_shardings(
                    {"t": specs["token"]}, ctx)["t"]
                len_sh = ctx.sharding()

                if kv_keep_idx is not None:
                    # Keep 1 of every 4 KV blocks. The selection MUST be
                    # shard-local: a plain gather or even a strided slice
                    # across the tp-sharded seq axis is resharded by GSPMD
                    # through a cache-sized masked all-reduce (measured,
                    # §Perf iterations 2-3 — refuted). shard_map pins the
                    # slice to each shard's local blocks.
                    stride = 4
                    kept = shape.seq_len // stride
                    local_seq = shape.seq_len // ctx.tp_size

                    def _slice_local(x):
                        for ax, d in enumerate(x.shape):
                            if d == local_seq and d > 1:
                                xb = x.reshape(
                                    x.shape[:ax]
                                    + (d // cfg.attn_chunk, cfg.attn_chunk)
                                    + x.shape[ax + 1:])
                                sl = [slice(None)] * xb.ndim
                                sl[ax] = slice(0, None, stride)
                                return xb[tuple(sl)].reshape(
                                    x.shape[:ax] + (d // stride,)
                                    + x.shape[ax + 1:])
                        return x

                    state_specs = jax.tree.map(lambda s: s.spec, state_sh)
                    slice_fn = jax.shard_map(
                        lambda st: jax.tree.map(_slice_local, st),
                        mesh=ctx.mesh, in_specs=(state_specs,),
                        out_specs=state_specs, check_vma=False)

                    def serve_step(params, state, token, cache_len):
                        small = slice_fn(state)
                        pos = jnp.minimum(cache_len,
                                          jnp.int32(kept - 1))
                        return zoo.decode_step(params, small, token, pos,
                                               cfg, Knobs())

                    donate = False  # gathered cache aliases nothing
                else:
                    def serve_step(params, state, token, cache_len):
                        return zoo.decode_step(params, state, token,
                                               cache_len, cfg, knobs)

                jfn = jax.jit(
                    serve_step,
                    in_shardings=(params_sh, state_sh, tok_sh, len_sh),
                    donate_argnums=(1,) if donate else ())
                lowered = jfn.lower(params_sds, specs["state"],
                                    specs["token"], specs["cache_len"])
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = {}
        try:
            ma = compiled.memory_analysis()
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                if hasattr(ma, k):
                    mem[k] = int(getattr(ma, k))
        except Exception as e:  # CPU backend may not support it
            mem["error"] = str(e)
        cost = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            cost = {k: float(v) for k, v in ca.items()
                    if isinstance(v, (int, float, np.floating))}
        except Exception as e:
            cost = {"error": str(e)}
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
        coll = collective_bytes(hlo)
        from repro.launch.hlo_analysis import analyze
        loop_aware = analyze(hlo)
        n_param_bytes = _tree_bytes(zoo.abstract_params(cfg))
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory_analysis": mem,
            "cost_analysis": {k: cost[k] for k in sorted(cost)
                              if k in ("flops", "bytes accessed",
                                       "transcendentals", "error")},
            "collectives": coll,
            "loop_aware": loop_aware,
            "param_bytes_global": int(n_param_bytes),
            "hlo_bytes": len(hlo),
        })
    except Exception as e:
        rec.update({"status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    out_path.write_text(json.dumps(_jsonable(rec), indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multipod", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced configs (machinery self-test)")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=VARIANTS)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.time()
                rec = run_cell(arch, shape, mp, out_dir,
                               reduced=args.reduced, fsdp=args.fsdp,
                               variant=args.variant)
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    fl = rec["cost_analysis"].get("flops", 0)
                    cb = rec["collectives"]["total_bytes"]
                    extra = (f" flops/dev={fl:.3e}"
                             f" coll_bytes/dev={cb:.3e}")
                elif status == "error":
                    extra = " " + rec.get("error", "")[:120]
                print(f"[{time.strftime('%H:%M:%S')}] {arch} {shape} "
                      f"{'multipod' if mp else 'single'}: {status}"
                      f" ({time.time() - t0:.0f}s){extra}", flush=True)


if __name__ == "__main__":
    main()
