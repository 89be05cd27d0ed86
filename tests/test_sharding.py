"""Distribution tests.

Partition-rule unit tests run in-process (no devices needed); the
multi-device lower/compile test runs the real dryrun machinery in a
subprocess with 8 forced host devices (device count is locked at first
jax use, so it must not happen in the test process).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.hlo_analysis import analyze
from repro.models import model_zoo as zoo

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _fake_ctx(tp=4):
    """A MeshContext-shaped stub for rule tests (no devices touched)."""

    class _Mesh:
        shape = {"data": 2, "model": tp}

    class _Ctx:
        mesh = _Mesh()
        dp_axes = ("data",)
        tp_axis = "model"
        tp_size = tp
        dp_size = 2
        tp_enabled = True

    return _Ctx()


def test_partition_rules_megatron_pattern():
    from repro.sharding.partition import param_spec

    ctx = _fake_ctx(4)
    cfg = get_config("glm4-9b", reduced=True)
    params = zoo.abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {"/".join(str(getattr(p, "key", p)) for p in path):
             param_spec(path, leaf, ctx) for path, leaf in flat}
    assert specs["embed"] == P("model", None)
    assert specs["unembed"] == P(None, "model")
    attn_wq = [v for k, v in specs.items() if k.endswith("attn/wq")][0]
    assert attn_wq == P(None, None, "model")  # (L, D, H*Dh)
    attn_wo = [v for k, v in specs.items() if k.endswith("attn/wo")][0]
    assert attn_wo == P(None, "model", None)
    mlp_wi = [v for k, v in specs.items() if k.endswith("mlp/wi")][0]
    assert mlp_wi == P(None, None, "model")


def test_partition_rules_moe_expert_parallel():
    from repro.sharding.partition import param_spec

    ctx = _fake_ctx(4)
    cfg = get_config("kimi-k2-1t-a32b", reduced=True)
    params = zoo.abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        if key.endswith("moe/wi"):
            assert param_spec(path, leaf, ctx) == P(
                None, "model", None, None)  # (L, E, D, 2F): EP on experts
        if key.endswith("moe/router"):
            assert param_spec(path, leaf, ctx) == P(None, None, None)


def test_partition_rules_indivisible_falls_back_to_replication():
    from repro.sharding.partition import param_spec

    ctx = _fake_ctx(16)
    cfg = get_config("whisper-tiny")  # 6 heads: 384-dim attn not % 16 == 0
    params = zoo.abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        if "attn/wq" in key:
            spec = param_spec(path, leaf, ctx)
            assert spec[-1] == "model"  # 384 % 16 == 0 -> sharded
        if key == "embed":
            # vocab 51865 is odd -> falls back to replication
            assert param_spec(path, leaf, ctx)[0] is None


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, {src!r})
import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs import get_config
from repro.launch.dryrun import run_cell
from pathlib import Path

# shrink the production mesh to fit 8 host devices
import repro.launch.mesh as mesh_mod
def small_mesh(*, multi_pod=False):
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return mesh_mod.make_mesh(shape, axes)
mesh_mod.make_production_mesh = small_mesh
from repro.sharding.context import MeshContext
def small_ctx(*, multi_pod=False):
    m = small_mesh(multi_pod=multi_pod)
    dp = ("pod", "data") if multi_pod else ("data",)
    return MeshContext(mesh=m, dp_axes=dp, tp_axis="model")
mesh_mod.make_context = small_ctx
import repro.launch.dryrun as dr
dr.make_context = small_ctx

# ALSO shrink the shapes so reduced configs divide evenly
import repro.configs.base as base
base.SHAPES["train_4k"] = base.ShapeConfig("train_4k", 64, 8, "train")
base.SHAPES["decode_32k"] = base.ShapeConfig("decode_32k", 64, 8, "decode")

out = Path({out!r})
recs = []
for arch in ["glm4-9b", "kimi-k2-1t-a32b", "rwkv6-7b"]:
    for shape in ["train_4k", "decode_32k"]:
        for mp in (False, True):
            rec = run_cell(arch, shape, mp, out, reduced=True)
            recs.append({{"arch": arch, "shape": shape, "mp": mp,
                         "status": rec["status"],
                         "err": rec.get("error", "")}})
print(json.dumps(recs))
"""


@pytest.mark.slow
def test_multidevice_lower_compile(tmp_path):
    """The dry-run machinery compiles reduced cells on an 8-device mesh,
    single- and multi-pod, for dense + MoE(shard_map EP) + rwkv."""
    code = _SUBPROC.format(src=SRC, out=str(tmp_path))
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    recs = json.loads(proc.stdout.strip().splitlines()[-1])
    bad = [r for r in recs if r["status"] != "ok"]
    assert not bad, bad


_EP_NUMERIC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import model_zoo as zoo
from repro.launch.mesh import make_host_mesh
from repro.sharding import mesh_context
cfg = get_config('kimi-k2-1t-a32b', reduced=True).scaled(
    compute_dtype='float32', capacity_factor=8.0)
params = zoo.init_params(cfg, jax.random.key(0))
state = zoo.init_decode_state(cfg, 8, 32)
tok = jnp.arange(8, dtype=jnp.int32)
ref, _ = zoo.decode_step(params, state, tok, jnp.int32(3), cfg)
ctx = make_host_mesh(8, model=4)
errs = []
for c in (cfg, cfg.scaled(ep_dp_shard=True)):
    with mesh_context(ctx):
        got, _ = jax.jit(lambda p, s, t: zoo.decode_step(
            p, s, t, jnp.int32(3), c))(params, state, tok)
    errs.append(float(jnp.abs(ref - got).max()))
assert all(e < 1e-4 for e in errs), errs
print("OK", errs)
"""


@pytest.mark.slow
def test_moe_ep_decode_numerics_match_single_device(tmp_path):
    """Replicated-EP partial combine and 2-D EP decode paths must match the
    single-device MoE bit-for-bit (fp32 tolerance)."""
    code = _EP_NUMERIC.format(src=SRC)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.startswith("OK")


def test_hlo_analyzer_on_synthetic_module():
    hlo = """
HloModule test, entry_computation_layout={()->f32[]}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[8,8]{1,0} all-gather(%d), dimensions={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%i2, %ag)
}

%cond (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i3, %n), direction=LT
}

ENTRY %main () -> f32[8,8] {
  %init = (s32[], f32[8,8]) tuple(), sharding={replicated}
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
    res = analyze(hlo)
    # dot: 2*8*8*8 = 1024 flops x 10 trips
    assert res["flops_per_device"] == 1024 * 10
    assert res["collective_bytes_per_device"]["all-gather"] == 256 * 10
    assert res["unbounded_loops"] == 0


# ---------------------------------------------------------------------------
# fleet mesh (--mesh-fleet K): partition rules, rebalance protocol units,
# and the sharded serve's three-evaluation bit-equality
# ---------------------------------------------------------------------------

import collections

import numpy as np


def test_fleet_axis_spec_divisibility_fallback():
    from repro.sharding.context import FLEET_AXIS
    from repro.sharding.partition import fleet_axis_spec

    class _L:
        def __init__(self, *shape):
            self.shape = shape
            self.ndim = len(shape)

    assert fleet_axis_spec(_L(256), 8) == P(FLEET_AXIS)
    assert fleet_axis_spec(_L(255), 8) == P(None)  # odd -> replicate
    assert fleet_axis_spec(_L(8, 32), 8) == P(FLEET_AXIS, None)
    assert fleet_axis_spec(_L(), 8) == P()  # 0-d scalar counter


def test_split_counts_partitions_exactly():
    from repro.fleet.sched import split_counts

    rng = np.random.default_rng(0)
    counts = rng.integers(0, 9, (40, 3))
    sp = split_counts(counts, 8)
    assert sp.shape == (8, 40, 3)
    assert np.array_equal(sp.sum(axis=0), counts)
    # deterministic remainder: low-numbered shards get the extras
    assert np.array_equal(split_counts(np.array([5]), 3).ravel(),
                          [2, 2, 1])


def test_rebalance_targets_pinned():
    from repro.fleet.sched import rebalance_targets

    backlog = np.array([10, 0], dtype=np.int64)
    cap = np.array([1, 3], dtype=np.int64)
    surplus, deficit = rebalance_targets(backlog, cap, backlog.sum(),
                                         cap.sum(), np)
    # energy-proportional targets: 10*1//4 = 2, 10*3//4 = 7
    assert np.array_equal(surplus, [8, 0])
    assert np.array_equal(deficit, [0, 7])


_QS = collections.namedtuple("_QS", "q_len q_head q_t q_r rebalanced")


class _SpStub:  # the only SchedParams fields the queue helpers touch
    W = 2
    Q = 6
    rebalance_max = 3


def test_rebalance_pop_push_pinned():
    """Work stealing is a pure value transfer: tail entries pop into
    the ppermute buffers oldest-of-the-moved first and land at the
    receiver's tail in the same order, bit-for-bit."""
    from repro.fleet.sched import (queue_pop_tail, queue_push_tail,
                                   rebalance_moves)

    sp = _SpStub()
    giver = _QS(q_len=np.array([3, 1], dtype=np.int64),
                q_head=np.array([2, 0], dtype=np.int64),
                q_t=np.arange(12, dtype=np.float64).reshape(2, 6),
                q_r=np.arange(12, dtype=np.int64).reshape(2, 6) * 10,
                rebalanced=np.int64(0))
    move = rebalance_moves(sp, giver.q_len, np.int64(3), np)
    assert np.array_equal(move, [3, 0])  # w0 fills the give, w1 spared
    giver2, bt, br = queue_pop_tail(sp, giver, move, np)
    assert np.array_equal(giver2.q_len, [0, 1])
    # w0 ring: head=2, len=3 -> physical slots [2, 3, 4], in order
    assert np.array_equal(bt[0], [2.0, 3.0, 4.0])
    assert np.array_equal(br[0], [20, 30, 40])
    assert np.array_equal(bt[1], [0.0, 0.0, 0.0])  # untaken lanes zeroed

    taker = _QS(q_len=np.array([1, 0], dtype=np.int64),
                q_head=np.array([4, 1], dtype=np.int64),
                q_t=np.zeros((2, 6)), q_r=np.zeros((2, 6), dtype=np.int64),
                rebalanced=np.int64(0))
    taker2 = queue_push_tail(sp, taker, move, bt, br, xp=np)
    assert np.array_equal(taker2.q_len, [4, 0])
    assert int(taker2.rebalanced) == 3  # the receiver counts arrivals
    # tail of w0: head=4, len=1 -> slots [5, 0, 1] wrap, order preserved
    assert taker2.q_t[0, 5] == 2.0 and taker2.q_r[0, 5] == 20
    assert taker2.q_t[0, 0] == 3.0 and taker2.q_r[0, 0] == 30
    assert taker2.q_t[0, 1] == 4.0 and taker2.q_r[0, 1] == 40


def test_rebalance_host_moves_backlog_to_energy_rich_shard():
    from repro.fleet.sched import rebalance_host

    sps = [_SpStub(), _SpStub()]
    starved = _QS(q_len=np.array([3, 2], dtype=np.int64),
                  q_head=np.zeros(2, dtype=np.int64),
                  q_t=np.arange(12, dtype=np.float64).reshape(2, 6),
                  q_r=np.arange(12, dtype=np.int64).reshape(2, 6),
                  rebalanced=np.int64(0))
    rich = _QS(q_len=np.zeros(2, dtype=np.int64),
               q_head=np.zeros(2, dtype=np.int64),
               q_t=np.zeros((2, 6)), q_r=np.zeros((2, 6), dtype=np.int64),
               rebalanced=np.int64(0))
    plans = [np.zeros(4), np.full(4, 1e-3)]  # shard 1 has all the energy
    out = rebalance_host(sps, [starved, rich], plans)
    assert np.array_equal(out[0].q_len, [0, 0])  # fully drained
    assert np.array_equal(out[1].q_len, [3, 2])
    assert int(out[1].rebalanced) == 5
    # pure value transfer: the moved payloads survive bit-for-bit
    assert sorted(out[1].q_t[0, :3]) == [0.0, 1.0, 2.0]
    assert sorted(out[1].q_t[1, :2]) == [6.0, 7.0]


def _tiny_sharded_run(mesh_fleet=2, **kw):
    from repro.fleet.workloads import lm_workload
    from repro.launch.fleet import make_power_matrix, run_scheduled

    power = make_power_matrix(["RF"], 2, 2.0, 0.01, 0)
    return run_scheduled(power, 0.01, 8, [lm_workload()], rate_rps=1.0,
                         mix=np.array([1.0]), n_steps=200, seed=0,
                         backend="jax", mesh_fleet=mesh_fleet, **kw)


def test_mesh_fleet_must_divide_workers():
    with pytest.raises(ValueError, match="does not divide"):
        _tiny_sharded_run(mesh_fleet=3)  # 8 % 3 != 0


def test_sharded_serve_never_falls_back_to_one_device(monkeypatch):
    # --mesh-fleet K runs on a K-device mesh by default: with fewer
    # devices it raises; the one-device vmap runs only when asked for
    import jax
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    with pytest.raises(ValueError, match="needs 2 devices"):
        _tiny_sharded_run(mesh_fleet=2)


def test_sharded_rejects_pallas_kernel():
    with pytest.raises(ValueError, match="Pallas serve megakernel"):
        _tiny_sharded_run(kernel="pallas")


def test_sharded_rejects_trace_obs():
    with pytest.raises(ValueError, match="event ring"):
        _tiny_sharded_run(obs_mode="trace")


def test_sharded_rebalance_cadence_must_align():
    with pytest.raises(ValueError, match="multiple of dispatch"):
        _tiny_sharded_run(rebalance_every_s=0.15)  # 15 ticks vs 10


def test_shard_sched_params_slices_per_worker_fields():
    from repro.fleet.scheduler import FleetScheduler
    from repro.fleet.sched import PER_WORKER_FIELDS, shard_sched_params
    from repro.fleet.workloads import lm_workload
    from repro.launch.fleet import build_dispatch_pool, make_power_matrix

    power = make_power_matrix(["RF", "SOM"], 2, 2.0, 0.01, 0)
    wl = lm_workload()
    pool = build_dispatch_pool(power, 0.01, 8, [wl], seed=0)
    sp = FleetScheduler(pool, [wl], shards=2, rebalance_max=4).params
    v = shard_sched_params(sp, 1)
    assert v.n == 4 and v.shards == 1
    assert v.max_queue == sp.max_queue // 2
    # ring headroom: admission slice + every in-flight retry requeued at
    # once + an incoming rebalance push cannot overflow
    assert v.Q == sp.max_queue // 2 + 4 * sp.B + sp.rebalance_max
    for f in PER_WORKER_FIELDS:
        a = np.asarray(getattr(sp, f))
        if a.ndim >= 1 and a.shape[0] == sp.n:
            assert np.array_equal(np.asarray(getattr(v, f)), a[4:8]), f


_FLEET_SOA = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P
from repro.sharding.context import FLEET_AXIS, make_fleet_mesh
K, ns = 8, 32
state = {{"v": np.arange(K * ns, dtype=np.int64).reshape(K, ns),
         "on": (np.arange(K * ns) % 3 == 0).reshape(K, ns)}}

def per_shard(sh):
    # a miniature serve shard: SoA carry, scan over ticks, psum +
    # ring-ppermute collectives feeding back into per-worker state
    def body(c, i):
        v = c["v"] + jnp.where(c["on"], i, 0)
        tot = lax.psum(jnp.sum(v), FLEET_AXIS)
        nxt = lax.ppermute(jnp.sum(v), FLEET_AXIS,
                           [(s, (s + 1) % K) for s in range(K)])
        return {{"v": v + tot % 7 + nxt % 5, "on": c["on"]}}, jnp.sum(v)
    return lax.scan(body, sh, jnp.arange(10, dtype=jnp.int64))

def shard_fn(sh):
    c, ys = per_shard(jax.tree.map(lambda x: x[0], sh))
    return jax.tree.map(lambda x: x[None], (c, ys))

mesh = make_fleet_mesh(K)
sm = jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                           in_specs=(P(FLEET_AXIS),),
                           out_specs=P(FLEET_AXIS), check_vma=False))(state)
vm = jax.vmap(per_shard, axis_name=FLEET_AXIS)(state)
ok = all(bool((np.asarray(a) == np.asarray(b)).all())
         for a, b in zip(jax.tree.leaves(sm), jax.tree.leaves(vm)))
assert ok, "shard_map and vmap evaluations disagree"
print("OK")
"""


@pytest.mark.slow
def test_shard_map_compat_fleet_soa_state():
    """shard_map over the fleet mesh and a single-device vmap of the
    same per-shard program (SoA state, scan, psum/ppermute ring) are
    bit-identical on a forced 8-device CPU mesh."""
    code = _FLEET_SOA.format(src=SRC)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.startswith("OK")


_SHARDED_SERVE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, {src!r})
import numpy as np
from repro.launch.fleet import (make_power_matrix, run_scheduled,
                                trace_family_labels)
from repro.fleet.workloads import (har_workload, harris_workload,
                                   lm_workload)
TRACES = ["RF", "SOM", "SIM", "SOR", "SIR"]
DT, K, N, dur, rows = 0.01, 8, 256, 30.0, 16
power = make_power_matrix(TRACES, rows, dur, DT, 0)
fams = trace_family_labels(TRACES, rows)
out = {{}}
for reb in (0.0, 1.0):
    blobs = {{}}
    for name, backend, placement in (("numpy", "numpy", "mesh"),
                                     ("single", "jax", "single"),
                                     ("mesh", "jax", "mesh")):
        wls = [har_workload(), harris_workload(), lm_workload()]
        r = run_scheduled(power, DT, N, wls, rate_rps=N / 10.0,
                          mix=np.array([0.4, 0.3, 0.3]),
                          n_steps=int(dur / DT), seed=0, backend=backend,
                          sched="forecast", trace_families=fams,
                          mesh_fleet=K, rebalance_every_s=reb,
                          fleet_placement=placement)
        for k in ("mode", "backend", "mesh_fleet", "obs"):
            r.pop(k, None)
        blobs[name] = json.dumps(r, sort_keys=True, default=str)
    out[str(reb)] = {{"agree": len(set(blobs.values())) == 1,
                     "rebalanced": json.loads(blobs["mesh"])["rebalanced"],
                     "completed": json.loads(blobs["mesh"])["completed"]}}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_serve_three_evaluation_bitequality():
    """The acceptance pin for --mesh-fleet: at N=256 / K=8 on a forced
    8-device CPU mesh, the NumPy host twin, the single-device vmap, and
    the real shard_map mesh produce bit-identical full summaries (every
    request/quality/latency counter) with rebalance off AND on, and the
    rebalance-on case actually moves requests."""
    code = _SHARDED_SERVE.format(src=SRC)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["0.0"]["agree"], out
    assert out["1.0"]["agree"], out
    assert out["0.0"]["rebalanced"] == 0
    assert out["1.0"]["rebalanced"] > 0  # the pin is not vacuous
