"""Ambient mesh context.

Model code is written once and runs either on a single device (smoke tests,
no context) or under a production mesh (dry-run/launch). The context carries
the mesh and the axis-name conventions:

- ``dp_axes``: data-parallel axes (('pod', 'data') multi-pod, ('data',)
  single-pod) — batch is sharded over these,
- ``tp_axis``: tensor/model-parallel axis — attention heads, MLP hidden,
  vocab, MoE experts (expert parallelism), and sequence-parallel segments
  are sharded over this one.

``shard_hint`` is a no-op without a context so the pure model code never
depends on distribution being configured.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()

# the fleet mesh axis: the serve scan's worker dimension is row-sharded
# over this 1-D axis (repro.fleet.backend_jax sharded run_serve); kept
# distinct from the model axes above so a future combined launch can
# nest both
FLEET_AXIS = "fleet"


def make_fleet_mesh(k: int) -> Mesh:
    """1-D ``(fleet,)`` mesh over the first ``k`` local devices — one
    control-plane shard per device. Raises a clear error when the host
    exposes fewer devices (on CPU, force more with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K``)."""
    from jax.experimental import mesh_utils
    devs = jax.devices()
    if len(devs) < k:
        raise ValueError(
            f"--mesh-fleet {k} needs {k} devices but jax.device_count() "
            f"== {len(devs)}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={k} (before jax "
            f"imports) or ask for the single-device vmap with "
            f"--fleet-placement single")
    # the shard ring's ppermute neighbours should be physical neighbours:
    # let mesh_utils order the devices by the chip topology
    return Mesh(mesh_utils.create_device_mesh((k,), devices=devs[:k]),
                (FLEET_AXIS,))


@dataclasses.dataclass(frozen=True)
class MeshContext:
    mesh: Mesh
    dp_axes: tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    # False: the model axis is folded into data parallelism (pure-DP/FSDP
    # layouts); activation hints drop their tp entries and partition rules
    # skip TP sharding.
    tp_enabled: bool = True

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def all_axes(self) -> tuple[str, ...]:
        return tuple(self.dp_axes) + (self.tp_axis,)

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))


def current_mesh_context() -> MeshContext | None:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def mesh_context(ctx: MeshContext):
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = ctx
    try:
        with ctx.mesh:
            yield ctx
    finally:
        _STATE.ctx = prev


def shard_hint(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint if a mesh context is active, else identity."""
    ctx = current_mesh_context()
    if ctx is None:
        return x
    if not ctx.tp_enabled:
        spec = tuple(None if s == ctx.tp_axis else s for s in spec)
    return jax.lax.with_sharding_constraint(x, ctx.sharding(*spec))


def batch_spec() -> tuple:
    """PartitionSpec entry for the global-batch axis."""
    ctx = current_mesh_context()
    if ctx is None:
        return (None,)
    return (ctx.dp_axes,)
