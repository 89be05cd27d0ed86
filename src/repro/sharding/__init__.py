"""Mesh axes, partition rules, and the ambient mesh context."""
from repro.sharding.context import (MeshContext, current_mesh_context,
                                    mesh_context, shard_hint)

__all__ = ["MeshContext", "current_mesh_context", "mesh_context",
           "shard_hint"]
