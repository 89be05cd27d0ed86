"""Shared (rows, 128)-lane tiling for the fleet Pallas kernels.

Every fleet kernel views the flat (N,) worker axis as a (rows, LANES)
matrix and tiles it (block_rows, LANES) per grid step. N is rarely a
whole number of tiles, so each kernel pads up to the tile grid on the
way in and slices the pad lanes off on the way out; ``serve_tick``
(and any future fleet kernel) reuses this one implementation of that
pad/reshape arithmetic.

Pad lanes must stay *inert* through a kernel — callers choose the fill
value per array so padded workers never wake, never hold work, and never
emit (e.g. fill C with 1.0 so a padded sqrt stays finite, fill ``on``
with 0, fill thresholds with a huge sentinel).
"""
from __future__ import annotations

import jax.numpy as jnp

LANES = 128


def tile_rows(n: int, block_rows: int) -> tuple[int, int]:
    """Grid geometry for ``n`` workers: ``(rows, total)`` where ``rows``
    is the smallest multiple of ``block_rows`` covering ``n`` lanes-wide
    rows and ``total = rows * LANES`` is the padded worker count."""
    tile = block_rows * LANES
    rows = -(-n // tile) * block_rows
    return rows, rows * LANES


def pad_to_tiles(x, n: int, rows: int, fill, dtype=None):
    """Pad the (N,) array ``x`` to ``rows * LANES`` workers with ``fill``
    and reshape to the (rows, LANES) matrix the kernels tile over."""
    x = jnp.asarray(x) if dtype is None else jnp.asarray(x, dtype)
    total = rows * LANES
    return jnp.pad(x, (0, total - n), constant_values=fill
                   ).reshape(rows, LANES)


def untile(y, n: int):
    """Inverse of :func:`pad_to_tiles`: flatten the (rows, LANES) kernel
    output and slice off the pad lanes, returning the first ``n``."""
    return y.reshape(-1)[:n]
