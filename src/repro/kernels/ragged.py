"""Grouped matmul over an expert-sorted buffer whose groups end short of it.

``lhs`` [m, k] holds group 0's rows first, then group 1's, and so on;
``group_sizes`` [G] say how many, and their sum may be less than ``m``.
Row r of the result is ``lhs[r] @ rhs[g]`` for the group g holding row r.
Rows past the last group are left undefined: callers select them away,
never multiply them by a zero weight.

On a TPU the Pallas megablox kernel (``jax.experimental.pallas.ops.tpu.
megablox``) runs it: its grid walks only the row tiles that hold a group's
rows, so the empty tail costs nothing. Elsewhere ``jax.lax.ragged_dot``
computes the same rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The kernel's row tile, and the lane tiles it may take for the contracted
# and output widths: the first that divides the width, else the width.
ROW_TILE = 512
LANE_TILES = (1024, 512, 256, 128)


def row_tile(m: int) -> int:
    """The kernel's row tile for a buffer of ``m`` rows (at least 16)."""
    return min(ROW_TILE, -(-m // 16) * 16)


def buffer_rows(rows: int) -> int:
    """The rows of a buffer that holds ``rows`` rows and that the kernel
    takes: a multiple of its row tile."""
    tm = row_tile(rows)
    return -(-rows // tm) * tm


def _lane_tile(x: int) -> int:
    return next((t for t in LANE_TILES if x % t == 0), x)


def _tiling(m: int, k: int, n: int):
    return row_tile(m), _lane_tile(k), _lane_tile(n)


def on_tpu() -> bool:
    """Whether :func:`grouped_matmul` runs the TPU's kernel."""
    return jax.default_backend() == "tpu"


def rows_issued(group_sizes, m: int):
    """The rows :func:`grouped_matmul` computes for ``group_sizes`` over
    ``m`` rows (int32). On a TPU, as the kernel's own metadata counts them:
    each group's rows rounded out to whole row tiles, a tile that two
    groups share counted once for each. Elsewhere the groups' rows."""
    if not on_tpu():
        return jnp.sum(group_sizes, dtype=jnp.int32)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    tm = row_tile(m)
    _, tiles = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=group_sizes.shape[0],
        visit_empty_groups=False)
    return (tiles * tm).astype(jnp.int32)


def tpu_grouped_matmul(lhs, rhs, group_sizes):
    """:func:`grouped_matmul` by the megablox kernel (TPU only)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops
    return ops.gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                   _tiling)


def grouped_matmul(lhs, rhs, group_sizes):
    """[m, k] rows by group × [G, k, n] → [m, n] in ``lhs.dtype``; ``m``
    a multiple of ``row_tile(m)``."""
    if on_tpu():
        return tpu_grouped_matmul(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=lhs.dtype)
