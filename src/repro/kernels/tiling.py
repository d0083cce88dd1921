"""Block-size selection and the MXU product shared by the grouped-GEMM
kernels.

Mosaic accepts a block only if each of its last two dimensions is a
multiple of the (8, 128) tile or spans the whole array dimension, and a
kernel's double-buffered blocks plus its scratch must fit the scoped VMEM
limit. These helpers never return a block that breaks either rule: rows
are padded up to a multiple of the row block, lane dimensions use a
128-multiple divisor (or the whole dimension), and the K block shrinks
until the working set fits — or a ``ValueError`` says why nothing does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

SUBLANE, LANE = 8, 128
# Default scoped VMEM limit of one TPU v5e kernel (the chip has 128 MiB of
# VMEM; Mosaic grants a kernel 16 MiB of it unless told otherwise).
VMEM_LIMIT_BYTES = 16 * 2**20


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def row_block(rows: int, pref: int) -> tuple[int, int]:
    """``(bm, padded_rows)``: a multiple-of-8 row block and the row count
    padded up to a multiple of it."""
    bm = min(round_up(pref, SUBLANE), round_up(rows, SUBLANE))
    return bm, round_up(rows, bm)


def lane_blocks(dim: int, pref: int) -> list[int]:
    """Legal blocks for a lane (last) dimension, largest first: the
    128-multiple divisors of ``dim`` up to ``pref`` (the whole ``dim`` when
    it is not a multiple of 128, which Mosaic also accepts)."""
    if dim % LANE:
        return [dim]
    top = max(LANE, min(pref, dim) // LANE * LANE)
    return [b for b in range(top, 0, -LANE) if dim % b == 0]


def lane_block(dim: int, pref: int) -> int:
    return lane_blocks(dim, pref)[0]


def fit_k(K: int, working_set, what: str) -> int:
    """Largest K block — all of K, else a 128-multiple divisor — whose
    ``working_set(bk)`` bytes fit ``VMEM_LIMIT_BYTES``."""
    for bk in lane_blocks(K, K):
        if working_set(bk) <= VMEM_LIMIT_BYTES:
            return bk
    raise ValueError(
        f"{what}: no K block of K={K} fits the {VMEM_LIMIT_BYTES} byte VMEM "
        f"limit (smallest needs {working_set(lane_blocks(K, K)[-1])} bytes)")


def pad_rows(x, rows: int):
    """Zero-pad axis 1 of ``[E, C, ...]`` to ``rows`` (zeros are inert in
    every product and reduction these kernels compute)."""
    extra = rows - x.shape[1]
    if extra == 0:
        return x
    return jnp.pad(x, [(0, 0), (0, extra)] + [(0, 0)] * (x.ndim - 2))


def compiler_params(*semantics: str):
    """Grid semantics (``"parallel"``/``"arbitrary"``) plus the VMEM limit
    the working sets were checked against."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def mxu_dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """``dot_general`` (by default ``a @ b``) accumulated in fp32. Two bf16
    operands take DEFAULT precision whatever
    ``jax.default_matmul_precision`` says: one MXU pass
    already multiplies them exactly, and Mosaic refuses an fp32 contract
    precision on bf16 operands ("Bad lhs type")."""
    bf16 = a.dtype == b.dtype == jnp.bfloat16
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.DEFAULT if bf16 else None,
        preferred_element_type=jnp.float32)
