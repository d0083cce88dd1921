"""Grouped GEMM Pallas kernel — expert-block tiles, K reduction in VMEM.

The paper's GMM decomposition constraint (§4.2): task-level parallelism only
along token/expert-block dimensions; the K reduction stays intact so the
accumulation structure and expert-local layout survive. On TPU that maps to
a grid over (expert, M-tile, N-tile) with K whole inside the tile when it
fits VMEM, and otherwise a trailing K axis that accumulates into an fp32
VMEM scratch tile (never through HBM).

Block sizes come from :mod:`.tiling`: rows are padded up to a multiple of
the row block, lane blocks are 128-aligned, and the working set is checked
against the v5e kernel VMEM limit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tiling as T
from .ref import gmm_ref  # noqa: F401  (oracle lives alongside)


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref):
    # x_ref: [1, bm, bk]; w_ref: [1, bk, bn]; o_ref: [1, bm, bn]
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += T.mxu_dot(x_ref[0], w_ref[0])

    @pl.when(k == pl.num_programs(3) - 1)
    def _out():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gmm(x, w, *, bm: int = 128, bn: int = 128, interpret: bool = False):
    """x: [E, C, K] expert-grouped tokens; w: [E, K, N] → [E, C, N]."""
    E, C, K = x.shape
    N = w.shape[-1]
    bm, Cp = T.row_block(C, bm)
    bn = T.lane_block(N, bn)
    it = x.dtype.itemsize
    bk = T.fit_k(K, lambda bk: (2 * (bm * bk + bk * bn) * it
                                + 2 * bm * bn * it + 2 * bm * bn * 4), "gmm")

    out = pl.pallas_call(
        _gmm_kernel,
        grid=(E, Cp // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=T.compiler_params(
            "parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(T.pad_rows(x, Cp), w)
    return out[:, :C]
