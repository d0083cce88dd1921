"""Fused GMM1 + SwiGLU Pallas kernel — the VMEM-resident producer/consumer.

This is the TPU adaptation of the paper's L2-reuse insight (§2.1, §4.4,
§6.1): on Ascend, a GMM tile's output lands in the shared L2 and the SwiGLU
tile reads it back at >4× HBM bandwidth; on TPU we go one step further and
never let the intermediate leave VMEM at all — the gate/up matmul results
are consumed by the SwiGLU activation inside the same tile program.

Gate and up are read straight from the fused ``[E, K, 2F]`` projection by
two BlockSpecs on the same array: column block ``j`` (gate) and ``j + F/bn``
(up), so no relayout copy of ``w_in`` is made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tiling as T
from .ref import gmm_swiglu_ref  # noqa: F401


def gate_up_operands(w_in):
    """``(wg, wu, fused)``: the gate and up operands of ``w_in [E, K, 2F]``.

    With F a multiple of 128 (``fused``) both are ``w_in`` itself, and the
    caller's index map starts up's column blocks ``F / bn`` blocks after
    gate's. Otherwise no lane block can start at column F, so the halves
    are sliced apart (small widths only)."""
    F = w_in.shape[-1] // 2
    if F % T.LANE == 0:
        return w_in, w_in, True
    return w_in[..., :F], w_in[..., F:], False


def _gmm_swiglu_kernel(x_ref, wg_ref, wu_ref, o_ref, g_acc, u_acc):
    # x_ref: [1, bm, bk]; wg_ref/wu_ref: [1, bk, bn]; o_ref: [1, bm, bn]
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    x = x_ref[0]
    g_acc[...] += T.mxu_dot(x, wg_ref[0])
    u_acc[...] += T.mxu_dot(x, wu_ref[0])

    @pl.when(k == pl.num_programs(3) - 1)
    def _out():
        # SwiGLU on the VMEM-resident accumulators (never round-trips HBM).
        g = g_acc[...]
        o_ref[0] = (g * jax.nn.sigmoid(g) * u_acc[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gmm_swiglu(x, w_in, *, bm: int = 128, bn: int = 128,
               interpret: bool = False):
    """x: [E, C, K]; w_in: [E, K, 2F] (gate ‖ up) → [E, C, F]."""
    E, C, K = x.shape
    F = w_in.shape[-1] // 2
    bm, Cp = T.row_block(C, bm)
    bn = T.lane_block(F, bn)
    wg, wu, fused = gate_up_operands(w_in)
    up = F // bn if fused else 0
    it = x.dtype.itemsize
    bk = T.fit_k(K, lambda bk: (2 * (bm * bk + 2 * bk * bn) * it
                                + 2 * bm * bn * it + 4 * bm * bn * 4),
                 "gmm_swiglu")

    out = pl.pallas_call(
        _gmm_swiglu_kernel,
        grid=(E, Cp // bm, F // bn, K // bk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j + up)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] * 2,
        compiler_params=T.compiler_params(
            "parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(T.pad_rows(x, Cp), wg, wu)
    return out[:, :C]
