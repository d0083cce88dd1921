"""Backward Pallas kernels for the fused GMM+SwiGLU (custom VJP).

Flash-style: the forward saves only (x, w_in); the backward recomputes the
gate/up activations tile by tile in VMEM (K-tiled, fp32 accumulators)
instead of saving the [E, C, 2F] pre-activation from the forward. Three
kernels, each tiled so its working set fits the v5e kernel VMEM limit:

    dg, du = dout ⊙ u ⊙ silu'(g), dout ⊙ silu(g)   (g, u recomputed)
    dx     = dg·wgᵀ + du·wuᵀ                        (accumulated over F)
    dwg, dwu = xᵀ·dg, xᵀ·du                         (accumulated over M)

Gate and up are read from ``w_in [E, K, 2F]`` by two BlockSpecs, as in the
forward kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tiling as T
from .gmm_swiglu import gate_up_operands, gmm_swiglu

_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def _dgu_kernel(x_ref, wg_ref, wu_ref, do_ref, dg_ref, du_ref, g_acc, u_acc):
    # grid (E, M, F, K): recompute g/u over K, then form dg/du for the tile.
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
        g, u = g_acc[...], u_acc[...]
        sig = jax.nn.sigmoid(g)
        do = do_ref[0].astype(jnp.float32)
        dg_ref[0] = (do * u * sig * (1.0 + g * (1.0 - sig))).astype(
            dg_ref.dtype)
        du_ref[0] = (do * g * sig).astype(du_ref.dtype)


def _dx_kernel(dg_ref, du_ref, wg_ref, wu_ref, dx_ref, acc):
    # grid (E, M, K, F): dx tile [bm, bk] accumulates over F.
    f = pl.program_id(3)

    @pl.when(f == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += (T.mxu_dot(dg_ref[0], wg_ref[0], _NT)
                 + T.mxu_dot(du_ref[0], wu_ref[0], _NT))

    @pl.when(f == pl.num_programs(3) - 1)
    def _out():
        dx_ref[0] = acc[...].astype(dx_ref.dtype)


def _dw_kernel(x_ref, dg_ref, du_ref, dwg_ref, dwu_ref, g_acc, u_acc):
    # grid (E, K, F, M): dw tiles [bk, bf] accumulate over M.
    m = pl.program_id(3)

    @pl.when(m == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    x = x_ref[0]
    g_acc[...] += T.mxu_dot(x, dg_ref[0], _TN)
    u_acc[...] += T.mxu_dot(x, du_ref[0], _TN)

    @pl.when(m == pl.num_programs(3) - 1)
    def _out():
        dwg_ref[0] = g_acc[...].astype(dwg_ref.dtype)
        dwu_ref[0] = u_acc[...].astype(dwu_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bf", "interpret"))
def gmm_swiglu_bwd(x, w_in, dout, *, bm=128, bf=128, interpret=False):
    """x: [E,C,K]; w_in: [E,K,2F]; dout: [E,C,F] → (dx [E,C,K], dw_in)."""
    E, C, K = x.shape
    F = w_in.shape[-1] // 2
    bm, Cp = T.row_block(C, bm)
    bf = T.lane_block(F, bf)
    wg, wu, fused = gate_up_operands(w_in)
    up = F // bf if fused else 0
    it, wit = x.dtype.itemsize, w_in.dtype.itemsize
    xp, dop = T.pad_rows(x, Cp), T.pad_rows(dout, Cp)
    par3 = ("parallel", "parallel", "parallel", "arbitrary")

    bkr = T.fit_k(K, lambda b: (2 * (bm * b + 2 * b * bf) * it
                                + 6 * bm * bf * it + 8 * bm * bf * 4),
                  "gmm_swiglu_bwd dg/du")
    dg, du = pl.pallas_call(
        _dgu_kernel,
        grid=(E, Cp // bm, F // bf, K // bkr),
        in_specs=[
            pl.BlockSpec((1, bm, bkr), lambda e, i, f, k: (e, i, k)),
            pl.BlockSpec((1, bkr, bf), lambda e, i, f, k: (e, k, f)),
            pl.BlockSpec((1, bkr, bf), lambda e, i, f, k: (e, k, f + up)),
            pl.BlockSpec((1, bm, bf), lambda e, i, f, k: (e, i, f)),
        ],
        out_specs=[pl.BlockSpec((1, bm, bf), lambda e, i, f, k: (e, i, f))] * 2,
        out_shape=[jax.ShapeDtypeStruct((E, Cp, F), x.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32)] * 2,
        compiler_params=T.compiler_params(*par3),
        interpret=interpret,
    )(xp, wg, wu, dop)

    bk = T.fit_k(K, lambda b: (2 * (2 * bm * bf + 2 * b * bf) * it
                               + 2 * bm * b * it + 3 * bm * b * 4),
                 "gmm_swiglu_bwd dx")
    dx = pl.pallas_call(
        _dx_kernel,
        grid=(E, Cp // bm, K // bk, F // bf),
        in_specs=[
            pl.BlockSpec((1, bm, bf), lambda e, i, k, f: (e, i, f)),
            pl.BlockSpec((1, bm, bf), lambda e, i, k, f: (e, i, f)),
            pl.BlockSpec((1, bk, bf), lambda e, i, k, f: (e, k, f)),
            pl.BlockSpec((1, bk, bf), lambda e, i, k, f: (e, k, f + up)),
        ],
        out_specs=pl.BlockSpec((1, bm, bk), lambda e, i, k, f: (e, i, k)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, K), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        compiler_params=T.compiler_params(*par3),
        interpret=interpret,
    )(dg, du, wg, wu)

    bkw = T.fit_k(K, lambda b: (2 * (bm * b + 2 * bm * bf) * it
                                + 4 * b * bf * wit + 4 * b * bf * 4),
                  "gmm_swiglu_bwd dw")
    dwg, dwu = pl.pallas_call(
        _dw_kernel,
        grid=(E, K // bkw, F // bf, Cp // bm),
        in_specs=[
            pl.BlockSpec((1, bm, bkw), lambda e, k, f, m: (e, m, k)),
            pl.BlockSpec((1, bm, bf), lambda e, k, f, m: (e, m, f)),
            pl.BlockSpec((1, bm, bf), lambda e, k, f, m: (e, m, f)),
        ],
        out_specs=[pl.BlockSpec((1, bkw, bf),
                                lambda e, k, f, m: (e, k, f))] * 2,
        out_shape=[jax.ShapeDtypeStruct((E, K, F), w_in.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bkw, bf), jnp.float32)] * 2,
        compiler_params=T.compiler_params(*par3),
        interpret=interpret,
    )(xp, dg, du)
    return dx[:, :C], jnp.concatenate([dwg, dwu], axis=-1)


# ---------------------------------------------------------------------------
# custom_vjp wrapper: fully-Pallas fused GMM+SwiGLU for training.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gmm_swiglu_trainable(x, w_in, interpret=False):
    return gmm_swiglu(x, w_in, interpret=interpret)


def _fwd(x, w_in, interpret):
    return gmm_swiglu(x, w_in, interpret=interpret), (x, w_in)


def _bwd(interpret, res, dout):
    x, w_in = res
    return gmm_swiglu_bwd(x, w_in, dout, interpret=interpret)


gmm_swiglu_trainable.defvjp(_fwd, _bwd)
