"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.gmm import gmm
from repro.kernels.gmm_swiglu import gmm_swiglu
from repro.kernels.swiglu_add import (swiglu_add_interleaved,
                                      swiglu_add_serial)

SHAPES_GMM = [
    (1, 128, 64, 128),
    (4, 256, 192, 256),
    (3, 64, 96, 160),      # non-128-multiple N
    (8, 512, 128, 64),
    (2, 200, 256, 384),    # rows padded up to the row block
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("E,C,K,N", SHAPES_GMM)
def test_gmm_matches_oracle(E, C, K, N, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (E, C, K), dtype)
    w = jax.random.normal(k2, (E, K, N), dtype) * 0.1
    got = gmm(x, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref.gmm_ref(x, w), np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("E,C,K,F", [(2, 128, 64, 128), (4, 192, 96, 64),
                                     (1, 256, 128, 384)])
def test_gmm_swiglu_fused(E, C, K, F, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (E, C, K), dtype)
    w = jax.random.normal(k2, (E, K, 2 * F), dtype) * 0.1
    got = gmm_swiglu(x, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref.gmm_swiglu_ref(x, w), np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("M", [256, 1024, 4096])
@pytest.mark.parametrize("mode", ["serial", "interleaved"])
def test_swiglu_add_modes(M, mode, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    h = jax.random.normal(k1, (M, 4096), dtype)
    y = jax.random.normal(k2, (M, 2048), dtype)
    fn = swiglu_add_serial if mode == "serial" else swiglu_add_interleaved
    got = fn(h, y, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref.swiglu_add_ref(h, y), np.float32), **_tol(dtype))


def test_moe_expert_ffn_drop_in():
    """The fused-kernel path is a drop-in gmm_fn for moe_grouped."""
    from repro.models.moe import MoEConfig, init_moe, moe_grouped
    mc = MoEConfig(n_experts=4, top_k=2, d_expert=32, capacity_factor=8.0)
    params = init_moe(jax.random.PRNGKey(3), 64, mc)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 64), jnp.float32)

    def gmm_fn(disp, w_in, w_down, act):
        return ops.moe_expert_ffn(disp, w_in.astype(disp.dtype),
                                  w_down.astype(disp.dtype), act)

    base = moe_grouped(params, x, mc, cap=64)
    fused = moe_grouped(params, x, mc, cap=64, gmm_fn=gmm_fn)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                               rtol=1e-4, atol=1e-4)


def test_vmem_budget_guard():
    # K=60000 is not a multiple of 128, so it cannot be tiled, and whole it
    # does not fit the kernel VMEM limit.
    x = jnp.zeros((1, 128, 60000), jnp.float32)
    w = jnp.zeros((1, 60000, 512), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        gmm(x, w, bm=128, bn=512, interpret=True)


def test_gmm_tiles_k_that_does_not_fit_whole():
    """A K too large for one VMEM block is reduced over K tiles."""
    from repro.kernels import tiling
    K = 8192
    ws = lambda bk: 2 * (128 * bk + bk * 512) * 4     # noqa: E731
    assert ws(K) > tiling.VMEM_LIMIT_BYTES
    assert tiling.fit_k(K, ws, "t") < K
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    x = jax.random.normal(k1, (1, 128, K), jnp.float32)
    w = jax.random.normal(k2, (1, K, 512), jnp.float32) * 0.01
    np.testing.assert_allclose(
        np.asarray(gmm(x, w, bm=128, bn=512, interpret=True)),
        np.asarray(ref.gmm_ref(x, w)), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows,pref", [(2736, 128), (5, 128), (64, 128),
                                       (1000, 256), (130, 8)])
def test_row_block_aligned_and_covering(rows, pref):
    from repro.kernels.tiling import row_block
    bm, padded = row_block(rows, pref)
    assert bm % 8 == 0 and bm <= max(pref, 8)
    assert padded % bm == 0 and rows <= padded < rows + bm


@pytest.mark.parametrize("dim,pref", [(1536, 128), (512, 512), (7168, 512),
                                      (2048, 384), (160, 128), (64, 128)])
def test_lane_blocks_aligned_or_whole(dim, pref):
    from repro.kernels.tiling import lane_blocks
    blocks = lane_blocks(dim, pref)
    assert blocks == sorted(blocks, reverse=True)
    for b in blocks:
        assert dim % b == 0
        assert b == dim or (b % 128 == 0 and b <= pref)


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("E,C,K,F", [(2, 128, 64, 128), (3, 64, 96, 64),
                                     (2, 100, 128, 256)])
def test_gmm_swiglu_custom_vjp(E, C, K, F, dtype):
    """Pallas backward kernels == jax.vjp of the jnp oracle."""
    from repro.kernels.gmm_swiglu_bwd import gmm_swiglu_trainable
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k1, (E, C, K), dtype)
    w = jax.random.normal(k2, (E, K, 2 * F), dtype) * 0.1
    dout = jax.random.normal(k3, (E, C, F), dtype)

    out, vjp = jax.vjp(lambda x, w: gmm_swiglu_trainable(x, w, True), x, w)
    dx, dw = vjp(dout)
    out_ref, vjp_ref = jax.vjp(ref.gmm_swiglu_ref, x, w)
    dx_ref, dw_ref = vjp_ref(dout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-4, atol=1e-4)


def test_gmm_swiglu_vjp_tiles_k(monkeypatch):
    """Under a VMEM limit that all of K exceeds, the forward and the three
    backward kernels each reduce over K tiles and still match the oracle."""
    from repro.kernels import tiling
    from repro.kernels.gmm_swiglu_bwd import gmm_swiglu_trainable
    E, C, K, F = 1, 64, 512, 128
    fit_k, blocks = tiling.fit_k, []

    def spy(K, working_set, what):
        blocks.append(fit_k(K, working_set, what))
        return blocks[-1]

    monkeypatch.setattr(tiling, "VMEM_LIMIT_BYTES", 2**20)
    monkeypatch.setattr(tiling, "fit_k", spy)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    x = jax.random.normal(k1, (E, C, K), jnp.float32)
    w = jax.random.normal(k2, (E, K, 2 * F), jnp.float32) * 0.05
    dout = jax.random.normal(k3, (E, C, F), jnp.float32)
    out, vjp = jax.vjp(lambda x, w: gmm_swiglu_trainable(x, w, True), x, w)
    grads = vjp(dout)
    assert len(blocks) == 4 and all(b < K for b in blocks)
    out_ref, vjp_ref = jax.vjp(ref.gmm_swiglu_ref, x, w)
    for got, want in zip((out, *grads), (out_ref, *vjp_ref(dout))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_gmm_swiglu_vjp_bf16_vs_fp32_oracle():
    """bf16 kernel grads vs the fp32 oracle: the Pallas backward must be at
    least as accurate as the all-bf16 jnp path (its accumulators are f32)."""
    from repro.kernels.gmm_swiglu_bwd import gmm_swiglu_trainable
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(k1, (2, 64, 32), jnp.bfloat16)
    w = jax.random.normal(k2, (2, 32, 128), jnp.bfloat16) * 0.1
    dout = jax.random.normal(k3, (2, 64, 64), jnp.bfloat16)
    _, vjp = jax.vjp(lambda x, w: gmm_swiglu_trainable(x, w, True), x, w)
    dx, dw = vjp(dout)
    # fp32 oracle on the same (bf16-rounded) values
    _, vjp32 = jax.vjp(ref.gmm_swiglu_ref, x.astype(jnp.float32),
                       w.astype(jnp.float32))
    dx32, dw32 = vjp32(dout.astype(jnp.float32))
    _, vjp_bf = jax.vjp(ref.gmm_swiglu_ref, x, w)
    dx_bf, dw_bf = vjp_bf(dout)

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    assert err(dx, dx32) <= err(dx_bf, dx32) + 0.05
    assert err(dw, dw32) <= err(dw_bf, dw32) + 0.05
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(dx32), rtol=5e-2, atol=5e-2)
