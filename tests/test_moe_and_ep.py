"""MoE layer tests + multi-device EP equivalence (subprocess: the EP test
needs forced host devices, which must not leak into this process)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe import (MoEConfig, capacity, init_moe, make_dispatch,
                              moe_dense_ref, moe_grouped, router_topk)

KEY = jax.random.PRNGKey(0)
MC = MoEConfig(n_experts=6, top_k=2, d_expert=16, capacity_factor=8.0,
               n_padding_experts=2)


def test_router_masks_padding_and_normalizes():
    params = init_moe(KEY, 32, MC)
    x = jax.random.normal(KEY, (64, 32))
    p, i = router_topk(params["router"], x, MC)
    assert int(i.max()) < MC.n_experts          # padding never selected
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, rtol=1e-5)


def test_grouped_equals_dense_ref():
    params = init_moe(KEY, 32, MC)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    a = moe_dense_ref(params, x, MC, cap=512)
    b = moe_grouped(params, x, MC, cap=512)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_capacity_drop_consistency():
    """With a tiny capacity, both paths drop the same tokens."""
    params = init_moe(KEY, 32, MC)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 32))
    a = moe_dense_ref(params, x, MC, cap=4)
    b = moe_grouped(params, x, MC, cap=4)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_slots_unique_per_expert():
    p = jnp.ones((16, 2)) / 2
    i = jnp.stack([jnp.arange(16) % 4, (jnp.arange(16) + 1) % 4], 1)
    w, ii, slot = make_dispatch(p, i, 16, 4, 100)
    pairs = set()
    for t in range(16):
        for k in range(2):
            key = (int(ii[t, k]), int(slot[t, k]))
            assert key not in pairs, "slot collision"
            pairs.add(key)


def test_capacity_rounding():
    mc = MoEConfig(n_experts=8, top_k=2, d_expert=8, capacity_factor=1.0)
    assert capacity(100, mc, ep=4) % 4 == 0


_EP_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.parallel.ep import EPConfig, make_moe_ep
from repro.models.moe import MoEConfig, init_moe, moe_dense_ref

mesh = make_test_mesh(data=2, model=4)
mc = MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=8.0)
params = init_moe(jax.random.PRNGKey(0), 32, mc)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)
ref = moe_dense_ref(params, x, mc, cap=1000)
for mode in ("baseline", "hyperparallel"):
    impl = make_moe_ep(mesh, EPConfig(mode=mode, capacity_factor=16.0))
    with jax.set_mesh(mesh):
        y = jax.jit(lambda p, x: impl(p, x, mc))(params, x)
        g = jax.jit(jax.grad(lambda p, x: jnp.sum(impl(p, x, mc)**2)))(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4, atol=1e-4)
    gr = jax.grad(lambda p, x: jnp.sum(moe_dense_ref(p, x, mc, cap=1000)**2))(params, x)
    for k in g:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gr[k]),
                                   rtol=1e-3, atol=1e-3)
print("EP_SUBPROCESS_OK")

# --- Pallas fused kernels inside the EP shard (production TPU path) ------
impl_pl = make_moe_ep(mesh, EPConfig(mode="hyperparallel",
                                     capacity_factor=16.0, use_pallas=True))
with jax.set_mesh(mesh):
    y_pl = jax.jit(lambda p, x: impl_pl(p, x, mc))(params, x)
np.testing.assert_allclose(np.asarray(y_pl), np.asarray(ref),
                           rtol=1e-4, atol=1e-4)
print("PALLAS_EP_OK")

# --- flash-decoding equivalence on a seq-sharded cache -------------------
from repro.parallel.flash_decode import make_flash_decode
B, S, H, K, hd = 4, 32, 4, 2, 16
ks = jax.random.split(jax.random.PRNGKey(7), 5)
q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
kc = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
vc = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
nk = jax.random.normal(ks[3], (B, 1, K, hd), jnp.float32)
nv = jax.random.normal(ks[4], (B, 1, K, hd), jnp.float32)
clen = 17
from repro.models.layers import decode_attention
kc_ref = kc.at[:, clen].set(nk[:, 0])
vc_ref = vc.at[:, clen].set(nv[:, 0])
want = decode_attention(q, kc_ref, vc_ref, jnp.int32(clen + 1))
fd = make_flash_decode(mesh, "model")
with jax.set_mesh(mesh):
    o, kc2, vc2 = jax.jit(lambda *a: fd(*a))(q, kc, vc, nk, nv, clen)
np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(kc2), np.asarray(kc_ref), rtol=1e-6, atol=1e-6)
print("FLASH_DECODE_OK")
"""


def test_ep_modes_multidevice_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _EP_SUBPROCESS],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=env, capture_output=True, text=True, timeout=600)
    assert "EP_SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]
    assert "PALLAS_EP_OK" in out.stdout, out.stderr[-2000:]
    assert "FLASH_DECODE_OK" in out.stdout, out.stderr[-2000:]


def test_load_balance_loss_minimized_at_uniform():
    from repro.models.moe import load_balance_loss
    d, E = 16, 8
    mc2 = MoEConfig(n_experts=E, top_k=2, d_expert=8)
    x = jax.random.normal(KEY, (512, d))
    # collapsed router (all tokens to expert 0) vs near-uniform router
    r_collapsed = jnp.zeros((d, E)).at[:, 0].set(5.0)
    r_uniform = jnp.zeros((d, E))
    aux_c, z_c = load_balance_loss(r_collapsed, x, mc2)
    aux_u, z_u = load_balance_loss(r_uniform, x, mc2)
    assert float(aux_c) > float(aux_u)
    assert abs(float(aux_u) - 1.0) < 0.2      # ≈1 at uniform
    assert float(z_c) > float(z_u) >= 0.0


def test_load_balance_loss_masks_padding():
    from repro.models.moe import load_balance_loss
    mc2 = MoEConfig(n_experts=6, top_k=2, d_expert=8, n_padding_experts=2)
    x = jax.random.normal(KEY, (128, 16))
    r = jax.random.normal(jax.random.PRNGKey(3), (16, mc2.e_total))
    aux, _ = load_balance_loss(r, x, mc2)
    assert np.isfinite(float(aux))
