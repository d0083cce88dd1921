"""Dropless data-dependent training step: plan bucketing, SSC cache reuse,
loss parity against the fixed-capacity path, and the ragged EP ring.

The dropless path (``repro.launch.dropless``) compiles a schedule from each
batch's actual router output and trains *through* it (custom-vjp executor
callbacks). These tests pin its three contracts: (1) bucketed plan keys make
jittered routing cache-hit without changing results, (2) ``train_step`` under
``DroplessConfig`` matches the fixed-capacity step bit-for-bit when capacity
drops nothing, (3) the plan-sized EP ring moves/skips exactly the rows the
plan names.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.ssc import SSCCache
from repro.core.odg import ScheduleConfig
from repro.models.moe import (MoEConfig, bucket_counts, init_moe,
                              moe_grouped, plan_from_routing)
from repro.launch.dropless import DroplessConfig, DroplessMoE

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Bucketing semantics.
# ---------------------------------------------------------------------------

def test_bucket_counts_quantizes_up_preserving_zeros():
    c = np.array([[[0, 1], [4, 5]], [[8, 9], [0, 16]]])
    b = bucket_counts(c, 4)
    np.testing.assert_array_equal(
        b, [[[0, 4], [4, 8]], [[8, 12], [0, 16]]])
    np.testing.assert_array_equal(bucket_counts(c, 1), c)


def test_bucketed_plan_rows_cover_exact_plan():
    mc = MoEConfig(n_experts=8, top_k=2, d_expert=8)
    rng = np.random.default_rng(0)
    ti = rng.integers(0, 8, size=(64, 2))
    exact = plan_from_routing(ti, mc, 4, capacity=None)
    bucketed = plan_from_routing(ti, mc, 4, capacity=None, bucket_rows=8)
    ce = np.asarray(exact.plan.counts)
    cb = np.asarray(bucketed.plan.counts)
    assert (cb >= ce).all() and ((cb == 0) == (ce == 0)).all()
    assert (bucketed.send_row >= 0).all()          # dropless: nothing dropped
    assert cb.sum() % 8 == 0 or (cb == 0).any()


# ---------------------------------------------------------------------------
# Cache hit/miss under repeated vs jittered routing.
# ---------------------------------------------------------------------------

def _fetch(cache, plan, direction="forward"):
    cfg = ScheduleConfig(ep=plan.ep, e_loc=plan.e_loc, rows=0, d_model=16,
                         d_ff=8, plan=plan)
    cache.get_or_compile(cfg, direction, pipeline=["ratr"])


def test_cache_hits_repeated_and_bucketed_jitter():
    mc = MoEConfig(n_experts=4, top_k=1, d_expert=8)
    # base: each global expert gets 4 of rank 0's tokens and 4 of rank 1's;
    # jittered: one token moved between experts (counts 3/5 — same bucket-8
    # key as 4/4, different exact key).
    base = np.repeat(np.arange(4), 4)[:, None]
    base = np.concatenate([base, base], axis=0)          # [32, 1], ep=2
    jit_ = base.copy()
    jit_[0, 0] = 1

    exact = SSCCache(max_entries=8)
    for ti in (base, base, jit_):
        _fetch(exact, plan_from_routing(ti, mc, 2, capacity=None).plan)
    assert (exact.hits, exact.misses) == (1, 2)   # repeat hits, jitter misses

    bucketed = SSCCache(max_entries=8)
    for ti in (base, base, jit_):
        _fetch(bucketed, plan_from_routing(ti, mc, 2, capacity=None,
                                           bucket_rows=8).plan)
    assert (bucketed.hits, bucketed.misses) == (2, 1)    # jitter hits too

    stats = bucketed.step_stats()
    assert stats["hits"] == 2 and stats["misses"] == 1
    assert bucketed.step_stats() == {"hits": 0, "misses": 0,
                                     "evictions": 0, "entries": 1,
                                     "pad_ratio": 1.0}


# ---------------------------------------------------------------------------
# Bucketed-key collisions compute correct results for *both* colliding
# routings (padding rows provably inert).
# ---------------------------------------------------------------------------

def test_bucketed_key_collision_correctness():
    mc = MoEConfig(n_experts=4, top_k=2, d_expert=8, capacity_factor=8.0)
    d = 16
    params = init_moe(KEY, d, mc)
    x1 = jax.random.normal(jax.random.PRNGKey(1), (1, 32, d), jnp.float32)
    x2 = jax.random.normal(jax.random.PRNGKey(2), (1, 32, d), jnp.float32)
    cache = SSCCache(max_entries=8)
    dm = DroplessMoE(DroplessConfig(ep=2, bucket_rows=64), cache=cache)

    from repro.models.moe import router_topk
    tis = [np.asarray(router_topk(params["router"],
                                  np.asarray(x).reshape(32, d), mc)[1])
           for x in (x1, x2)]
    p1, p2 = [plan_from_routing(ti, mc, 2, capacity=None,
                                bucket_rows=64).plan for ti in tis]
    assert not np.array_equal(*[np.asarray(plan_from_routing(
        ti, mc, 2, capacity=None).plan.counts) for ti in tis])
    assert p1.counts == p2.counts          # distinct routings, one cache key

    y1 = dm.impl(params, x1, mc)
    assert cache.misses == 1 and cache.hits == 0
    y2 = dm.impl(params, x2, mc)
    assert cache.misses == 1 and cache.hits == 1   # collision reused the SSC
    for x, y in ((x1, y1), (x2, y2)):
        want = moe_grouped(params, x, mc, cap=10_000)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# The dropless fragment vs the grouped reference (fwd + grads).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket", [1, 8])
def test_dropless_impl_matches_grouped(bucket):
    mc = MoEConfig(n_experts=8, top_k=2, d_expert=8, capacity_factor=8.0)
    d = 16
    params = init_moe(KEY, d, mc)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d), jnp.float32)
    dm = DroplessMoE(DroplessConfig(ep=4, bucket_rows=bucket),
                     cache=SSCCache(max_entries=8))
    want = moe_grouped(params, x, mc, cap=10_000)
    y = jax.jit(lambda p, x: dm.impl(p, x, mc))(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    g1 = jax.grad(lambda p: jnp.sum(dm.impl(p, x, mc) ** 2))(params)
    g2 = jax.grad(lambda p: jnp.sum(
        moe_grouped(p, x, mc, cap=10_000) ** 2))(params)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   rtol=1e-3, atol=1e-4, err_msg=k)


# ---------------------------------------------------------------------------
# End-to-end: train_step through compiled schedules == fixed-capacity step.
# ---------------------------------------------------------------------------

def test_train_step_loss_parity_and_cache_reuse():
    from repro.configs import get_smoke_config
    from repro.launch import steps as St
    from repro.launch.mesh import make_test_mesh
    from repro.optim import adamw
    from repro.models import model as M

    cfg = get_smoke_config("granite-moe-3b-a800m")
    cfg = dataclasses.replace(
        cfg, n_layers=1, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    mesh = make_test_mesh(data=1, model=1)
    params = M.init_params(cfg, KEY)
    opt_state = adamw.init_opt_state(params)
    batch = {"tokens": jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % 50,
             "labels": jnp.ones((2, 16), jnp.int32)}
    oc = adamw.OptConfig(lr=1e-3, warmup_steps=2, total_steps=10)

    fixed = St.make_steps(cfg, mesh, opt=oc, mode="zero1")
    drop = St.make_steps(cfg, mesh, opt=oc, mode="zero1",
                         dropless=DroplessConfig(ep=2, bucket_rows=4))
    assert drop.dropless is not None and fixed.dropless is None
    with jax.set_mesh(mesh):
        p1, _, m1 = fixed.train_step(params, opt_state, batch)
        p2, o2, m2 = drop.train_step(params, opt_state, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-3, atol=1e-5)
        # first step compiled fwd+bwd; identical routing next step is
        # fully cache-served and says so in its metrics
        assert m2["ssc_misses"] == 2 and m2["ssc_entries"] == 2
        _, _, m3 = drop.train_step(p2, o2, batch)
        assert m3["ssc_misses"] == 0 and m3["ssc_hits"] >= 2


# ---------------------------------------------------------------------------
# Ragged EP ring: plan-sized chunk caps.
# ---------------------------------------------------------------------------

def test_ring_chunk_caps():
    from repro.core.routing import RoutingPlan
    from repro.parallel.ep import ring_chunk_caps
    plan = RoutingPlan.from_counts(
        [[[3, 0], [0, 0], [1, 2]],
         [[0, 1], [2, 0], [0, 0]],
         [[4, 0], [0, 0], [0, 5]]])
    caps = ring_chunk_caps(plan, 3)
    c = np.asarray(plan.counts)
    for k in range(3):
        assert caps[k] == max(c[s, (s + k) % 3].max() for s in range(3))
    # purely rank-local routing → every nonlocal ring step is all-padding
    diag = np.zeros((3, 3, 2), np.int64)
    for s in range(3):
        diag[s, s] = (7, 3)
    assert ring_chunk_caps(RoutingPlan.from_counts(diag), 3) == (7, 0, 0)
    with pytest.raises(ValueError):
        ring_chunk_caps(plan, 4)


_RAGGED_EP_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.parallel.ep import (EPConfig, make_moe_ep, plan_from_dispatch,
                               _pair_capacity, ring_chunk_caps)
from repro.models.moe import MoEConfig, init_moe, moe_dense_ref, router_topk

mesh = make_test_mesh(data=1, model=4)
ep = 4
mc = MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=8.0)
params = init_moe(jax.random.PRNGKey(0), 32, mc)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)
ref = moe_dense_ref(params, x, mc, cap=1000)

# replicate per-rank routing host-side (x is sequence-sharded over `model`)
B, S, d = x.shape
t_loc = B * (S // ep)
x_sh = np.transpose(np.asarray(x).reshape(B, ep, S // ep, d),
                    (1, 0, 2, 3)).reshape(ep, t_loc, d)
top_i = np.stack([np.asarray(router_topk(params["router"],
                                         jnp.asarray(x_sh[r]), mc)[1])
                  for r in range(ep)])
C = _pair_capacity(t_loc, mc, ep, 16.0)
plan = plan_from_dispatch(top_i, mc, ep, C)

full = make_moe_ep(mesh, EPConfig(capacity_factor=16.0))
ragged = make_moe_ep(mesh, EPConfig(capacity_factor=16.0), plan=plan)
# bucketed plan: caps only ever round up, so results must be identical
ragged_b = make_moe_ep(mesh, EPConfig(capacity_factor=16.0), plan=plan,
                       bucket="geometric:8")
with jax.set_mesh(mesh):
    y_full = jax.jit(lambda p, x: full(p, x, mc))(params, x)
    y_ragged = jax.jit(lambda p, x: ragged(p, x, mc))(params, x)
    y_ragged_b = jax.jit(lambda p, x: ragged_b(p, x, mc))(params, x)
    g = jax.jit(jax.grad(lambda p, x: jnp.sum(ragged(p, x, mc) ** 2)))(
        params, x)
    g_ref = jax.grad(lambda p, x: jnp.sum(
        moe_dense_ref(p, x, mc, cap=1000) ** 2))(params, x)
np.testing.assert_allclose(np.asarray(y_full), np.asarray(ref),
                           rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(y_ragged), np.asarray(y_full),
                           rtol=1e-6, atol=1e-6)
np.testing.assert_allclose(np.asarray(y_ragged_b), np.asarray(y_full),
                           rtol=1e-6, atol=1e-6)
print("RAGGED_BUCKET_OK")
for k in g:
    np.testing.assert_allclose(np.asarray(g[k]), np.asarray(g_ref[k]),
                               rtol=1e-3, atol=1e-3)
print("RAGGED_EP_OK")

# purely rank-local routing: every nonlocal ring step must be skipped
W = np.zeros((32, 8), np.float32)
for gexp in range(8):
    W[gexp, gexp] = 10.0
params_diag = dict(params, router=jnp.asarray(W))
xd = np.zeros((B, S, 32), np.float32)
rng = np.random.default_rng(0)
for s in range(S):
    r = s // (S // ep)
    xd[:, s, 2 * r] = 1.0 + 0.1 * rng.standard_normal(B)
    xd[:, s, 2 * r + 1] = 0.9
    xd[:, s, 8:] = 0.05 * rng.standard_normal((B, 24))
xd = jnp.asarray(xd)
xd_sh = np.transpose(np.asarray(xd).reshape(B, ep, S // ep, 32),
                     (1, 0, 2, 3)).reshape(ep, t_loc, 32)
top_i_d = np.stack([np.asarray(router_topk(params_diag["router"],
                                           jnp.asarray(xd_sh[r]), mc)[1])
                    for r in range(ep)])
plan_d = plan_from_dispatch(top_i_d, mc, ep, C)
assert ring_chunk_caps(plan_d, ep)[1:] == (0,) * (ep - 1)
ragged_d = make_moe_ep(mesh, EPConfig(capacity_factor=16.0), plan=plan_d)
with jax.set_mesh(mesh):
    y_f = jax.jit(lambda p, x: full(p, x, mc))(params_diag, xd)
    y_r = jax.jit(lambda p, x: ragged_d(p, x, mc))(params_diag, xd)
    hlo = jax.jit(lambda p, x: ragged_d(p, x, mc)).lower(
        params_diag, xd).compile().as_text()
np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_f),
                           rtol=1e-6, atol=1e-6)
assert "collective-permute" not in hlo, "all-padding steps must be skipped"
print("RAGGED_SKIP_OK")
"""


def test_ragged_ep_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _RAGGED_EP_SUBPROCESS],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=env, capture_output=True, text=True, timeout=600)
    assert "RAGGED_EP_OK" in out.stdout, out.stderr[-2000:]
    assert "RAGGED_BUCKET_OK" in out.stdout, out.stderr[-2000:]
    assert "RAGGED_SKIP_OK" in out.stdout, out.stderr[-2000:]
