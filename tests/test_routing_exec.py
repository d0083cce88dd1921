"""Imbalanced-plan execution: adversarial orders + the moe_grouped bridge.

The acceptance bar for the RoutingPlan refactor: a schedule compiled from
*real* (imbalanced) router output must execute bit-for-bit equal to the
grouped-MoE reference, forward and backward, under randomized event-driven
order — and the executor's per-rank buffers must be sized strictly from the
schedule, never guessed from same-named peers.
"""

import numpy as np
import pytest

from repro.core import executor as ex
from repro.core.odg import (ScheduleConfig, build_moe_ffn_backward,
                            build_moe_ffn_forward)
from repro.core.routing import (RoutingPlan, hotspot_plan, random_plan,
                                skewed_plan)
from repro.core.scheduler import compile_schedule, validate_schedule
from repro.models.moe import (MoEConfig, bridge_combine, bridge_dispatch,
                              capacity, init_moe, moe_grouped,
                              plan_from_routing, router_topk)


def _plan_grid():
    rng = np.random.default_rng(42)
    return [
        ("skewed", skewed_plan(3, 2, 6, 1.5)),
        ("sparse", random_plan(3, 2, 7, rng, p_zero=0.5)),
        ("hotspot", hotspot_plan(3, 2, 4)),
        ("one_empty_src", RoutingPlan.from_counts(
            [[[0, 0], [0, 0], [0, 0]],
             [[5, 1], [0, 2], [3, 0]],
             [[2, 0], [4, 4], [0, 1]]])),
    ]


def _cfg(plan):
    return ScheduleConfig(ep=plan.ep, e_loc=plan.e_loc, rows=0,
                          d_model=8, d_ff=4, plan=plan)


@pytest.mark.parametrize("name,plan", _plan_grid())
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_imbalanced_forward_adversarial_order(name, plan, seed):
    cfg = _cfg(plan)
    s = compile_schedule(build_moe_ffn_forward(cfg), ratr=True)
    validate_schedule(s)
    x_src, w1, w2 = ex.make_inputs_plan(cfg, 7)
    st = ex.ExecutorState(cfg)
    ex.load_forward_state_plan(cfg, st, x_src, w1, w2)
    ex.execute(s, st, rng=np.random.default_rng(seed))
    ref = ex.reference_forward_plan(cfg, x_src, w1, w2)
    for r in range(cfg.ep):
        if plan.send_rows(r):
            np.testing.assert_array_equal(st.get("y_ret", r),
                                          ref["y_ret"][r])
        if plan.recv_rows(r):
            np.testing.assert_array_equal(st.get("x_recv", r),
                                          ref["x_recv"][r])


@pytest.mark.parametrize("name,plan", _plan_grid())
@pytest.mark.parametrize("seed", [0, 3])
def test_imbalanced_backward_adversarial_order(name, plan, seed):
    cfg = _cfg(plan)
    s = compile_schedule(build_moe_ffn_backward(cfg), ratr=True,
                         gmm_interleave=True)
    validate_schedule(s)
    x_src, w1, w2 = ex.make_inputs_plan(cfg, 11)
    fwd = ex.reference_forward_plan(cfg, x_src, w1, w2)
    rng = np.random.default_rng(seed + 100)
    dy = [rng.standard_normal(fwd["y_ret"][r].shape).astype(np.float32)
          for r in range(cfg.ep)]
    st = ex.ExecutorState(cfg)
    ex.load_backward_state_plan(cfg, st, fwd, w1, w2, dy)
    ex.execute(s, st, rng=np.random.default_rng(seed))
    dx_ref, dw1_ref, dw2_ref = ex.reference_backward_plan(
        cfg, fwd, w1, w2, dy)
    for r in range(cfg.ep):
        if plan.send_rows(r):
            np.testing.assert_array_equal(st.get("dx_ret", r), dx_ref[r])
        if plan.recv_rows(r):
            np.testing.assert_array_equal(st.get("dW1", r), dw1_ref[r])
            np.testing.assert_array_equal(st.get("dW2", r), dw2_ref[r])
        else:
            assert not dw1_ref[r].any() and not dw2_ref[r].any()
    # independent autodiff oracle
    dx_j, dw1_j, dw2_j = ex.reference_backward_plan_jax(
        cfg, x_src, w1, w2, dy)
    for r in range(cfg.ep):
        if plan.send_rows(r):
            np.testing.assert_allclose(dx_ref[r], dx_j[r],
                                       rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dw1_ref, dw1_j, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dw2_ref, dw2_j, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name,plan", _plan_grid())
def test_imbalanced_order_independence(name, plan):
    """Different legal adversarial orders give bit-identical results."""
    cfg = _cfg(plan)
    outs = []
    for seed in range(3):
        s = compile_schedule(build_moe_ffn_forward(cfg),
                             ratr=bool(seed % 2))
        x_src, w1, w2 = ex.make_inputs_plan(cfg, 5)
        st = ex.ExecutorState(cfg)
        ex.load_forward_state_plan(cfg, st, x_src, w1, w2)
        ex.execute(s, st, rng=np.random.default_rng(seed))
        outs.append([st.get("y_ret", r) for r in range(cfg.ep)
                     if plan.send_rows(r)])
    for o in outs[1:]:
        for a, b in zip(outs[0], o):
            np.testing.assert_array_equal(a, b)


def test_buffers_sized_from_rows_map():
    """Regression for the `_rows_hint` peer-guessing bug: with per-rank row
    counts differing, every lazily-created buffer must get exactly the
    extent recorded in the schedule's write set."""
    plan = RoutingPlan.from_counts(
        [[[9, 1], [2, 0]], [[0, 3], [1, 1]]])   # recv: rank0=13, rank1=4
    cfg = _cfg(plan)
    s = compile_schedule(build_moe_ffn_forward(cfg))
    x_src, w1, w2 = ex.make_inputs_plan(cfg, 0)
    st = ex.ExecutorState(cfg)
    ex.load_forward_state_plan(cfg, st, x_src, w1, w2)
    ex.execute(s, st, rng=np.random.default_rng(1))
    assert st.get("x_recv", 0).shape[0] == 13
    assert st.get("x_recv", 1).shape[0] == 4
    for (tname, rank), rows in st.rows_map.items():
        if (tname, rank) in st.buffers and tname != "dW1":
            assert st.buffers[(tname, rank)].shape[0] == rows, (tname, rank)


# ---------------------------------------------------------------------------
# The bridge: real router output → compiled schedule ≡ moe_grouped.
# ---------------------------------------------------------------------------

def _routed_case(seed=0, ep=4, t_loc=8, d=16, f=8, top_k=2):
    import jax
    mc = MoEConfig(n_experts=ep * 2, top_k=top_k, d_expert=f)
    T = ep * t_loc
    params = init_moe(jax.random.PRNGKey(seed), d, mc)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                     (1, T, d)), dtype=np.float32)
    top_p, top_i = router_topk(params["router"], x.reshape(T, d), mc)
    return mc, params, x, np.asarray(top_p), np.asarray(top_i)


@pytest.mark.parametrize("seed", [0, 1])
def test_bridge_schedule_matches_moe_grouped(seed):
    """Compile from real (imbalanced) router output; execute under a random
    event-driven order; combine; compare against the grouped reference."""
    ep, t_loc, d, f = 4, 8, 16, 8
    mc, params, x, top_p, top_i = _routed_case(seed, ep, t_loc, d, f)
    T = ep * t_loc
    C = capacity(T, mc)
    bridge = plan_from_routing(top_i, mc, ep, capacity=C)
    plan = bridge.plan
    assert not plan.is_balanced()          # real routing is skewed

    cfg = ScheduleConfig(ep=ep, e_loc=mc.e_total // ep, rows=0,
                         d_model=d, d_ff=f, plan=plan)
    s = compile_schedule(build_moe_ffn_forward(cfg), ratr=True)
    validate_schedule(s)

    x_src = bridge_dispatch(bridge, x.reshape(ep, t_loc, d))
    w1 = np.asarray(params["w_in"]).reshape(ep, cfg.e_loc, d, 2 * f)
    w2 = np.asarray(params["w_down"]).reshape(ep, cfg.e_loc, f, d)
    st = ex.ExecutorState(cfg)
    ex.load_forward_state_plan(cfg, st, x_src, w1, w2)
    ex.execute(s, st, rng=np.random.default_rng(seed))

    y_ret = [st.get("y_ret", r) if plan.send_rows(r)
             else np.zeros((0, d), np.float32) for r in range(ep)]
    y = bridge_combine(bridge, y_ret, top_p)

    want = np.asarray(moe_grouped(params, x, mc, cap=C)).reshape(
        ep, t_loc, d)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)

    # and bit-for-bit against the ragged numpy grouped reference
    ref = ex.reference_forward_plan(cfg, x_src, w1, w2)
    for r in range(ep):
        if plan.send_rows(r):
            np.testing.assert_array_equal(st.get("y_ret", r),
                                          ref["y_ret"][r])


def test_bridge_backward_matches_moe_grouped_vjp():
    """Executor weight grads on a bridged plan == jax.vjp(moe_grouped)."""
    import jax
    import jax.numpy as jnp
    ep, t_loc, d, f = 4, 8, 16, 8
    mc, params, x, top_p, top_i = _routed_case(3, ep, t_loc, d, f)
    T = ep * t_loc
    C = capacity(T, mc)
    bridge = plan_from_routing(top_i, mc, ep, capacity=C)
    plan = bridge.plan
    cfg = ScheduleConfig(ep=ep, e_loc=mc.e_total // ep, rows=0,
                         d_model=d, d_ff=f, plan=plan)

    x_src = bridge_dispatch(bridge, x.reshape(ep, t_loc, d))
    w1 = np.asarray(params["w_in"]).reshape(ep, cfg.e_loc, d, 2 * f)
    w2 = np.asarray(params["w_down"]).reshape(ep, cfg.e_loc, f, d)
    fwd = ex.reference_forward_plan(cfg, x_src, w1, w2)

    # Token-space cotangent; chain through the (fixed) combine weights to
    # get the per-row cotangent entering the schedulable fragment.
    g_y = np.asarray(jax.random.normal(jax.random.PRNGKey(9),
                                       (ep, t_loc, d)), dtype=np.float32)
    dy = [np.zeros((plan.send_rows(s), d), np.float32) for s in range(ep)]
    for s_rank in range(ep):
        for t in range(t_loc):
            for j in range(mc.top_k):
                row = bridge.send_row[s_rank, t, j]
                if row >= 0:
                    dy[s_rank][row] += top_p[s_rank * t_loc + t, j] \
                        * g_y[s_rank, t]

    sb = compile_schedule(build_moe_ffn_backward(cfg), ratr=True,
                          gmm_interleave=True)
    st = ex.ExecutorState(cfg)
    ex.load_backward_state_plan(cfg, st, fwd, w1, w2, dy)
    ex.execute(sb, st, rng=np.random.default_rng(2))

    def f_params(w_in, w_down):
        return moe_grouped({**params, "w_in": w_in, "w_down": w_down},
                           jnp.asarray(x), mc, cap=C)

    _, vjp = jax.vjp(f_params, params["w_in"], params["w_down"])
    dw_in, dw_down = vjp(jnp.asarray(g_y.reshape(1, T, d)))
    dw_in = np.asarray(dw_in).reshape(ep, cfg.e_loc, d, 2 * f)
    dw_down = np.asarray(dw_down).reshape(ep, cfg.e_loc, f, d)
    for r in range(ep):
        got1 = (st.get("dW1", r) if plan.recv_rows(r)
                else np.zeros_like(dw_in[r]))
        got2 = (st.get("dW2", r) if plan.recv_rows(r)
                else np.zeros_like(dw_down[r]))
        np.testing.assert_allclose(got1, dw_in[r], rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(got2, dw_down[r], rtol=1e-3, atol=1e-4)


def test_bridge_dropless_counts():
    """Dropless bridge conserves every (token, choice) pair."""
    mc, params, x, top_p, top_i = _routed_case(5)
    bridge = plan_from_routing(top_i, mc, 4, capacity=None)
    assert bridge.plan.total_rows == top_i.size
    assert (bridge.send_row >= 0).all()


def test_ep_pair_capacity_plan():
    """parallel.ep.plan_from_dispatch mirrors _route_local's slots."""
    from repro.parallel.ep import plan_from_dispatch
    mc, params, x, top_p, top_i = _routed_case(7)
    ep, t_loc = 4, 8
    ti = top_i.reshape(ep, t_loc, mc.top_k)
    C = 3
    plan = plan_from_dispatch(ti, mc, ep, C)
    for s_rank in range(ep):
        hist = np.bincount(ti[s_rank].reshape(-1), minlength=mc.e_total)
        want = np.minimum(hist, C).reshape(ep, mc.e_total // ep)
        got = np.array([[plan.count(s_rank, d_, e_)
                         for e_ in range(mc.e_total // ep)]
                        for d_ in range(ep)])
        np.testing.assert_array_equal(got, want)
