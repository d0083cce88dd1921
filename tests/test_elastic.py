"""Elastic plan remapping: invariants, executor parity, cache re-keying,
and the observed-time straggler feedback loop.

``core/elastic.py`` claims a remapped plan is *the* plan a shrunken mesh
would have built natively — these tests pin that cell-for-cell and
bit-for-bit (executor outputs), property-test the invariants over the full
plan-generator zoo × random dead-rank sets, and check the two integration
seams: ``SSCCache.rekey_for_mesh`` (re-key, never flush) and
``CostModel(rank_bias=)`` → ``autoselect`` (a measured-slow rank becomes
the compile-time critical rank).
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from _proptest import given, settings, st
from repro.core import autoselect
from repro.core import executor as ex
from repro.core.buckets import BucketSpec
from repro.core.costmodel import CostModel
from repro.core.elastic import (BIAS_CEIL, BIAS_FLOOR, check_remap,
                                observed_cost_model, rank_bias_from_times,
                                rechunk_expert_array, remap_plan,
                                surviving_ranks)
from repro.core.odg import (CTQ, ScheduleConfig, build_moe_ffn_backward,
                            build_moe_ffn_forward)
from repro.core.routing import (RoutingPlan, balanced_plan, hotspot_plan,
                                node_limited_plan, random_plan, skewed_plan)
from repro.core.scheduler import compile_schedule
from repro.core.ssc import SSCCache
from repro.core.tasks import TaskDescriptor
from repro.ft.runner import ElasticContext, FTConfig, RunState, train_loop


# ---------------------------------------------------------------------------
# remap_plan properties over the plan-generator zoo × random dead sets.
# e_total = 4 * 3 = 12 divides every survivor count 1..4, so any dead set
# is legal. A hotspot plan of 4 rows a cell holds backgrounds 0 and 1 but
# not 2 (``hotspot_plan`` refuses it); that one takes 5 rows.
# ---------------------------------------------------------------------------

def _make_plan(kind: str, seed: int) -> RoutingPlan:
    if kind == "skewed":
        return skewed_plan(4, 3, 8 + seed % 5, alpha=0.5 + (seed % 4) * 0.5)
    if kind == "hotspot":
        rows, background = 4 + seed % 4, seed % 3
        if background == 2:
            rows = max(rows, 5)
        return hotspot_plan(4, 3, rows, background=background)
    if kind == "node_limited":
        return node_limited_plan(4, 3, 4 + seed % 4, node_size=2,
                                 m_nodes=1 + seed % 2)
    return random_plan(4, 3, 12, np.random.default_rng(seed), p_zero=0.3)


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(["skewed", "hotspot", "node_limited", "random"]),
       st.integers(0, 10 ** 6),
       st.lists(st.integers(0, 3), min_size=1, max_size=3, unique=True))
def test_remap_invariants_property(kind, seed, dead):
    plan = _make_plan(kind, seed)
    survivors = surviving_ranks(4, dead)
    new = remap_plan(plan, dead_ranks=dead)
    assert new.ep == len(survivors)
    assert new.ep * new.e_loc == 12          # experts conserved
    report = check_remap(plan, new, survivors)
    assert report["ok"], report
    # Idempotence: remapping with nothing dead is the identity, and
    # re-chunking onto the same mesh size changes nothing.
    assert remap_plan(new, dead_ranks=[]).counts == new.counts
    assert remap_plan(new, new_ep=new.ep).counts == new.counts
    # Total rows equal the survivors' send rows — no cell addresses a
    # dead rank.
    assert new.total_rows == sum(plan.send_rows(r) for r in survivors)


def test_remap_argument_validation():
    plan = balanced_plan(4, 3, 2)
    with pytest.raises(ValueError, match="exactly one"):
        remap_plan(plan)
    with pytest.raises(ValueError, match="exactly one"):
        remap_plan(plan, dead_ranks=[0], new_ep=2)
    with pytest.raises(ValueError, match="outside mesh"):
        remap_plan(plan, dead_ranks=[4])
    with pytest.raises(ValueError, match="nothing to remap"):
        remap_plan(plan, dead_ranks=[0, 1, 2, 3])
    # 12 experts cannot land on 5 ranks.
    with pytest.raises(ValueError, match="valid mesh sizes"):
        remap_plan(plan, new_ep=5)


def test_remap_growth_roundtrip():
    """Shrink then grow back: the original cells return (fresh sources
    join empty, so the dead rank's rows are gone — but the survivors'
    cells land back in their original (src, dst, expert) slots)."""
    plan = skewed_plan(4, 3, 6, alpha=1.0)
    small = remap_plan(plan, dead_ranks=[3])
    back = remap_plan(small, new_ep=4)
    c_old = np.asarray(plan.counts)[:3]
    c_back = np.asarray(back.counts)
    np.testing.assert_array_equal(c_back[:3], c_old)
    assert c_back[3].sum() == 0


def test_rechunk_expert_array_forms():
    w = np.arange(12 * 5 * 7, dtype=np.float32).reshape(12, 5, 7)
    per_rank = w.reshape(4, 3, 5, 7)
    out_a = rechunk_expert_array(w, 2)
    # ep=4 divides new_ep=2, so the per-rank form needs e_total= to
    # disambiguate; new_ep=3 resolves on its own.
    out_b = rechunk_expert_array(per_rank, 2, e_total=12)
    assert out_a.shape == out_b.shape == (2, 6, 5, 7)
    np.testing.assert_array_equal(out_a, out_b)
    np.testing.assert_array_equal(out_a.reshape(12, 5, 7), w)
    np.testing.assert_array_equal(rechunk_expert_array(per_rank, 3),
                                  rechunk_expert_array(w, 3))
    with pytest.raises(ValueError, match="re-chunk"):
        rechunk_expert_array(w, 7)


# ---------------------------------------------------------------------------
# Executor parity: the remapped plan executes bit-for-bit like the old
# mesh (surviving rows) and like a fresh native small-mesh compile.
# ---------------------------------------------------------------------------

def _small_cfg(plan):
    return ScheduleConfig(ep=plan.ep, e_loc=plan.e_loc, rows=0,
                          d_model=8, d_ff=4, plan=plan)


@pytest.mark.parametrize("kind,dead", [
    ("skewed", [1]), ("hotspot", [0]), ("random", [0, 2]),
    ("node_limited", [3]),
])
def test_remap_executor_forward_backward_parity(kind, dead):
    plan = _make_plan(kind, seed=7)
    survivors = surviving_ranks(4, dead)
    new = remap_plan(plan, dead_ranks=dead)

    old_cfg = _small_cfg(plan)
    new_cfg = _small_cfg(new)
    x_src, w1, w2 = ex.make_inputs_plan(old_cfg, 3)
    # Survivors keep their send buffers verbatim; expert weights re-chunk
    # by pure reshape (global expert order preserved).
    x_small = [x_src[r] for r in survivors]
    w1_small = rechunk_expert_array(w1, new.ep, e_total=12)
    w2_small = rechunk_expert_array(w2, new.ep, e_total=12)

    fwd_old = ex.reference_forward_plan(old_cfg, x_src, w1, w2)
    s = compile_schedule(build_moe_ffn_forward(new_cfg), ratr=True)
    st_f = ex.ExecutorState(new_cfg)
    ex.load_forward_state_plan(new_cfg, st_f, x_small, w1_small, w2_small)
    ex.execute(s, st_f, rng=np.random.default_rng(0))
    for i, r in enumerate(survivors):
        if new.send_rows(i):
            # Bit-identical to the old mesh's per-source combined output.
            np.testing.assert_array_equal(st_f.get("y_ret", i),
                                          fwd_old["y_ret"][r])

    # Backward through the real executor vs the fresh small-mesh reference.
    fwd_small = ex.reference_forward_plan(new_cfg, x_small, w1_small,
                                          w2_small)
    rng = np.random.default_rng(11)
    dy = [rng.standard_normal(fwd_small["y_ret"][i].shape).astype(np.float32)
          for i in range(new.ep)]
    sb = compile_schedule(build_moe_ffn_backward(new_cfg), ratr=True,
                          gmm_interleave=True)
    st_b = ex.ExecutorState(new_cfg)
    ex.load_backward_state_plan(new_cfg, st_b, fwd_small, w1_small,
                                w2_small, dy)
    ex.execute(sb, st_b, rng=np.random.default_rng(1))
    dx_ref, dw1_ref, dw2_ref = ex.reference_backward_plan(
        new_cfg, fwd_small, w1_small, w2_small, dy)
    for i in range(new.ep):
        if new.send_rows(i):
            np.testing.assert_array_equal(st_b.get("dx_ret", i), dx_ref[i])
        if new.recv_rows(i):
            np.testing.assert_array_equal(st_b.get("dW1", i), dw1_ref[i])
            np.testing.assert_array_equal(st_b.get("dW2", i), dw2_ref[i])


# ---------------------------------------------------------------------------
# BucketSpec mesh tagging.
# ---------------------------------------------------------------------------

def test_bucketspec_ep_tagging():
    b = BucketSpec.linear(16)
    assert b.key() == ("linear", 16)          # untagged = pre-tag bytes
    t = b.for_mesh(4)
    assert t.key() == ("linear", 16, ("ep", 4))
    assert str(t) == "linear:16@ep4"
    assert BucketSpec.parse("linear:16@ep4") == t
    assert BucketSpec.from_any(t.key()) == t
    assert BucketSpec.from_any(t.spec()) == t
    assert t.for_mesh(None) == b
    assert t.for_mesh(4) is t
    g = BucketSpec.geometric(8, 1.5).for_mesh(2)
    assert BucketSpec.from_any(g.spec()) == g
    assert str(BucketSpec.parse(str(g))) == str(g)
    with pytest.raises(ValueError, match="@epN"):
        BucketSpec.parse("linear:16@4")
    with pytest.raises(ValueError, match="ep tag"):
        BucketSpec.linear(4).for_mesh(0)
    # Quantization is tag-independent.
    c = np.array([1, 7, 16, 17])
    np.testing.assert_array_equal(b.quantize(c), t.quantize(c))


# ---------------------------------------------------------------------------
# SSCCache: ep-tagged keys and rekey_for_mesh.
# ---------------------------------------------------------------------------

def _sched_cfg(plan, bucket=None):
    return ScheduleConfig(ep=plan.ep, e_loc=plan.e_loc, rows=0, d_model=8,
                          d_ff=4, plan=plan, bucket=bucket)


def test_cache_key_tags_bucket_with_mesh():
    plan4 = balanced_plan(4, 3, 4)
    k = SSCCache.key(_sched_cfg(plan4, bucket=16), "forward",
                     pipeline=["ratr"])
    assert k[8] == ("linear", 16, ("ep", 4))
    # Bucket-less keys are unchanged.
    k0 = SSCCache.key(_sched_cfg(plan4), "forward", pipeline=["ratr"])
    assert k0[8] is None


def test_rekey_for_mesh_rekeys_not_flushes():
    cache = SSCCache(max_entries=8)
    plan4 = skewed_plan(4, 3, 4, alpha=1.0)
    plan3 = remap_plan(plan4, dead_ranks=[3])
    cache.get_or_compile(_sched_cfg(plan4, 16), "forward", pipeline=["ratr"])
    cache.get_or_compile(_sched_cfg(plan3, 16), "forward", pipeline=["ratr"])
    assert cache.info()["by_ep"] == {3: 1, 4: 1}

    out = cache.rekey_for_mesh(3)
    assert out == {"entries": 2, "active": 1, "stale": 1, "retagged": 0}
    info = cache.info()
    assert info["rekeyed"] == 1 and info["active_ep"] == 3
    assert info["evictions"] == 0 and info["entries"] == 2
    # Post-rekey, both mesh populations still hit.
    cache.get_or_compile(_sched_cfg(plan3, 16), "forward", pipeline=["ratr"])
    cache.get_or_compile(_sched_cfg(plan4, 16), "forward", pipeline=["ratr"])
    assert cache.hits == 2 and cache.misses == 2
    # Stale-mesh entries bear LRU pressure: with room for one more entry,
    # inserting two fresh ep=3 plans evicts the boosted-last ep=4 entry
    # only after the cache is truly full.
    small = SSCCache(max_entries=2)
    small.get_or_compile(_sched_cfg(plan4, 16), "forward", pipeline=["ratr"])
    small.get_or_compile(_sched_cfg(plan3, 16), "forward", pipeline=["ratr"])
    small.rekey_for_mesh(3)
    plan3b = remap_plan(skewed_plan(4, 3, 5, alpha=1.0), dead_ranks=[3])
    small.get_or_compile(_sched_cfg(plan3b, 16), "forward",
                         pipeline=["ratr"])
    assert small.evictions == 1
    assert small.info()["by_ep"] == {3: 2}   # the ep=4 entry was the victim


def test_rekey_retags_legacy_untagged_keys():
    cache = SSCCache(max_entries=8)
    plan4 = balanced_plan(4, 3, 4)
    k = SSCCache.key(_sched_cfg(plan4, 16), "forward", pipeline=["ratr"])
    legacy = k[:8] + (("linear", 16),) + k[9:]    # pre-tag key format
    cache._insert(legacy, b"blob", fragments=1)
    out = cache.rekey_for_mesh(4)
    assert out["retagged"] == 1
    assert list(cache._cache) == [k]              # now the canonical key


# ---------------------------------------------------------------------------
# Observed-time feedback: rank_bias → critical rank → autoselect.
# ---------------------------------------------------------------------------

def test_rank_bias_normalization_and_clipping():
    bias = rank_bias_from_times([100.0, 100.0, 100.0])
    assert bias == (1.0, 1.0, 1.0)
    bias = rank_bias_from_times([100.0, 100.0, 400.0])
    assert abs(sum(bias) / 3 - 1.0) < 0.5         # mean-normalized pre-clip
    assert max(bias) == bias[2]
    huge = rank_bias_from_times([1.0] * 9 + [1e9])
    assert max(huge) == BIAS_CEIL and min(huge) == BIAS_FLOOR
    assert rank_bias_from_times([0.0, 0.0]) == (1.0, 1.0)
    with pytest.raises(ValueError, match="empty"):
        rank_bias_from_times([])
    with pytest.raises(ValueError, match="negative"):
        rank_bias_from_times([1.0, -1.0])


def test_cost_model_bias_prices_tasks_and_stays_hashable():
    cm = observed_cost_model([300.0, 100.0, 100.0, 100.0])
    base = CostModel(l2=False)
    td = TaskDescriptor(task_type="GMM", queue_type=CTQ, rank=0, flops=1e9)
    td1 = dataclasses.replace(td, rank=1)
    assert cm.task_us(td) / cm.task_us(td1) == pytest.approx(
        cm.rank_bias[0] / cm.rank_bias[1])
    # Unbiased ranks (and out-of-range ranks) price exactly as the base.
    assert cm._task_us_unbiased(td) == base.task_us(td)
    assert cm.task_us(dataclasses.replace(td, rank=7)) == base.task_us(td)
    assert observed_cost_model(None, base) is base
    hash(cm)                                      # lru_cache memo key


def test_slow_rank_becomes_critical_and_autoselect_reacts():
    plan = balanced_plan(4, 3, 16)
    cfg = ScheduleConfig(ep=4, e_loc=3, rows=16, d_model=64, d_ff=128,
                         plan=plan)
    view = autoselect.cube_taskset(plan, cfg, "forward")
    # Unbiased: balanced plan, no straggler, no crit pipeline priced.
    ratio0, _ = CostModel(l2=False).critical_rank(view)
    assert ratio0 == pytest.approx(1.0)
    # 3× slow rank 2: it becomes the compile-time critical rank and the
    # selector picks a pipeline containing critical_rank_first.
    cm = observed_cost_model([100.0, 100.0, 300.0, 100.0])
    ratio, crit = cm.critical_rank(view)
    assert crit == 2 and ratio > 1.05
    choice = autoselect.select(plan, cfg, cm)
    names = [n for n, _ in choice.pipeline.key()]
    assert "critical_rank_first" in names, choice.tag


# ---------------------------------------------------------------------------
# ElasticContext: rescale-on-restore through train_loop (cheap fake step).
# ---------------------------------------------------------------------------

class _Stream:
    def sharded_batch(self, step, mesh, sharding):
        return jnp.float32(step + 1)


def _fake_step(ep):
    def step(params, opt_state, batch):
        w = params["w"] - 0.01 * batch
        return ({"w": w}, opt_state,
                {"loss": jnp.sum(w * w), "grad_norm": jnp.float32(0.1),
                 "rank_time_us": np.r_[np.full(ep - 1, 100.0), 300.0]})
    return step


def test_train_loop_elastic_rescale_on_restore(tmp_path):
    plan = skewed_plan(3, 2, 8, alpha=1.0)
    cache = SSCCache(8)
    cache.get_or_compile(_sched_cfg(plan, 4), "forward", pipeline=["ratr"])
    ft = FTConfig(ckpt_dir=str(tmp_path), ckpt_every=2)
    params = {"w": jnp.float32(1.0)}

    run3 = train_loop(step_fn=_fake_step(3), params=params, opt_state=None,
                      stream=_Stream(), mesh=None, batch_sharding=None,
                      n_steps=4, ft=ft, log_every=1,
                      elastic=ElasticContext(ep=3, cache=cache,
                                             plans={"live": plan}))
    assert run3.rank_time_ewma is not None and len(run3.rank_time_ewma) == 3

    # Resume on 2 ranks: rank 1 died.
    elastic = ElasticContext(ep=2, cache=cache, dead_ranks=(1,))
    run2 = train_loop(step_fn=_fake_step(2), params=params, opt_state=None,
                      stream=_Stream(), mesh=None, batch_sharding=None,
                      n_steps=6, ft=ft, log_every=1, elastic=elastic)
    assert run2.resumed_from == 4 and run2.step == 6
    # The persisted plan came back remapped = native on the small mesh.
    remapped = elastic.plans["live"]
    assert remapped.counts == remap_plan(plan, dead_ranks=[1]).counts
    assert check_remap(plan, remapped, (0, 2))["ok"]
    (event,) = run2.elastic_events
    assert event["from_ep"] == 3 and event["to_ep"] == 2
    assert event["survivors"] == [0, 2] and event["cache"]["entries"] == 1
    assert cache.info()["active_ep"] == 2 and cache.evictions == 0
    # The EWMA restricted to survivors: old rank 2 (slow) is now rank 1.
    cm = run2.cost_model()
    assert cm.rank_bias is not None and len(cm.rank_bias) == 2
    # Merged history spans the crash boundary.
    assert [m["step"] for m in run2.metrics_log] == list(range(1, 7))
    # Growth: resuming back on 3 ranks re-chunks the other way (the new
    # source joins with zero rows; 6 experts spread back to e_loc=2).
    elastic3 = ElasticContext(ep=3, cache=cache)
    run4 = train_loop(step_fn=_fake_step(3), params=params, opt_state=None,
                      stream=_Stream(), mesh=None, batch_sharding=None,
                      n_steps=8, ft=ft, log_every=1, elastic=elastic3)
    assert run4.elastic_events[0]["to_ep"] == 3
    grown = elastic3.plans["live"]
    assert grown.ep == 3 and grown.total_rows == remapped.total_rows


def test_runstate_cost_model_without_observations():
    rs = RunState(step=0, params=None, opt_state=None, metrics_log=[],
                  stragglers=[])
    assert rs.cost_model().rank_bias is None


def test_dead_ranks_mismatch_raises(tmp_path):
    ft = FTConfig(ckpt_dir=str(tmp_path), ckpt_every=2)
    params = {"w": jnp.float32(1.0)}
    train_loop(step_fn=_fake_step(3), params=params, opt_state=None,
               stream=_Stream(), mesh=None, batch_sharding=None, n_steps=2,
               ft=ft, elastic=ElasticContext(ep=3))
    with pytest.raises(ValueError, match="survivors"):
        train_loop(step_fn=_fake_step(2), params=params, opt_state=None,
                   stream=_Stream(), mesh=None, batch_sharding=None,
                   n_steps=4, ft=ft,
                   elastic=ElasticContext(ep=2, dead_ranks=(0, 1)))


# ---------------------------------------------------------------------------
# End-to-end acceptance: the harness scenarios (dropless run killed
# mid-training, resumed on a shrunken mesh; injected 3× slow rank).
# ---------------------------------------------------------------------------

def test_e2e_rescale_scenario(tmp_path):
    import ftharness
    checks = ftharness.run_rescale("uniform", str(tmp_path))
    assert all(checks.values()), checks


def test_e2e_slow_rank_scenario(tmp_path):
    import ftharness
    checks = ftharness.run_slow("hotspot", str(tmp_path))
    assert all(checks.values()), checks
