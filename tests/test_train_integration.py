"""End-to-end training integration: loss decreases, FT restart, simulator."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.hardware import AscendA3
from repro.core.odg import ScheduleConfig, build_moe_ffn_forward
from repro.core.scheduler import compile_schedule
from repro.core.simulator import simulate_baseline, simulate_unified
from repro.data.pipeline import DataConfig, SyntheticStream
from repro.ft.runner import FTConfig, train_loop
from repro.models import model as M
from repro.optim import adamw

KEY = jax.random.PRNGKey(0)


def _setup(cfg):
    params = adamw.cast_params(M.init_params(cfg, KEY), cfg.compute_dtype)
    opt_state = adamw.init_opt_state(params)
    oc = adamw.OptConfig(lr=3e-3, warmup_steps=5, total_steps=100,
                         weight_decay=0.0)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch))(params)
        p2, s2, m = adamw.apply_updates(params, grads, opt_state, oc)
        m["loss"] = loss
        return p2, s2, m

    return params, opt_state, step


class _Stream:
    def __init__(self, dc):
        self.s = SyntheticStream(dc)

    def sharded_batch(self, step, mesh, sharding):
        b = self.s.global_batch_np(step)
        return {k: jnp.asarray(v) for k, v in b.items()}


def test_loss_decreases():
    cfg = dataclasses.replace(get_smoke_config("olmo-1b"), n_layers=2)
    params, opt_state, step = _setup(cfg)
    dc = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
    stream = SyntheticStream(dc)
    losses = []
    for i in range(30):
        batch = {k: jnp.asarray(v)
                 for k, v in stream.global_batch_np(i % 4).items()}
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses[::6]


def test_ft_checkpoint_restart_determinism(tmp_path):
    """Crash mid-run → resume gives the same final state as uninterrupted."""
    cfg = dataclasses.replace(get_smoke_config("qwen2-1.5b"), n_layers=1)
    dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
    stream = _Stream(dc)
    ft_a = FTConfig(ckpt_dir=str(tmp_path / "a"), ckpt_every=5)
    ft_b = FTConfig(ckpt_dir=str(tmp_path / "b"), ckpt_every=5)

    # uninterrupted run
    params, opt_state, step = _setup(cfg)
    run_a = train_loop(step_fn=step, params=params, opt_state=opt_state,
                       stream=stream, mesh=None, batch_sharding=None,
                       n_steps=12, ft=ft_a)

    # crashing run: dies at step 8, then resumes from the step-5 checkpoint
    params, opt_state, step = _setup(cfg)

    def bomb(s):
        if s == 8 and not os.environ.get("_RESUMED"):
            os.environ["_RESUMED"] = "1"
            raise RuntimeError("injected node failure")

    with pytest.raises(RuntimeError, match="injected"):
        train_loop(step_fn=step, params=params, opt_state=opt_state,
                   stream=stream, mesh=None, batch_sharding=None,
                   n_steps=12, ft=ft_b, inject_fault=bomb)
    params2, opt_state2, step2 = _setup(cfg)
    run_b = train_loop(step_fn=step2, params=params2, opt_state=opt_state2,
                       stream=stream, mesh=None, batch_sharding=None,
                       n_steps=12, ft=ft_b)
    os.environ.pop("_RESUMED", None)
    assert run_b.resumed_from == 5
    for a, b in zip(jax.tree.leaves(run_a.params),
                    jax.tree.leaves(run_b.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_elastic_restore_structure(tmp_path):
    """Checkpoints restore into a differently-jitted context (logical)."""
    cfg = dataclasses.replace(get_smoke_config("olmo-1b"), n_layers=1)
    params = M.init_params(cfg, KEY)
    from repro.checkpoint import ckpt as CK
    CK.save(str(tmp_path), 1, params)
    restored, _ = CK.restore(CK.latest_step_dir(str(tmp_path)), params)
    assert jax.tree_util.tree_structure(restored) == \
        jax.tree_util.tree_structure(params)


def test_simulator_unified_beats_baseline():
    cfg = ScheduleConfig(ep=8, e_loc=8, rows=1024, d_model=7168, d_ff=1024,
                         gmm_m_split=1)
    s_base = compile_schedule(build_moe_ffn_forward(cfg))
    cfg_opt = ScheduleConfig(ep=8, e_loc=8, rows=1024, d_model=7168,
                             d_ff=1024, gmm_m_split=32)
    s_opt = compile_schedule(build_moe_ffn_forward(cfg_opt), ratr=True)
    hw = AscendA3()
    b = simulate_baseline(s_base, hw)
    u = simulate_unified(s_opt, hw)
    assert u.makespan_us < b.makespan_us
    assert u.mac_ratio > b.mac_ratio
    assert u.exposed_comm_us < b.exposed_comm_us


def test_simulator_ratr_helps_ingress_balance():
    cfg = ScheduleConfig(ep=8, e_loc=8, rows=1024, d_model=7168, d_ff=1024,
                         gmm_m_split=8)
    hw = AscendA3()
    naive = simulate_unified(
        compile_schedule(build_moe_ffn_forward(cfg)), hw)
    ratr = simulate_unified(
        compile_schedule(build_moe_ffn_forward(cfg), ratr=True), hw)
    assert ratr.makespan_us <= naive.makespan_us * 1.02


def test_train_main_depth_cut_sharded_state_and_resume(tmp_path,
                                                       monkeypatch):
    """The launcher cuts depth only, builds the state on its shardings, and
    resumes a compiled step from its own checkpoint."""
    from repro.launch import train as T
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    argv = ["--arch", "granite-moe-3b-a800m", "--smoke", "--layers", "1",
            "--mesh", "1x1", "--global-batch", "2", "--seq", "16",
            "--ckpt-dir", str(tmp_path / "ckpt"),
            "--ckpt-every", "2"]
    run = T.main(argv + ["--steps", "3"])
    assert [m["step"] for m in run.metrics_log] == [1, 2, 3]
    assert all(np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
               for m in run.metrics_log)
    smoke = get_smoke_config("granite-moe-3b-a800m")
    w_in = run.params["blocks"]["moe"]["w_in"]
    assert w_in.shape == (1, smoke.moe.e_total, smoke.d_model,
                          2 * smoke.moe.d_expert)
    for leaf in jax.tree.leaves((run.params, run.opt_state)):
        assert isinstance(leaf.sharding, jax.sharding.NamedSharding)

    resumed = T.main(argv + ["--steps", "4"])
    assert resumed.resumed_from == 3
    assert [m["step"] for m in resumed.metrics_log] == [1, 2, 3, 4]
    assert resumed.metrics_log[2] == run.metrics_log[2]


def test_compile_cache_dir_env_or_fixed_in_checkout(tmp_path, monkeypatch):
    from repro.launch import compile_cache as CC
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert CC.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert CC.enable_compile_cache() == str(CC.DEFAULT_DIR)
        repo = CC.DEFAULT_DIR.parent
        assert (repo / "src" / "repro" / "launch" / "compile_cache.py"
                ).is_file()
        ignored = (repo / ".gitignore").read_text().split()
        assert CC.DEFAULT_DIR.name + "/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
