"""Checkpointing, optimizer, compression, and data pipeline tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt as CK
from repro.data.pipeline import DataConfig, SyntheticStream
from repro.optim import adamw
from repro.parallel import compression as comp

KEY = jax.random.PRNGKey(0)


def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((5,)) * 2,
                       "t": (jnp.zeros((2, 2)), jnp.full((3,), 7.0))}}


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree()
    CK.save(str(tmp_path), 7, tree)
    d = CK.latest_step_dir(str(tmp_path))
    restored, manifest = CK.restore(d, tree)
    assert manifest["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_incomplete_ignored(tmp_path):
    tree = _tree()
    CK.save(str(tmp_path), 1, tree)
    # simulate a crashed save: dir without _COMPLETE
    os.makedirs(tmp_path / "step_00000002")
    (tmp_path / "latest").write_text("step_00000002")
    d = CK.latest_step_dir(str(tmp_path))
    assert d.endswith("step_00000001")


def test_checkpoint_checksum_detects_corruption(tmp_path):
    tree = _tree()
    d = CK.save(str(tmp_path), 3, tree)
    shard = os.path.join(d, "shard_00000.npz")
    data = dict(np.load(shard))
    first = sorted(data)[0]
    data[first] = data[first] + 1
    np.savez(shard, **data)
    with pytest.raises(IOError, match="checksum"):
        CK.restore(d, tree)


def test_checkpoint_gc(tmp_path):
    for s in (1, 2, 3, 4, 5):
        CK.save(str(tmp_path), s, {"x": jnp.ones(3)})
    CK.gc_old(str(tmp_path), keep=2)
    dirs = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert sorted(dirs) == ["step_00000004", "step_00000005"]


def test_adamw_master_update():
    params = adamw.cast_params({"w": jnp.ones((4, 4))}, jnp.bfloat16)
    state = adamw.init_opt_state(params)
    oc = adamw.OptConfig(lr=0.1, warmup_steps=0, weight_decay=0.0)
    g = {"w": jnp.full((4, 4), 0.5, jnp.bfloat16)}
    p2, s2, m = adamw.apply_updates(params, g, state, oc)
    assert p2["w"].dtype == jnp.bfloat16
    assert s2["master"]["w"].dtype == jnp.float32
    assert float(s2["master"]["w"][0, 0]) < 1.0     # moved against grad
    assert float(m["grad_norm"]) > 0


def test_grad_clip():
    g = {"w": jnp.full((10,), 10.0)}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    assert abs(float(adamw.global_norm(clipped)) - 1.0) < 1e-5


def test_accumulate_grads_matches_full_batch():
    w = jnp.array([[1.0, 2.0], [3.0, 4.0]])
    xs = jax.random.normal(KEY, (8, 2))

    def loss(w, batch):
        return jnp.mean((batch @ w) ** 2)

    full = jax.grad(loss)(w, xs)
    mb = xs.reshape(4, 2, 2)
    (_, aux), acc = adamw.accumulate_grads(
        lambda p, b: jax.value_and_grad(
            lambda p, b: (loss(p, b), {"rows": b.shape[0]}),
            has_aux=True)(p, b), w, mb)
    assert int(aux["rows"]) == 8
    np.testing.assert_allclose(np.asarray(acc), np.asarray(full),
                               rtol=1e-5, atol=1e-6)


def test_int8_error_feedback_reduces_bias():
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.standard_normal((64,)) * 0.01)
    err = comp.int8_ef_init({"g": g_true})
    acc_with = np.zeros(64)
    err_state = err
    for _ in range(50):
        deq, err_state = comp.int8_ef_compress({"g": g_true}, err_state)
        acc_with += np.asarray(deq["g"])
    # with error feedback the accumulated average converges to the truth
    np.testing.assert_allclose(acc_with / 50, np.asarray(g_true),
                               atol=2e-4)


def test_data_determinism_and_sharding():
    dc = DataConfig(vocab=101, seq_len=16, global_batch=8)
    s = SyntheticStream(dc)
    a = s.global_batch_np(3)
    b = s.global_batch_np(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = s.global_batch_np(4)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # labels are next-token shifted
    full0 = s._tokens(3, 0, 1)[0]
    np.testing.assert_array_equal(a["tokens"][0], full0[:-1])
    np.testing.assert_array_equal(a["labels"][0], full0[1:])
    # row-ranges compose: rows 2..5 match the global batch slice
    np.testing.assert_array_equal(s._tokens(3, 2, 5), s._tokens(3, 0, 8)[2:5])
    assert a["tokens"].min() >= 1 and a["tokens"].max() < dc.vocab


def test_straggler_watchdog():
    """Slow steps trip the EWMA watchdog in the FT loop."""
    import time as _time
    import dataclasses as _dc
    from repro.configs import get_smoke_config
    from repro.ft.runner import FTConfig, train_loop
    from repro.models import model as M
    import jax as _jax

    cfg = _dc.replace(get_smoke_config("olmo-1b"), n_layers=1)
    params = M.init_params(cfg, _jax.random.PRNGKey(0))

    calls = {"n": 0}

    def fake_step(params, opt_state, batch):
        calls["n"] += 1
        if calls["n"] == 6:
            _time.sleep(0.5)          # injected straggler
        return params, opt_state, {"loss": jnp.float32(1.0),
                                   "grad_norm": jnp.float32(0.1)}

    class _S:
        def sharded_batch(self, step, mesh, sharding):
            return {}

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        run = train_loop(step_fn=fake_step, params=params, opt_state={},
                         stream=_S(), mesh=None, batch_sharding=None,
                         n_steps=10,
                         ft=FTConfig(ckpt_dir=d, ckpt_every=100,
                                     straggler_factor=5.0))
    assert any(s[0] == 5 for s in run.stragglers), run.stragglers
