"""Model substrate tests: per-arch smokes + layer-level oracles."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.configs.shapes import SHAPES, cells, skip_reason
from repro.models import model as M
from repro.models import layers as L

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    if cfg.family == "audio":
        return {"features": jax.random.normal(KEY, (B, S, cfg.feat_in)),
                "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab)}
    b = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
         "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab)}
    if cfg.family == "vlm":
        b["patches"] = jax.random.normal(KEY, (B, cfg.n_patches, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    """Reduced config: one forward + one grad step, shapes + finiteness."""
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, KEY)
    batch = _batch(cfg)
    logits = M.forward(cfg, params, batch)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss, grads = jax.value_and_grad(
        lambda p: M.loss_fn(cfg, p, batch))(params)
    assert np.isfinite(float(loss))
    gnorm = float(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                      for g in jax.tree.leaves(grads)) ** 0.5)
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", [a for a in ARCHS if a != "hubert-xlarge"])
def test_arch_smoke_decode(arch):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, KEY)
    batch = {k: v for k, v in _batch(cfg).items() if k != "labels"}
    last, cache = M.prefill(cfg, params, batch, max_len=40)
    tok = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
    for _ in range(3):
        lg, cache = M.decode_step(cfg, params, tok, cache)
        tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
        assert np.isfinite(np.asarray(lg, np.float32)).all()


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-1.3b",
                                  "recurrentgemma-2b", "qwen2-1.5b"])
def test_decode_consistency_with_forward(arch):
    """Teacher-forced decode must reproduce the parallel forward logits.

    fp32 compute: this asserts *path* equivalence (prefill+decode vs the
    parallel forward), not bf16 rounding behaviour."""
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    params = M.init_params(cfg, KEY)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab)
    full = M.forward(cfg, params, {"tokens": toks})

    pre = 8
    last, cache = M.prefill(cfg, params, {"tokens": toks[:, :pre]},
                            max_len=S)
    np.testing.assert_allclose(np.asarray(last, np.float32),
                               np.asarray(full[:, pre - 1], np.float32),
                               rtol=2e-2, atol=2e-2)
    for t in range(pre, S):
        lg, cache = M.decode_step(cfg, params, toks[:, t:t + 1], cache)
        np.testing.assert_allclose(
            np.asarray(lg[:, 0], np.float32),
            np.asarray(full[:, t], np.float32), rtol=2e-2, atol=2e-2)


def test_blockwise_attention_vs_naive():
    B, S, H, hd = 2, 64, 4, 16
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, S, H, hd))
    k = jax.random.normal(k2, (B, S, H, hd))
    v = jax.random.normal(k3, (B, S, H, hd))
    got = L.blockwise_attention(q, k, v, causal=True, q_offset=0, block=16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_blockwise_attention_sliding_window():
    B, S, H, hd, W = 1, 64, 2, 8, 16
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks)
    got = L.blockwise_attention(q, k, v, causal=True, q_offset=0,
                                sliding_window=W, block=16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    qi = jnp.arange(S)[:, None]
    kj = jnp.arange(S)[None, :]
    mask = (qi >= kj) & (qi - kj < W)
    s = jnp.where(mask[None, None], s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gqa_repeat_equivalence():
    """GQA with K<H equals full MHA with repeated KV heads."""
    B, S, H, K, hd = 1, 32, 4, 2, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    got = L.blockwise_attention(q, k, v, causal=True, q_offset=0, block=8)
    want = L.blockwise_attention(q, jnp.repeat(k, 2, 2),
                                 jnp.repeat(v, 2, 2), causal=True,
                                 q_offset=0, block=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_ssd_chunked_vs_sequential():
    from repro.models.ssm import _ssd_chunked, ssd_reference
    b, Lseq, H, P, N = 2, 32, 3, 4, 8
    rng = jax.random.split(KEY, 4)
    x = jax.random.normal(rng[0], (b, Lseq, H, P))
    dt = jax.nn.softplus(jax.random.normal(rng[1], (b, Lseq, H)))
    A = -jnp.exp(jax.random.normal(rng[2], (H,)) * 0.3)
    B_ = jax.random.normal(rng[3], (b, Lseq, N))
    C_ = jax.random.normal(rng[0], (b, Lseq, N))
    D = jnp.ones((H,))
    got, _ = _ssd_chunked(x, dt, A, B_, C_, D, chunk=8)
    want = ssd_reference(x, dt, A, B_, C_, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_rglru_scan_vs_sequential():
    from repro.models.rglru import init_rglru, rglru_reference, _rglru_core
    p = init_rglru(KEY, 16, 24)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 20, 24))
    got, h_last = _rglru_core(x, p)
    want = rglru_reference(x, p)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last),
                               np.asarray(want[:, -1]), rtol=1e-4, atol=1e-4)


def test_param_count_sane():
    # Full configs match their nameplate sizes (±20% — vocab/rounding).
    expect = {"olmo-1b": 1.3e9, "llama3_2-3b": 3.4e9, "qwen2-1_5b": 1.6e9,
              "gemma-2b": 2.6e9, "mamba2-1_3b": 1.3e9, "dbrx-132b": 132e9}
    for arch, n in expect.items():
        got = get_config(arch).param_count()
        assert 0.7 * n < got < 1.35 * n, (arch, got, n)


def test_shape_skip_rules():
    assert skip_reason(get_config("olmo-1b"), "long_500k")
    assert not skip_reason(get_config("mamba2-1.3b"), "long_500k")
    assert skip_reason(get_config("hubert-xlarge"), "decode_32k")
    assert len(cells(get_config("hubert-xlarge"))) == 2
    total = sum(len(cells(get_config(a))) for a in ARCHS)
    # 11 archs x 4 shapes assigned minus 10 mandated skips (DESIGN.md §4):
    # long_500k for the 9 full-attention archs, hubert's decode_32k.
    assert total == 34
