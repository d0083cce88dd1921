"""Core transformer layer primitives (pure JAX, shard-friendly).

Everything is a pure function over explicit parameter pytrees — no module
framework. Conventions:

* activations ``[batch, seq, d_model]``; attention heads ``[B, S, H, hd]``;
* parameters are created in ``init_*`` fns (fp32 masters; cast at use);
* attention is *always* computed blockwise over KV (online softmax), so the
  full ``S×S`` score matrix never materializes — required for the 32k prefill
  cells to fit HBM and the production answer anyway;
* all einsums keep named dims stable so GSPMD can propagate shardings.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def layer_norm(x, w, b, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(dt)


def nonparam_ln(x, eps=1e-5):
    """OLMo-style non-parametric LayerNorm (no scale, no bias)."""
    return layer_norm(x, None, None, eps)


def apply_norm(kind: str, x, p, name: str, eps: float = 1e-6):
    """``eps`` is the RMSNorm's; the LayerNorms keep their own."""
    if kind == "rmsnorm":
        return rms_norm(x, p[name], eps)
    if kind == "layernorm":
        return layer_norm(x, p[name], p.get(name + "_b"))
    if kind == "nonparam_ln":
        return nonparam_ln(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_tables(positions, head_dim: int, theta: float):
    """cos/sin tables [..., head_dim/2] for given integer positions."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: [B, S, H, hd]; cos/sin: [B, S, hd/2] (or broadcastable)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention — online softmax over KV blocks.
# ---------------------------------------------------------------------------


def _attn_block(q, k, v, mask, scale):
    """One KV block: returns (scores_max, exp_sum, weighted_v) in fp32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)                                  # [B,H,Q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                                  # noqa: E741
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return m, l, o.astype(jnp.float32)


def blockwise_attention(q, k, v, *, causal: bool, q_offset,
                        sliding_window: int = 0, block: int = 1024,
                        scale: Optional[float] = None):
    """Online-softmax attention, O(S·block) memory.

    q/k: [B, S, H or K, hd]; v: [B, Sk, K, hd_v], K | H (GQA: kv repeated);
    the output has v's head size.
    ``q_offset`` is the absolute position of q[0] relative to k[0] (decode:
    cache_len; self-attn: 0). ``sliding_window`` masks keys older than W.
    """
    B, Sq, H, hd = q.shape
    Sk, K, hd_v = k.shape[1], k.shape[2], v.shape[-1]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    nb = max(1, (Sk + block - 1) // block)
    pad = nb * block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nb, block, H, hd)
    vb = v.reshape(B, nb, block, H, hd_v)

    q_pos = q_offset + jnp.arange(Sq)                        # [Sq]

    def body(carry, blk):
        m_acc, l_acc, o_acc, i = carry
        kb_i, vb_i = blk
        k_pos = i * block + jnp.arange(block)                # [block]
        mask = jnp.ones((Sq, block), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if sliding_window:
            mask &= q_pos[:, None] - k_pos[None, :] < sliding_window
        mask &= (k_pos < Sk)[None, :]
        m, l, o = _attn_block(q, kb_i, vb_i, mask[None, None], scale)  # noqa: E741
        m_new = jnp.maximum(m_acc, m)
        c_old = jnp.exp(m_acc - m_new)
        c_new = jnp.exp(m - m_new)
        l_new = l_acc * c_old + l * c_new
        o_new = (o_acc * c_old[..., None].transpose(0, 2, 1, 3)
                 + o * c_new[..., None].transpose(0, 2, 1, 3))
        return (m_new, l_new, o_new, i + 1), None

    m0 = jnp.full((B, H, Sq), -1e30, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, Sq), dtype=jnp.float32)
    o0 = jnp.zeros((B, Sq, H, hd_v), dtype=jnp.float32)
    # checkpoint per KV block: the backward recomputes each block's scores
    # instead of saving [nb, B, H, Sq, block] fp32 probs — this is what
    # makes the attention actually flash-like in memory on the bwd pass.
    (m, l, o, _), _ = jax.lax.scan(      # noqa: E741
        jax.checkpoint(body), (m0, l0, o0, jnp.int32(0)),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
    denom = l.transpose(0, 2, 1)[..., None]                  # [B,Sq,H,1]
    return (o / jnp.maximum(denom, 1e-30)).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     sliding_window: int = 0,
                     scale: Optional[float] = None):
    """Single-token attention against a (possibly sharded) KV cache.

    q: [B, 1, H, hd]; caches: [B, S, K, hd]. Softmax reductions over the
    cache S dim are plain jnp reductions, so a sequence-sharded cache
    resolves to GSPMD all-reduces — the flash-decoding pattern.
    """
    B, _, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    qg = q.reshape(B, 1, K, H // K, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache)
    s = s.astype(jnp.float32) * scale
    pos = jnp.arange(S)
    lens = jnp.asarray(cache_len)
    if lens.ndim == 0:                                       # uniform batch
        lens = jnp.full((B,), lens)
    mask = pos[None, :] < lens[:, None]                      # [B, S]
    if sliding_window:
        mask &= pos[None, :] >= lens[:, None] - sliding_window
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, H, v_cache.shape[-1])


def _ring_decode_attention(q, k_cache, v_cache, n_tokens, W):
    """Decode attention over a ring-buffer window cache of W slots.

    Slot ``i`` holds the newest token with ``pos ≡ i (mod W)`` — all slots
    are within the window by construction; only not-yet-written slots are
    masked (n_tokens < W). Keys were rotated at absolute positions already.
    """
    B, _, H, hd = q.shape
    K = k_cache.shape[2]
    qg = q.reshape(B, 1, K, H // K, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache).astype(jnp.float32)
    s = s * (1.0 / np.sqrt(hd))
    lens = jnp.asarray(n_tokens)
    if lens.ndim == 0:
        lens = jnp.full((B,), lens)
    mask = jnp.arange(W)[None, :] < lens[:, None]
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# Attention layer (GQA/MQA, optional bias, RoPE, KV cache)
# ---------------------------------------------------------------------------


def init_attention(key, d_model, n_heads, n_kv_heads, head_dim,
                   qkv_bias=False, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d_model ** -0.5
    p = {
        "wq": jax.random.normal(k1, (d_model, n_heads * head_dim), dtype) * std,
        "wk": jax.random.normal(k2, (d_model, n_kv_heads * head_dim), dtype) * std,
        "wv": jax.random.normal(k3, (d_model, n_kv_heads * head_dim), dtype) * std,
        "wo": jax.random.normal(k4, (n_heads * head_dim, d_model), dtype) * std,
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


@jax.named_scope("attention")
def attention(p, x, *, n_heads, n_kv_heads, head_dim, rope_theta,
              causal=True, sliding_window=0, block=1024,
              cache=None, positions=None):
    """Returns (out, new_cache). ``cache`` = dict(k, v, len) for decode."""
    B, S, _ = x.shape
    compute_dtype = x.dtype

    def proj(w, b, n):
        y = jnp.einsum("bsd,de->bse", x, w.astype(compute_dtype))
        if b is not None:
            y = y + b.astype(compute_dtype)
        return y.reshape(B, S, n, head_dim)

    from repro.parallel.ctx import constrain_heads
    # Head-shard the attention tensors (SP→TP reshard at the block entry;
    # no-op without an active head_sharding context or on smoke tests).
    q = constrain_heads(proj(p["wq"], p.get("bq"), n_heads))
    k = proj(p["wk"], p.get("bk"), n_kv_heads)
    v = proj(p["wv"], p.get("bv"), n_kv_heads)

    if positions is None:
        if cache is not None:
            # cache["len"]: scalar (uniform batched serving) or [B]
            # (continuous batching with per-slot positions).
            lens = jnp.asarray(cache["len"])
            if lens.ndim == 0:
                positions = jnp.broadcast_to(
                    (lens + jnp.arange(S))[None, :], (B, S))
            else:
                positions = lens[:, None] + jnp.arange(S)[None, :]
        else:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if rope_theta:
        cos, sin = rope_tables(positions, head_dim, rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_cache = None
    if cache is not None:
        if S == 1:
            # Sharded one-token decode (flash-decoding) when the launcher
            # installed an impl: local cache write + LSE-combined partials.
            from repro.parallel.ctx import current_flash_decode
            fd = current_flash_decode()
            if fd is not None and not sliding_window:
                res = fd(q, cache["k"], cache["v"], k, v, cache["len"])
                if res is not None:
                    o, kc, vc = res
                    new_cache = {"k": kc, "v": vc, "len": cache["len"] + 1}
                    out = jnp.einsum(
                        "bse,ed->bsd", o.reshape(B, S, n_heads * head_dim),
                        p["wo"].astype(compute_dtype))
                    return out, new_cache
            # Decode: scatter this token's K/V at the write index. Scalar
            # len → one DUS (sharding-friendly); per-slot [B] len → vmapped
            # per-slot writes (continuous batching). Ring buffer for
            # sliding-window caches: wrap so the cache stays O(window).
            W = cache["k"].shape[1]
            lens = jnp.asarray(cache["len"])
            wrap = sliding_window and W <= sliding_window
            if lens.ndim == 0:
                idx = lens % W if wrap else lens
                kc = jax.lax.dynamic_update_slice(cache["k"], k,
                                                  (0, idx, 0, 0))
                vc = jax.lax.dynamic_update_slice(cache["v"], v,
                                                  (0, idx, 0, 0))
            else:
                idx = lens % W if wrap else lens
                kc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                    c, u, (i, 0, 0)))(cache["k"], k, idx)
                vc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                    c, u, (i, 0, 0)))(cache["v"], v, idx)
            new_cache = {"k": kc, "v": vc, "len": cache["len"] + 1}
            if sliding_window and W <= sliding_window:
                o = _ring_decode_attention(q, kc, vc, cache["len"] + 1, W)
            else:
                o = decode_attention(q, kc, vc, cache["len"] + 1,
                                     sliding_window=sliding_window)
        else:
            # Prefill into an empty cache. Window (ring) caches smaller than
            # the prompt keep the last W keys, aligned to ring slots.
            W = cache["k"].shape[1]
            if W < S:
                # slot(p) = p % W: element j of the last-W slice holds
                # position S-W+j and belongs at slot (j + S) % W.
                roll = S % W
                kc = jnp.roll(k[:, -W:], roll, axis=1)
                vc = jnp.roll(v[:, -W:], roll, axis=1)
            elif W > S:
                kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
            else:
                kc, vc = k, v
            new_cache = {"k": kc, "v": vc, "len": cache["len"] + S}
            o = blockwise_attention(q, k, v, causal=causal, q_offset=0,
                                    sliding_window=sliding_window,
                                    block=block)
    else:
        o = blockwise_attention(q, k, v, causal=causal, q_offset=0,
                                sliding_window=sliding_window, block=block)

    out = jnp.einsum("bse,ed->bsd", o.reshape(B, S, n_heads * head_dim),
                     p["wo"].astype(compute_dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3), without the query low-rank path
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Keys and values come up from a ``kv_lora_rank`` latent of each token;
    a ``qk_rope_dim`` rotary key is shared by all heads. Queries come
    straight from the hidden state (no query low-rank path)."""
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


def init_mla(key, d_model, n_heads, m: MLAConfig, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    r, hv = m.kv_lora_rank, n_heads * m.v_head_dim
    std = d_model ** -0.5
    return {
        "wq": jax.random.normal(k1, (d_model, n_heads * m.qk_dim), dtype)
        * std,
        "wkv_a": jax.random.normal(k2, (d_model, r + m.qk_rope_dim), dtype)
        * std,
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wkv_b": jax.random.normal(
            k3, (r, n_heads * (m.qk_nope_dim + m.v_head_dim)), dtype)
        * r ** -0.5,
        "wo": jax.random.normal(k4, (hv, d_model), dtype) * hv ** -0.5,
    }


@jax.named_scope("mla")
def mla_attention(p, x, m: MLAConfig, *, n_heads, rope_theta, eps,
                  block=1024, cache=None):
    """Returns (out, new_cache).

    ``q = x W_q`` ([nope | rope] per head); ``[c | k_rope] = x W_kv_a``;
    ``[k_nope | v] = RMSNorm(c) W_kv_b`` per head; RoPE on ``q_rope`` and on
    the one shared ``k_rope``; causal softmax of ``[q_nope, q_rope] .
    [k_nope, k_rope] / sqrt(qk_dim)``; ``o = (p v) W_o``. Rotary halves are
    split as in :func:`apply_rope`. ``cache`` (uniform length only) holds
    the heads' full keys and values, as a GQA cache does. The projections,
    the latent norm and the rotary embedding run under the ``mla`` scope,
    the causal softmax attention under ``mla/attention``."""
    B, S, _ = x.shape
    dt = x.dtype
    H, nope, r = n_heads, m.qk_nope_dim, m.kv_lora_rank
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)).reshape(
        B, S, H, m.qk_dim)
    kv_a = jnp.einsum("bsd,de->bse", x, p["wkv_a"].astype(dt))
    c = rms_norm(kv_a[..., :r], p["kv_norm"], eps)
    kv = jnp.einsum("bsr,re->bse", c, p["wkv_b"].astype(dt)).reshape(
        B, S, H, nope + m.v_head_dim)
    start = 0 if cache is None else jnp.asarray(cache["len"])
    if jnp.ndim(start):
        raise NotImplementedError("MLA caches take one length per batch")
    cos, sin = rope_tables(jnp.broadcast_to(start + jnp.arange(S), (B, S)),
                           m.qk_rope_dim, rope_theta)
    q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], cos, sin)],
                        -1)
    k_rope = apply_rope(kv_a[..., None, r:], cos, sin)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_dim))],
        -1)
    v = kv[..., nope:]
    scale = m.qk_dim ** -0.5
    new_cache = None
    if cache is not None:
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, start, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, start, 0, 0))
        new_cache = {"k": kc, "v": vc, "len": cache["len"] + S}
    # The attention proper, the blockwise kernel GQA runs too, under the
    # ``attention`` scope within ``mla``.
    with jax.named_scope("attention"):
        if cache is not None and S == 1:
            o = decode_attention(q, kc, vc, cache["len"] + 1, scale=scale)
        else:   # training, or a prefill into an empty cache
            o = blockwise_attention(q, k, v, causal=True, q_offset=0,
                                    block=block, scale=scale)
    out = jnp.einsum("bse,ed->bsd", o.reshape(B, S, H * m.v_head_dim),
                     p["wo"].astype(dt))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU fused-gate, or plain GELU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model, d_ff, act, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    std = d_model ** -0.5
    if act in ("swiglu", "geglu"):
        return {"w_in": jax.random.normal(k1, (d_model, 2 * d_ff), dtype) * std,
                "w_down": jax.random.normal(k2, (d_ff, d_model), dtype)
                * d_ff ** -0.5}
    return {"w_in": jax.random.normal(k1, (d_model, d_ff), dtype) * std,
            "w_down": jax.random.normal(k2, (d_ff, d_model), dtype)
            * d_ff ** -0.5}


def glu_act(h, act: str):
    f = h.shape[-1] // 2
    a, b = h[..., :f], h[..., f:]
    if act == "swiglu":
        return jax.nn.silu(a) * b
    if act == "geglu":
        return jax.nn.gelu(a, approximate=True) * b
    raise ValueError(act)


def mlp(p, x, act: str):
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(dt))
    if act in ("swiglu", "geglu"):
        h = glu_act(h, act)
    else:
        h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
