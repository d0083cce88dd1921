"""Composable model builder: ModelConfig → init / forward / prefill / decode.

One config dataclass covers all ten assigned architecture families:
dense & MoE decoders, encoder-only (audio), VLM backbones, Mamba2 SSD, and
the RecurrentGemma hybrid. Homogeneous stacks scan over stacked per-layer
parameters (compact HLO, remat-friendly); the hybrid stack scans over
(pattern)-superblocks with an unrolled tail.

The MoE block takes a pluggable ``moe_impl`` so the distributed launcher can
inject the EP-sharded execution path (see ``repro/parallel/ep.py``) without
touching model code — the paper's "low code intrusion" integration point.

The embedding and the unembedding with its cross-entropy run under the
``embed`` and ``unembed_ce`` named scopes. The MoE layer's counters (see
``models.moe.routing_counts``) leave the layer scan as its outputs, summed
over layers: ``loss_fn(..., with_counts=True)`` returns them beside the
loss. A sigmoid router's ``moe_load`` stays per layer ([L, E], for the
train step's bias update) and its ``moe_balance_loss``, summed over layers,
joins the loss.

A MoE stack may lead with ``n_dense_layers`` dense layers (DeepSeek-V3's
``first_k_dense_replace``), kept apart under ``dense_blocks`` and run before
the scanned MoE layers; their FFN runs under the ``dense_ffn`` scope.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.ctx import (constrain_activation, current_moe_impl,
                                moe_counts_scope)

from . import layers as L
from .layers import MLAConfig
from .moe import MoEConfig, init_moe, moe_grouped
from .rglru import init_rglru, rglru_block
from .ssm import SSMConfig, init_ssm, ssm_forward


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    head_dim: int = 0             # 0 → d_model // n_heads
    act: str = "swiglu"           # swiglu | geglu | gelu
    norm: str = "rmsnorm"         # rmsnorm | layernorm | nonparam_ln
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    sliding_window: int = 0
    embed_scale: bool = False     # gemma-style sqrt(d) embedding scaling
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid_pattern: tuple = ()    # e.g. ("rglru", "rglru", "local_attn")
    lru_width: int = 0
    feat_in: int = 0              # audio frontend feature width (stub)
    n_patches: int = 0            # vlm patch-prefix length (stub)
    vocab_pad: int = 256
    dtype: str = "bfloat16"
    remat: bool = True
    # 'full' recomputes everything; 'save_moe' checkpoints each block's MoE
    # output so the backward never re-runs dispatch/FFN/combine (saves one
    # full EP round-trip of collectives per layer at ~12MB/layer/device).
    remat_policy: str = "full"
    scan_layers: bool = True      # False → unrolled python loop (cost probes)
    attn_block: int = 1024        # KV block for blockwise attention
    norm_eps: float = 1e-6        # RMSNorm epsilon
    mla: Optional[MLAConfig] = None   # latent attention in place of GQA
    n_dense_layers: int = 0       # leading dense layers of a MoE stack

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(1, self.n_heads))

    @property
    def padded_vocab(self) -> int:
        return int(np.ceil(self.vocab / self.vocab_pad) * self.vocab_pad)

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def layer_types(self) -> list[str]:
        if self.family in ("dense", "audio", "vlm"):
            return ["attn"] * self.n_layers
        if self.family == "moe":
            return (["attn"] * self.n_dense_layers
                    + ["attn_moe"] * (self.n_layers - self.n_dense_layers))
        if self.family == "ssm":
            return ["ssm"] * self.n_layers
        if self.family == "hybrid":
            pat = list(self.hybrid_pattern)
            out = []
            while len(out) < self.n_layers:
                out.extend(pat)
            return out[:self.n_layers]
        raise ValueError(self.family)

    def param_count(self) -> int:
        """Analytic parameter count (for 6ND roofline bookkeeping)."""
        d, f, V = self.d_model, self.d_ff, self.padded_vocab
        n = V * d  # embed
        if not self.tie_embeddings:
            n += d * V
        for t in self.layer_types():
            if t in ("attn", "attn_moe", "local_attn") and self.mla:
                m, H = self.mla, self.n_heads
                n += d * H * m.qk_dim + d * (m.kv_lora_rank + m.qk_rope_dim)
                n += m.kv_lora_rank * H * (m.qk_nope_dim + m.v_head_dim)
                n += H * m.v_head_dim * d
            elif t in ("attn", "attn_moe", "local_attn"):
                n += d * (self.n_heads + 2 * self.n_kv_heads) * self.hd
                n += self.n_heads * self.hd * d
            if t == "attn":
                n += (3 if self.act in ("swiglu", "geglu") else 2) * d * f
            if t == "local_attn" or t == "rglru":
                n += (3 if self.act in ("swiglu", "geglu") else 2) * d * f
            if t == "attn_moe":
                m = self.moe
                n += d * m.e_total + m.e_held * 3 * d * m.d_expert
                n += m.n_shared * 3 * d * m.d_expert
            if t == "ssm":
                s = self.ssm
                d_in = s.expand * d
                H = s.n_heads(d)
                n += d * (2 * d_in + 2 * s.d_state + H) + d_in * d
            if t == "rglru":
                w = self.lru_width or d
                n += 2 * d * w + 2 * w * w + w * d
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k of experts)."""
        if self.family != "moe":
            return self.param_count()
        m = self.moe
        n_moe = self.n_layers - self.n_dense_layers
        full = self.param_count()
        expert_p = n_moe * m.e_held * 3 * self.d_model * m.d_expert
        active_e = n_moe * m.top_k * 3 * self.d_model * m.d_expert
        return full - expert_p + active_e


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_norms(cfg, p, key):
    if cfg.norm == "nonparam_ln":
        return p
    p["ln1"] = jnp.zeros((cfg.d_model,), jnp.float32)
    p["ln2"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if cfg.norm == "layernorm":
        p["ln1_b"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["ln2_b"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def _init_block(cfg: ModelConfig, btype: str, key):
    dt = jnp.float32
    ks = jax.random.split(key, 4)
    p: dict = {}
    if btype in ("attn", "attn_moe", "local_attn") and cfg.mla:
        p["attn"] = L.init_mla(ks[0], cfg.d_model, cfg.n_heads, cfg.mla, dt)
    elif btype in ("attn", "attn_moe", "local_attn"):
        p["attn"] = L.init_attention(ks[0], cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.hd, cfg.qkv_bias, dt)
    if btype in ("attn", "local_attn"):
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dt)
    if btype == "attn_moe":
        p["moe"] = init_moe(ks[1], cfg.d_model, cfg.moe, dt)
    if btype == "ssm":
        p["ssm"] = init_ssm(ks[0], cfg.d_model, cfg.ssm, dt)
    if btype == "rglru":
        p["rglru"] = init_rglru(ks[0], cfg.d_model,
                                cfg.lru_width or cfg.d_model, 4, dt)
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dt)
    return _init_norms(cfg, p, ks[3])


def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 4)
    V, d = cfg.padded_vocab, cfg.d_model
    params: dict = {
        "embed": jax.random.normal(ks[0], (V, d), jnp.float32) * d ** -0.5,
    }
    if cfg.family == "audio":
        params["feat_proj"] = jax.random.normal(
            ks[3], (cfg.feat_in, d), jnp.float32) * cfg.feat_in ** -0.5
    if not cfg.tie_embeddings:
        params["unembed"] = jax.random.normal(
            ks[1], (d, V), jnp.float32) * d ** -0.5
    if cfg.norm != "nonparam_ln":
        params["ln_f"] = jnp.zeros((d,), jnp.float32)
        if cfg.norm == "layernorm":
            params["ln_f_b"] = jnp.zeros((d,), jnp.float32)

    types = cfg.layer_types()
    lkeys = jax.random.split(ks[2], cfg.n_layers)
    if cfg.family == "hybrid":
        pat = len(cfg.hybrid_pattern)
        n_super = cfg.n_layers // pat
        super_blocks = []
        for pos in range(pat):
            idxs = [g * pat + pos for g in range(n_super)]
            stacked = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[_init_block(cfg, types[i], lkeys[i]) for i in idxs])
            super_blocks.append(stacked)
        params["super"] = tuple(super_blocks)
        params["tail"] = [
            _init_block(cfg, types[i], lkeys[i])
            for i in range(n_super * pat, cfg.n_layers)]
    else:
        def stack(lo, hi):
            return jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[_init_block(cfg, types[i], lkeys[i]) for i in range(lo, hi)])

        if cfg.n_dense_layers:
            params["dense_blocks"] = stack(0, cfg.n_dense_layers)
        params["blocks"] = stack(cfg.n_dense_layers, cfg.n_layers)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _norm(cfg, p, x, which):
    return L.apply_norm(cfg.norm, x, p, which, cfg.norm_eps)


def block_apply(cfg: ModelConfig, btype: str, p, x, cache=None,
                moe_impl: Optional[Callable] = None):
    """One residual block. Returns (x, new_cache)."""
    new_cache = None
    if btype in ("attn", "attn_moe", "local_attn") and cfg.mla:
        a, new_cache = L.mla_attention(
            p["attn"], _norm(cfg, p, x, "ln1"), cfg.mla,
            n_heads=cfg.n_heads, rope_theta=cfg.rope_theta, eps=cfg.norm_eps,
            block=cfg.attn_block, cache=cache)
    elif btype in ("attn", "attn_moe", "local_attn"):
        window = cfg.sliding_window if btype == "local_attn" else (
            cfg.sliding_window if cfg.family != "hybrid" else 0)
        a, new_cache = L.attention(
            p["attn"], _norm(cfg, p, x, "ln1"),
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
            rope_theta=cfg.rope_theta, causal=cfg.causal,
            sliding_window=window, block=cfg.attn_block, cache=cache)
    if btype in ("attn", "attn_moe", "local_attn"):
        x = x + a
        h = _norm(cfg, p, x, "ln2")
        if btype == "attn_moe":
            impl = (moe_impl or current_moe_impl()
                    or partial(moe_grouped, act=cfg.act))
            moe_out = impl(p["moe"], h, cfg.moe)
            if cfg.moe.n_shared:
                with jax.named_scope("moe/shared_expert"):
                    moe_out = moe_out + L.mlp(p["moe"]["shared"], h, cfg.act)
            if cfg.remat_policy == "save_moe":
                from jax.ad_checkpoint import checkpoint_name
                moe_out = checkpoint_name(moe_out, "moe_out")
            x = x + moe_out
        elif cfg.n_dense_layers:
            with jax.named_scope("dense_ffn"):
                x = x + L.mlp(p["mlp"], h, cfg.act)
        else:
            x = x + L.mlp(p["mlp"], h, cfg.act)
    elif btype == "ssm":
        y, new_cache = ssm_forward(p["ssm"], _norm(cfg, p, x, "ln1"),
                                   cfg.ssm, cache)
        x = x + y
    elif btype == "rglru":
        y, new_cache = rglru_block(p["rglru"], _norm(cfg, p, x, "ln1"), cache)
        x = x + y
        x = x + L.mlp(p["mlp"], _norm(cfg, p, x, "ln2"), cfg.act)
    else:
        raise ValueError(btype)
    return x, new_cache


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


@jax.named_scope("embed")
def embed_inputs(cfg: ModelConfig, params, batch) -> jnp.ndarray:
    dt = cfg.compute_dtype
    if cfg.family == "audio":
        x = jnp.einsum("bsf,fd->bsd", batch["features"].astype(dt),
                       params["feat_proj"].astype(dt))
    else:
        x = params["embed"].astype(dt)[batch["tokens"]]
        if cfg.family == "vlm" and "patches" in batch:
            x = jnp.concatenate([batch["patches"].astype(dt), x], axis=1)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)
    return x


def _sum_counts(counts: list) -> dict:
    """Key-wise sum of MoE counter dicts ({} for none)."""
    return {k: sum(c[k] for c in counts) for k in counts[0]} if counts else {}


# Router statistics kept per layer, not summed over the stack.
PER_LAYER = ("moe_load",)


def _layer_totals(counts: dict) -> dict:
    """Counters stacked over layers ([L, ...]) summed to totals; those of
    ``PER_LAYER`` keep their layer axis."""
    return {k: v if k in PER_LAYER else jnp.sum(v) for k, v in counts.items()}


def _run_stack(cfg: ModelConfig, params, x, caches=None,
               moe_impl=None):
    """Apply all layers. caches: stacked pytree or None.

    Returns ``(x, new_caches, counts)``: ``counts`` holds the MoE layers'
    counters summed over layers, ``{}`` where no layer recorded any (the
    hybrid stacks run no MoE layer)."""
    types = cfg.layer_types()

    if cfg.family == "hybrid":
        pat = len(cfg.hybrid_pattern)
        n_super = cfg.n_layers // pat

        def super_body(carry, inp):
            x = carry
            ps, cs = inp
            new_cs = []
            for pos in range(pat):
                x, nc = block_apply(cfg, cfg.hybrid_pattern[pos], ps[pos], x,
                                    None if cs is None else cs[pos], moe_impl)
                new_cs.append(nc)
            return x, (tuple(new_cs) if cs is not None else None)

        body = jax.checkpoint(super_body) if cfg.remat else super_body
        sup_caches = None if caches is None else caches["super"]
        if cfg.scan_layers:
            x, new_sup = jax.lax.scan(
                body, x, (params["super"], sup_caches))
        else:
            ncs = []
            for i in range(n_super):
                ps = jax.tree.map(lambda a: a[i], params["super"])
                cs = (None if sup_caches is None
                      else jax.tree.map(lambda a: a[i], sup_caches))
                x, nc = body(x, (ps, cs))
                ncs.append(nc)
            new_sup = (None if sup_caches is None
                       else jax.tree.map(lambda *a: jnp.stack(a), *ncs))
        new_tail = []
        for i, bp in enumerate(params["tail"]):
            btype = types[n_super * pat + i]
            c = None if caches is None else caches["tail"][i]
            x, nc = block_apply(cfg, btype, bp, x, c, moe_impl)
            new_tail.append(nc)
        new_caches = (None if caches is None
                      else {"super": new_sup, "tail": new_tail})
        return x, new_caches, {}

    n_dense = cfg.n_dense_layers
    if n_dense:
        # The leading dense layers, unrolled; their caches are the first
        # n_dense of the stacked caches (attention caches of one shape).
        def dense(x, p, c):
            x, nc = block_apply(cfg, "attn", p, x, c)
            return constrain_activation(x), nc

        dense = jax.checkpoint(dense) if cfg.remat else dense
        dense_caches = []
        for i in range(n_dense):
            x, nc = dense(
                x, jax.tree.map(lambda a: a[i], params["dense_blocks"]),
                None if caches is None
                else jax.tree.map(lambda a: a[i], caches))
            dense_caches.append(nc)
        if caches is not None:
            caches = jax.tree.map(lambda a: a[n_dense:], caches)
    btype = types[n_dense]

    def body(x, inp):
        ps, cs = inp
        with moe_counts_scope() as counts:
            x, nc = block_apply(cfg, btype, ps, x, cs, moe_impl)
        # Sequence-parallel residual stream between blocks (no-op unless an
        # activation_sharding context is active — keeps model mesh-agnostic).
        return constrain_activation(x), (nc, _sum_counts(counts))

    if cfg.remat and cfg.remat_policy == "save_moe":
        fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                "moe_out"))
    elif cfg.remat:
        fn = jax.checkpoint(body)
    else:
        fn = body
    if cfg.scan_layers:
        x, (new_caches, counts) = jax.lax.scan(
            fn, x, (params["blocks"], caches))
        if n_dense and new_caches is not None:
            new_caches = jax.tree.map(
                lambda a, *d: jnp.concatenate([jnp.stack(d), a]),
                new_caches, *dense_caches)
        return x, new_caches, _layer_totals(counts)
    # Unrolled loop (used by the dry-run cost probes: XLA's HloCostAnalysis
    # counts while bodies once, so scanned stacks under-report flops).
    ncs, counts = [], []
    for i in range(cfg.n_layers - cfg.n_dense_layers):
        ps = jax.tree.map(lambda a: a[i], params["blocks"])
        cs = (None if caches is None
              else jax.tree.map(lambda a: a[i], caches))
        x, (nc, c) = fn(x, (ps, cs))
        ncs.append(nc)
        counts.append(c)
    new_caches = (None if caches is None
                  else jax.tree.map(lambda *a: jnp.stack(a),
                                    *(dense_caches if n_dense else []), *ncs))
    return x, new_caches, _layer_totals(
        {k: jnp.stack([c[k] for c in counts]) for k in counts[0]}
        if counts and counts[0] else {})


def forward(cfg: ModelConfig, params, batch, moe_impl=None):
    """Full forward → logits [B, S, Vp] (VLM: token region only)."""
    x, _ = final_hidden(cfg, params, batch, moe_impl)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"])
    return jnp.einsum("bsd,dv->bsv", x, unembed.astype(x.dtype))


def _ce_chunk(cfg: ModelConfig, x, labels, unembed):
    """CE over one sequence chunk; logits exist only inside this fn."""
    logits = jnp.einsum("bsd,dv->bsv", x,
                        unembed.astype(x.dtype)).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab:
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad[None, None, :], -1e30, logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - picked) * mask), jnp.sum(mask)


def loss_fn(cfg: ModelConfig, params, batch, moe_impl=None,
            ce_chunk: int = 512, with_counts: bool = False):
    """Next-token (or frame-label) cross entropy, fp32, vocab-pad masked.

    The unembedding + logsumexp run in sequence chunks under jax.checkpoint
    so the full [B, S, V] logits tensor never materializes — required to fit
    the 100k+-vocab archs in HBM at train_4k. With ``with_counts`` returns
    ``(loss, counts)``: the MoE layers' counters summed over layers (``{}``
    for a model with no MoE layer)."""
    x, counts = final_hidden(cfg, params, batch, moe_impl)
    labels = batch["labels"]
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"])
    B, S, _ = x.shape
    n = max(1, S // max(1, min(ce_chunk, S)))
    while S % n:
        n -= 1

    def chunk_body(carry, inp):
        x_c, l_c = inp
        nll_c, cnt_c = _ce_chunk(cfg, x_c, l_c, unembed)
        return (carry[0] + nll_c, carry[1] + cnt_c), None

    with jax.named_scope("unembed_ce"):
        xs = x.reshape(B, n, S // n, -1).transpose(1, 0, 2, 3)
        ls = labels.reshape(B, n, S // n).transpose(1, 0, 2)
        (nll, cnt), _ = jax.lax.scan(jax.checkpoint(chunk_body),
                                     (0.0, 0.0), (xs, ls))
        loss = nll / jnp.maximum(cnt, 1.0)
    if "moe_balance_loss" in counts:
        loss = loss + counts["moe_balance_loss"]
    return (loss, counts) if with_counts else loss


def final_hidden(cfg: ModelConfig, params, batch, moe_impl=None):
    """Forward to the final (pre-unembedding) hidden states; returns
    ``(hidden, counts)``, ``counts`` as :func:`loss_fn` gives them."""
    x = constrain_activation(embed_inputs(cfg, params, batch))
    x, _, counts = _run_stack(cfg, params, x, None, moe_impl)
    if cfg.norm != "nonparam_ln":
        x = L.apply_norm(cfg.norm, x, params, "ln_f", cfg.norm_eps)
    else:
        x = L.nonparam_ln(x)
    if cfg.family == "vlm" and "patches" in batch:
        x = x[:, batch["patches"].shape[1]:]
    return x, counts


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _block_cache(cfg: ModelConfig, btype: str, B: int, max_len: int,
                 per_slot_len: bool = False):
    dt = cfg.compute_dtype
    zlen = (jnp.zeros((B,), jnp.int32) if per_slot_len else jnp.int32(0))
    if btype in ("attn", "attn_moe") and cfg.mla:
        m = cfg.mla
        return {"k": jnp.zeros((B, max_len, cfg.n_heads, m.qk_dim), dt),
                "v": jnp.zeros((B, max_len, cfg.n_heads, m.v_head_dim), dt),
                "len": zlen}
    if btype in ("attn", "attn_moe"):
        shp = (B, max_len, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt),
                "len": zlen}
    if btype == "local_attn":
        W = min(max_len, cfg.sliding_window or max_len)
        shp = (B, W, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt),
                "len": zlen}
    if btype == "ssm":
        s = cfg.ssm
        H = s.n_heads(cfg.d_model)
        d_in = s.expand * cfg.d_model
        return {"conv": jnp.zeros((B, s.conv_width - 1,
                                   d_in + 2 * s.d_state), dt),
                "ssm": jnp.zeros((B, H, s.d_state, s.head_dim), dt)}
    if btype == "rglru":
        w = cfg.lru_width or cfg.d_model
        return {"conv": jnp.zeros((B, 3, w), dt),
                "h": jnp.zeros((B, w), jnp.float32)}
    raise ValueError(btype)


def init_cache(cfg: ModelConfig, B: int, max_len: int,
               per_slot_len: bool = False):
    types = cfg.layer_types()
    if cfg.family == "hybrid":
        pat = len(cfg.hybrid_pattern)
        n_super = cfg.n_layers // pat
        sup = tuple(
            jax.tree.map(lambda x: jnp.stack([x] * n_super),
                         _block_cache(cfg, cfg.hybrid_pattern[pos], B,
                                      max_len, per_slot_len))
            for pos in range(pat))
        tail = [_block_cache(cfg, types[n_super * pat + i], B, max_len,
                             per_slot_len)
                for i in range(cfg.n_layers - n_super * pat)]
        return {"super": sup, "tail": tail}
    one = _block_cache(cfg, types[0], B, max_len, per_slot_len)
    return jax.tree.map(lambda x: jnp.stack([x] * cfg.n_layers), one)


def decode_step(cfg: ModelConfig, params, token, cache, moe_impl=None):
    """token: [B, 1] → (logits [B, 1, Vp], new_cache)."""
    x = params["embed"].astype(cfg.compute_dtype)[token]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    x, new_cache, _ = _run_stack(cfg, params, x, cache, moe_impl)
    if cfg.norm != "nonparam_ln":
        x = L.apply_norm(cfg.norm, x, params, "ln_f", cfg.norm_eps)
    else:
        x = L.nonparam_ln(x)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", x, unembed.astype(x.dtype))
    return logits, new_cache


def prefill(cfg: ModelConfig, params, batch, max_len: int, moe_impl=None):
    """Run the prompt through the stack, filling caches.

    Returns (last-token logits [B, Vp], cache). For encoder-only families
    there is no cache; call ``forward`` instead.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache = init_cache(cfg, B, max_len)
    x = embed_inputs(cfg, params, batch)
    x, new_cache, _ = _run_stack(cfg, params, x, cache, moe_impl)
    if cfg.norm != "nonparam_ln":
        x = L.apply_norm(cfg.norm, x, params, "ln_f", cfg.norm_eps)
    else:
        x = L.nonparam_ln(x)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"])
    logits = jnp.einsum("bd,dv->bv", x[:, -1], unembed.astype(x.dtype))
    return logits, new_cache