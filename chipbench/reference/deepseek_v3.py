"""Plain reference of the DeepSeek-V3 decoder's training step at one chip's
share of an expert-parallel group (Moonlight-16B-A3B is one such model).

Written from the architecture's equations (DeepSeek-V3 technical report,
arXiv 2412.19437; the model's config.json) and from AdamW's:

- pre-norm decoder, RMSNorm (``rms_norm_eps``), untied embedding and head;
- multi-head latent attention without the query low-rank path: ``q = x
  W_q`` per head ``[nope | rope]``; ``[c | k_rope] = x W_kv_a``; ``[k_nope
  | v] = RMSNorm(c) W_kv_b`` per head; rotary embedding (``rope_theta``) on
  ``q_rope`` and on the one ``k_rope`` all heads share; causal softmax of
  ``[q_nope, q_rope] . [k_nope, k_rope] / sqrt(nope + rope)``; ``o = (p v)
  W_o``;
- ``first_k_dense_replace`` leading layers with a SwiGLU FFN of
  ``intermediate_size``; then MoE layers: sigmoid scores over all
  ``published.n_routed_experts`` experts, the top ``num_experts_per_tok``
  of score plus the selection bias (no group limit), weights the chosen
  scores renormalised times ``routed_scaling_factor``; SwiGLU experts of
  ``moe_intermediate_size``; ``n_shared_experts`` shared experts on every
  token;
- the sequence-wise balance loss, ``alpha * sum_e f_e P_e`` a sequence and
  layer with ``f_e = E / (k S) * choices of e`` and ``P_e`` the sequence's
  mean of the scores normalised over the experts, averaged over sequences
  and added to the mean cross-entropy;
- after each step, the aux-loss-free balance: each MoE layer's bias moves
  ``gamma * sign(mean load - load)`` by expert, the load counted over the
  step's tokens and all experts; the optimizer leaves the bias alone.
  The bias takes no gradient: what it steps against is its excess load,
  ``load - mean load``, which the program keeps in the bias's slot of
  Adam's first moment. So the bias's entry among the first gradient's leaf
  norms is the first step's excess load, and ``compare`` holds the bias's
  change over the steps to the reference's as it holds a trained leaf's.

``alpha`` and ``gamma`` are the configuration's ``assumed`` values.

The share: the experts held here are ``deployment.held_experts``; the
router routes over all experts, and only the held experts' part of the
routed sum is computed. What the other chips' experts would add is left
out, as the program leaves it out. The vocabulary is the configuration's
slice. Rotary halves are split, not interleaved: with weights from a seed
both are one model up to a fixed permutation of columns.

It imports nothing of the program under test. Every matmul runs in float32
at ``Precision.HIGHEST``; no token is dropped. To fit one chip beside
nothing else, it computes each row's gradient alone, checkpoints each layer
and each block of queries, and keeps Adam's moments on the host, moving one
leaf at a time to the device for its update.

``precision="fp8"`` is the control: each matmul's operands rounded to
float8 e4m3 with a per-tensor scale, and each matmul's output gradient to
float8 e5m2 in the backward pass.

The parameter tree has the layout the program takes (``dense_blocks`` and
``blocks`` stacked by layer, the held experts, the router's bias as a
float32 leaf), so the benchmark's weights feed both. RMSNorm gains are
stored as ``1 + w`` with ``w`` starting at 0; the bias starts at 0.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
ZERO_INIT = ("['ln1']", "['ln2']", "['kv_norm']", "['router_bias']")
NOT_DECAYED = ("['ln1']", "['ln2']", "['ln_f']", "['kv_norm']")
BIAS = "['router_bias']"


# ---------------------------------------------------------------------------
# Sizes, layout and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    pad = cfg["program"]["vocab_pad"]
    V = cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    Ld = cfg["first_k_dense_replace"]
    return {
        "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "Ld": Ld, "Lm": L - Ld, "F": cfg["intermediate_size"],
        "f": cfg["moe_intermediate_size"],
        "E": cfg["published"]["n_routed_experts"],
        "Eh": cfg["n_routed_experts"],
        "lo": cfg["deployment"]["held_experts"][0],
        "k": cfg["num_experts_per_tok"], "ns": cfg["n_shared_experts"],
        "V": V, "Vp": int(math.ceil(V / pad) * pad),
    }


def _attn_layout(s: dict, L: int) -> dict:
    H, qk = s["H"], s["nope"] + s["rope"]
    return {"wq": (L, s["d"], H * qk),
            "wkv_a": (L, s["d"], s["r"] + s["rope"]), "kv_norm": (L, s["r"]),
            "wkv_b": (L, s["r"], H * (s["nope"] + s["v"])),
            "wo": (L, H * s["v"], s["d"])}


def layout(cfg: dict) -> dict:
    """Shape of every parameter (name -> shape), in the program's tree."""
    s = sizes(cfg)
    d, Ld, Lm, f = s["d"], s["Ld"], s["Lm"], s["f"]
    tree = {
        "embed": (s["Vp"], d), "unembed": (d, s["Vp"]), "ln_f": (d,),
        "blocks": {
            "attn": _attn_layout(s, Lm),
            "moe": {"router": (Lm, d, s["E"]), "router_bias": (Lm, s["E"]),
                    "w_in": (Lm, s["Eh"], d, 2 * f),
                    "w_down": (Lm, s["Eh"], f, d),
                    "shared": {"w_in": (Lm, d, 2 * s["ns"] * f),
                               "w_down": (Lm, s["ns"] * f, d)}},
            "ln1": (Lm, d), "ln2": (Lm, d),
        },
    }
    if Ld:
        tree["dense_blocks"] = {
            "attn": _attn_layout(s, Ld),
            "mlp": {"w_in": (Ld, d, 2 * s["F"]), "w_down": (Ld, s["F"], d)},
            "ln1": (Ld, d), "ln2": (Ld, d)}
    return tree


def _fan_in(path: str, shape: tuple) -> int:
    if path.endswith("['embed']"):
        return shape[-1]
    return shape[-2]


def seed_key(seed: int):
    """A threefry key from any whole seed below 2**64, as two words."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def weights(cfg: dict, key_words, dtype=None):
    """The cell's weights from a seed (``seed_key``), in the configuration's
    ``dtype`` unless given: normal draws scaled by 1/sqrt(fan-in) for
    matrices and embeddings, zeros for the RMSNorm ``w``; the router's
    bias, zeros in float32. Jit it: one call on the device."""
    dtype = dtype or jnp.dtype(cfg["dtype"])
    key = jax.random.wrap_key_data(jnp.asarray(key_words, jnp.uint32))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        layout(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith(BIAS):
            out.append(jnp.zeros(shape, jnp.float32))
        elif len(shape) == 1 or name.endswith(ZERO_INIT):
            out.append(jnp.zeros(shape, dtype))
        else:
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((w * _fan_in(name, shape) ** -0.5).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Matmuls at the reference's precision, and the fp8 control
# ---------------------------------------------------------------------------


def _fp8_round(x, dtype):
    x = x.astype(jnp.float32)
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q_fwd(x):
    """Forward operand rounded to scaled e4m3; gradient passes unchanged."""
    return _fp8_round(x, jnp.float8_e4m3fn)


_q_fwd.defvjp(lambda x: (_q_fwd(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_bwd(y):
    """Identity forward; the incoming gradient rounded to scaled e5m2."""
    return y


_q_bwd.defvjp(lambda y: (y, None),
              lambda _, g: (_fp8_round(g, jnp.float8_e5m2),))


def mm(eq: str, a, b, precision: str):
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _q_bwd(jnp.einsum(eq, _q_fwd(a), _q_fwd(b), precision=HIGHEST))
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# Forward and loss
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, theta):
    """Rotary embedding over the two halves of each head ([B, S, h, hd])."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def swiglu(p, h, prec):
    a = mm("bsd,df->bsf", h, p["w_in"], prec)
    f = a.shape[-1] // 2
    return mm("bsf,fd->bsd", jax.nn.silu(a[..., :f]) * a[..., f:],
              p["w_down"], prec)


def mla(cfg, s, p, h, prec):
    B, S, _ = h.shape
    H, nope, r, qk = s["H"], s["nope"], s["r"], s["nope"] + s["rope"]
    theta = cfg["rope_theta"]
    q = mm("bsd,de->bse", h, p["wq"], prec).reshape(B, S, H, qk)
    kv_a = mm("bsd,de->bse", h, p["wkv_a"], prec)
    c = rms_norm(kv_a[..., :r], p["kv_norm"], cfg["rms_norm_eps"])
    kv = mm("bsr,re->bse", c, p["wkv_b"], prec).reshape(
        B, S, H, nope + s["v"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k_rope = rope(kv_a[..., None, r:], theta)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, s["rope"]))], -1)
    v = kv[..., nope:]
    Q = min(S, QUERY_BLOCK)

    @jax.checkpoint
    def query_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q, Q, axis=1)
        scores = mm("bqhd,bkhd->bhqk", qi, k, prec) * qk ** -0.5
        causal = (i * Q + jnp.arange(Q))[:, None] >= jnp.arange(S)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return mm("bhqk,bkhd->bqhd", probs, v, prec)

    o = jax.lax.map(query_block, jnp.arange(S // Q))      # [n, B, Q, H, v]
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * s["v"])
    return mm("bse,ed->bsd", o, p["wo"], prec)


def moe(cfg, s, p, h, prec):
    """Returns (the held experts' routed part plus the shared experts, the
    routed choices per held expert [Eh], the routed choices per expert
    [E], the layer's balance loss)."""
    B, S, _ = h.shape
    E, k, lo, Eh = s["E"], s["k"], s["lo"], s["Eh"]
    scores = jax.nn.sigmoid(mm("bsd,de->bse", h, p["router"], prec))
    _, top_i = jax.lax.top_k(scores + jax.lax.stop_gradient(
        p["router_bias"]), k)
    top_s = jnp.take_along_axis(scores, top_i, -1)
    weights_ = top_s / jnp.sum(top_s, -1, keepdims=True) \
        * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)   # [B, S, k, E]
    gates = jnp.sum(weights_[..., None] * onehot, -2)[..., lo:lo + Eh]
    chosen = jnp.sum(onehot, (1, 2))                        # [B, E]
    share = jnp.sum(scores / jnp.sum(scores, -1, keepdims=True), 1)
    balance = cfg["assumed"]["aux_loss_alpha"] * jnp.mean(jnp.sum(
        chosen * (E / (k * S)) * share / S, -1))
    f = s["f"]

    @jax.checkpoint
    def one_expert(y, e):
        w_in, w_down, g = e
        a = mm("bsd,df->bsf", h, w_in, prec)
        act = jax.nn.silu(a[..., :f]) * a[..., f:]
        return y + g[..., None] * mm("bsf,fd->bsd", act, w_down, prec), None

    y, _ = jax.lax.scan(one_expert, swiglu(p["shared"], h, prec),
                        (p["w_in"], p["w_down"], jnp.moveaxis(gates, -1, 0)))
    return (y, jnp.sum(onehot[..., lo:lo + Eh], (0, 1, 2)),
            jnp.sum(chosen, 0), balance)


def row_loss(cfg, params, tokens, labels, prec):
    """The objective of a block of rows, summed over its tokens: summed
    cross-entropy plus, for each MoE layer, its balance loss times the
    block's tokens over its rows; and per MoE layer the routed choices per
    held expert [Lm, Eh] and per expert [Lm, E]."""
    s = sizes(cfg)
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]

    def attend(x, p):
        return x + mla(cfg, s, p["attn"], rms_norm(x, p["ln1"], eps), prec)

    @jax.checkpoint
    def dense_layer(x, p):
        x = attend(x, p)
        return x + swiglu(p["mlp"], rms_norm(x, p["ln2"], eps), prec), None

    @jax.checkpoint
    def moe_layer(x, p):
        x = attend(x, p)
        y, held, load, bal = moe(cfg, s, p["moe"], rms_norm(x, p["ln2"], eps),
                                 prec)
        return x + y, (held, load, bal)

    if s["Ld"]:
        x, _ = jax.lax.scan(dense_layer, x, params["dense_blocks"])
    x, (held, load, bal) = jax.lax.scan(moe_layer, x, params["blocks"])
    h = rms_norm(x, params["ln_f"], eps)
    logits = mm("bsd,dv->bsv", h, params["unembed"][:, :s["V"]], prec)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked) + labels.shape[1] * jnp.sum(bal), \
        (held, load)


# ---------------------------------------------------------------------------
# The training step: gradient over all rows, global-norm clip, AdamW, bias
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr``, then a cosine down to ``min_lr_frac``."""
    w, T = opt["warmup_steps"], opt["total_steps"]
    if step < w:
        return opt["lr"] * step / max(1, w)
    prog = min(max((step - w) / max(1, T - w), 0.0), 1.0)
    m = opt["min_lr_frac"]
    return opt["lr"] * (m + (1 - m) * 0.5 * (1 + math.cos(math.pi * prog)))


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


class Reference:
    """Three-step (or n-step) float32 training of the cell's model.

    ``fault`` plants a fault for calibration: ``"half_batch"`` (the loss is
    the mean over the first half of the rows), ``"altered_token"`` (each
    row's first token replaced by another).
    """

    def __init__(self, cfg: dict, chips: int, precision: str = "float32",
                 fault: str | None = None):
        self.cfg, self.chips = cfg, chips
        self.precision, self.fault = precision, fault
        self.opt = opt = cfg["optimizer"]
        self.gamma = cfg["assumed"]["bias_update_speed"]

        @jax.jit
        def row_grad(params, tokens, labels):
            (obj, aux), g = jax.value_and_grad(
                lambda p: row_loss(cfg, p, tokens, labels, precision),
                has_aux=True)(params)
            return obj, aux, g

        self._row_grad = row_grad

        @partial(jax.jit, donate_argnums=(0,))
        def add(acc, g):
            return jax.tree.map(jnp.add, acc, g)

        self._add = add

        @partial(jax.jit, donate_argnums=(0,))
        def clip(g, n_tok):
            g = jax.tree.map(lambda x: x / n_tok, g)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(
                1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9)), g)
            return g, leaf_norms(g)

        self._clip = clip

        @partial(jax.jit, static_argnames=("decay",), donate_argnums=(0, 1, 2))
        def adam(p, m, v, g, lr, t, decay):
            b1, b2 = opt["betas"]
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            delta = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                            + opt["eps"])
            if decay:
                delta = delta + opt["weight_decay"] * p
            return p - lr * delta, m, v

        self._adam = adam

        @partial(jax.jit, donate_argnums=(0,))
        def bias_step(bias, load):
            excess = load - jnp.mean(load, -1, keepdims=True)
            return (bias + self.gamma * jnp.sign(-excess),
                    jnp.sqrt(jnp.sum(jnp.square(excess))))

        self._bias_step = bias_step

    def run(self, make_weights, batches: list, device=None) -> dict:
        """``make_weights()``: the cell's weights as the program got them
        (called twice, so that no copy stays alive); ``batches``: the
        program's first steps' batches (numpy ``tokens``/``labels``
        [B, S]). Returns each step's loss, the clipped first gradient's
        leaf norms, the leaf norms of the parameters' change over all
        steps, the routed choices counted per (chip, MoE layer, held
        expert) at each step, and the device's peak bytes in use after
        the run."""
        put = partial(jax.device_put, device=device)
        params = jax.tree.map(lambda x: put(x).astype(jnp.float32),
                              make_weights())
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        names = [jax.tree_util.keystr(p) for p, _ in flat]
        bias_name, = (n for n in names if n.endswith(BIAS))
        # Adam's moments live on the host, one array per leaf.
        m = [np.zeros(x.shape, np.float32) for _, x in flat]
        v = [np.zeros(x.shape, np.float32) for _, x in flat]
        losses, grad_norms, counts = [], None, []
        for t, batch in enumerate(batches, start=1):
            B = batch["tokens"].shape[0]
            if self.fault == "altered_token":
                tok = batch["tokens"].copy()
                tok[:, 0] = tok[:, 0] % (self.cfg["vocab_size"] - 1) + 1
                batch = {"tokens": tok, "labels": batch["labels"]}
            rows = range(B // 2) if self.fault == "half_batch" else range(B)
            acc, total, per_chip, load = None, 0.0, [], 0.0
            for r in rows:
                chip = r // (B // self.chips)
                obj, (held, row_load), g = self._row_grad(
                    params, put(batch["tokens"][r:r + 1]),
                    put(batch["labels"][r:r + 1]))
                acc = g if acc is None else self._add(acc, g)
                total += float(obj)
                load = load + row_load
                per_chip.append((chip, np.asarray(held)))
            n_tok = len(rows) * batch["tokens"].shape[1]
            g, gn = self._clip(acc, float(n_tok))
            del acc
            leaves = jax.tree.leaves(params)
            g = jax.tree.leaves(g)
            lr = lr_at(self.opt, t)
            for i, name in enumerate(names):
                if name.endswith(BIAS):
                    continue
                p_i, m_i, v_i = self._adam(
                    leaves[i], put(m[i]), put(v[i]), g[i], lr, float(t),
                    decay=not name.endswith(NOT_DECAYED))
                leaves[i], m[i], v[i] = p_i, np.asarray(m_i), np.asarray(v_i)
                g[i] = None
            del g
            params = jax.tree_util.tree_unflatten(treedef, leaves)
            params["blocks"]["moe"]["router_bias"], excess = \
                self._bias_step(params["blocks"]["moe"]["router_bias"], load)
            losses.append(total / n_tok)
            if grad_norms is None:
                grad_norms = {k: float(x) for k, x in gn.items()}
                grad_norms[bias_name] = float(excess)
            step_counts = np.zeros((self.chips,) + per_chip[0][1].shape)
            for chip, c in per_chip:
                step_counts[chip] += c
            counts.append(step_counts)
        del m, v
        change = leaf_norms(jax.tree.map(
            lambda a, b: a - put(b).astype(jnp.float32), params,
            make_weights()))
        stats = (device or jax.devices()[0]).memory_stats() or {}
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": {k: float(x) for k, x in change.items()},
                "routed_counts": counts,
                "peak_bytes": stats.get("peak_bytes_in_use")}
