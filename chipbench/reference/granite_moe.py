"""Plain reference of the granite MoE decoder's training step.

Written from the architecture's equations (GraniteMoe: pre-norm decoder,
RMSNorm, rotary grouped-query attention, a softmax top-k router over the
experts with the top-k weights renormalised, SwiGLU experts, cross-entropy
over the vocabulary) and from AdamW's. The scalar keys of the configuration
file (``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier``, ``logits_scaling``, ``tie_word_embeddings``,
``rms_norm_eps``, ``rope_theta``) enter as the equations state them. It
imports nothing of the program under test. Every matmul runs in float32 at
``Precision.HIGHEST``; no token is dropped.

``precision="fp8"`` is the control: each matmul's operands rounded to
float8 e4m3 with a per-tensor scale, and each matmul's output gradient to
float8 e5m2 in the backward pass.

The parameter tree has the layout the program takes (stacked layers,
experts padded to ``deployment.padding_experts`` that the router never
picks, the vocabulary padded to ``program.vocab_pad`` rows that no token
and no label uses), so the benchmark's weights feed both. RMSNorm gains are
stored as ``1 + w`` with ``w`` starting at 0.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Sizes, layout and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    pad = cfg["program"]["vocab_pad"]
    V = cfg["vocab_size"]
    return {
        "d": d, "H": H, "K": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // H, "f": cfg["intermediate_size"],
        "L": cfg["num_hidden_layers"], "V": V,
        "Vp": int(math.ceil(V / pad) * pad),
        "E": cfg["num_local_experts"],
        "Et": cfg["num_local_experts"]
        + cfg["deployment"]["padding_experts"],
        "k": cfg["num_experts_per_tok"],
    }


def layout(cfg: dict) -> dict:
    """Shape of every parameter (name -> shape), in the program's tree."""
    s = sizes(cfg)
    d, L, hd, f, Et = s["d"], s["L"], s["hd"], s["f"], s["Et"]
    tree = {
        "embed": (s["Vp"], d),
        "ln_f": (d,),
        "blocks": {
            "attn": {"wq": (L, d, s["H"] * hd), "wk": (L, d, s["K"] * hd),
                     "wv": (L, d, s["K"] * hd), "wo": (L, s["H"] * hd, d)},
            "moe": {"router": (L, d, Et), "w_in": (L, Et, d, 2 * f),
                    "w_down": (L, Et, f, d)},
            "ln1": (L, d), "ln2": (L, d),
        },
    }
    if not cfg["tie_word_embeddings"]:
        tree["unembed"] = (d, s["Vp"])
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


def weights(cfg: dict, key_words, dtype=jnp.bfloat16):
    """The cell's weights from a seed (``seed_key``): normal draws scaled
    by 1/sqrt(fan-in) for matrices and embeddings, zeros for the RMSNorm
    ``w``. Jit it: one call on the device."""
    key = jax.random.wrap_key_data(jnp.asarray(key_words, jnp.uint32))
    shapes = layout(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if len(shape) == 1 or name.endswith(("['ln1']", "['ln2']")):
            out.append(jnp.zeros(shape, dtype))
            continue
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
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


def attention(cfg, s, p, h, prec):
    B, S, _ = h.shape
    q = mm("bsd,de->bse", h, p["wq"], prec).reshape(B, S, s["H"], s["hd"])
    k = mm("bsd,de->bse", h, p["wk"], prec).reshape(B, S, s["K"], s["hd"])
    v = mm("bsd,de->bse", h, p["wv"], prec).reshape(B, S, s["K"], s["hd"])
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    rep = s["H"] // s["K"]                  # query head j uses kv head j//rep
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    scores = mm("bqhd,bkhd->bhqk", q, k, prec) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    o = mm("bhqk,bkhd->bqhd", probs, v, prec).reshape(B, S, -1)
    return mm("bse,ed->bsd", o, p["wo"], prec)


def moe(cfg, s, p, h, prec):
    """Returns (output, per-expert count of routed choices)."""
    logits = mm("bsd,de->bse", h, p["router"][:, :s["E"]], prec)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_i = jax.lax.top_k(probs, s["k"])
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    onehot = jax.nn.one_hot(top_i, s["E"], dtype=jnp.float32)
    gates = jnp.sum(top_p[..., None] * onehot, -2)
    counts = jnp.sum(onehot, (0, 1, 2))
    f = s["f"]

    @jax.checkpoint
    def one_expert(y, e):
        w_in, w_down, g = e
        a = mm("bsd,df->bsf", h, w_in, prec)
        act = jax.nn.silu(a[..., :f]) * a[..., f:]
        return y + g[..., None] * mm("bsf,fd->bsd", act, w_down, prec), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (p["w_in"][:s["E"]], p["w_down"][:s["E"]],
                         jnp.moveaxis(gates, -1, 0)))
    return y, counts


def row_loss(cfg, params, tokens, labels, prec):
    """Summed cross-entropy of a block of rows, and the routed-choice
    counts per layer and expert."""
    s = sizes(cfg)
    eps = cfg["rms_norm_eps"]
    res = cfg["residual_multiplier"]
    x = params["embed"][tokens] * cfg["embedding_multiplier"]

    @jax.checkpoint
    def layer(x, p):
        x = x + res * attention(cfg, s, p["attn"],
                                rms_norm(x, p["ln1"], eps), prec)
        y, counts = moe(cfg, s, p["moe"], rms_norm(x, p["ln2"], eps), prec)
        return x + res * y, counts

    x, counts = jax.lax.scan(layer, x, params["blocks"])
    h = rms_norm(x, params["ln_f"], eps)
    unembed = (params["embed"].T if cfg["tie_word_embeddings"]
               else params["unembed"])
    logits = mm("bsd,dv->bsv", h, unembed[:, :s["V"]], prec) \
        / cfg["logits_scaling"]
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked), counts


# ---------------------------------------------------------------------------
# The training step: gradient over all rows, global-norm clip, AdamW
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr``, then a cosine down to ``min_lr_frac``."""
    w, T = opt["warmup_steps"], opt["total_steps"]
    if step < w:
        return opt["lr"] * step / max(1, w)
    prog = min(max((step - w) / max(1, T - w), 0.0), 1.0)
    m = opt["min_lr_frac"]
    return opt["lr"] * (m + (1 - m) * 0.5 * (1 + math.cos(math.pi * prog)))


def _is_matrix(path) -> bool:
    """Weight decay applies to matrices; norm gains are vectors per layer."""
    name = jax.tree_util.keystr(path)
    return not name.endswith(("['ln1']", "['ln2']", "['ln_f']"))


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
        self.opt = cfg["optimizer"]

        @jax.jit
        def row_grad(params, tokens, labels):
            (nll, counts), g = jax.value_and_grad(
                lambda p: row_loss(cfg, p, tokens, labels, precision),
                has_aux=True)(params)
            return nll, counts, g

        self._row_grad = row_grad

        @partial(jax.jit, donate_argnums=(0,))
        def add(acc, g):
            return jax.tree.map(jnp.add, acc, g)

        self._add = add

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def adam(params, m, v, g, n_tok, lr, t):
            g = jax.tree.map(lambda x: x / n_tok, g)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(
                1.0, self.opt["clip_norm"] / jnp.maximum(norm, 1e-9)), g)
            b1, b2 = self.opt["betas"]
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
            bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

            def upd(path, p, m, v):
                delta = (m / bc1) / (jnp.sqrt(v / bc2) + self.opt["eps"])
                if _is_matrix(path):
                    delta = delta + self.opt["weight_decay"] * p
                return p - lr * delta

            params = jax.tree_util.tree_map_with_path(upd, params, m, v)
            return params, m, v, leaf_norms(g)

        self._adam = adam

    def run(self, make_weights, batches: list, device=None) -> dict:
        """``make_weights()``: the cell's weights as the program got them
        (called twice, so that no copy stays alive); ``batches``: the
        program's first steps' batches (numpy ``tokens``/``labels``
        [B, S]). Returns each step's loss, the clipped first gradient's
        leaf norms, the leaf norms of the parameters' change over all
        steps, and the routed choices counted per (chip, layer, expert) at
        each step."""
        put = partial(jax.device_put, device=device)
        params = jax.tree.map(lambda x: put(x).astype(jnp.float32),
                              make_weights())
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms, counts = [], None, []
        for t, batch in enumerate(batches, start=1):
            B = batch["tokens"].shape[0]
            if self.fault == "altered_token":
                tok = batch["tokens"].copy()
                tok[:, 0] = tok[:, 0] % (self.cfg["vocab_size"] - 1) + 1
                batch = {"tokens": tok, "labels": batch["labels"]}
            rows = range(B // 2) if self.fault == "half_batch" else range(B)
            acc, nll, per_chip = None, 0.0, []
            for r in rows:
                chip = r // (B // self.chips)
                n, c, g = self._row_grad(
                    params, put(batch["tokens"][r:r + 1]),
                    put(batch["labels"][r:r + 1]))
                acc = g if acc is None else self._add(acc, g)
                nll += float(n)
                per_chip.append((chip, np.asarray(c)))
            n_tok = len(rows) * batch["tokens"].shape[1]
            params, m, v, gn = self._adam(
                params, m, v, acc, float(n_tok),
                lr_at(self.opt, t), float(t))
            del acc
            losses.append(nll / n_tok)
            if grad_norms is None:
                grad_norms = {k: float(x) for k, x in gn.items()}
            step_counts = np.zeros((self.chips,) + per_chip[0][1].shape)
            for chip, c in per_chip:
                step_counts[chip] += c
            counts.append(step_counts)
        del m, v
        change = leaf_norms(jax.tree.map(
            lambda a, b: a - put(b).astype(jnp.float32), params,
            make_weights()))
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": {k: float(x) for k, x in change.items()},
                "routed_counts": counts}
