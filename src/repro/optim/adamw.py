"""AdamW with cosine schedule, global-norm clipping and grad accumulation.

Self-contained (no optax in this environment). State is a pytree of the same
structure as params — m/v in fp32 — so the checkpoint layer and sharding
rules apply uniformly. ``grad_transform`` hooks (e.g. cross-pod gradient
compression) run before the moment update.

Leaves named in ``STATE_LEAVES`` are state that the train step moves by its
own rule (the MoE router's selection bias): they stay float32, and the
optimizer leaves them, their moments and their masters alone and out of the
clip's global norm (``launch/steps.py`` moves the bias and its first moment).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    betas: tuple = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1


def schedule(oc: OptConfig, step):
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(1.0, oc.warmup_steps)
    prog = (step - oc.warmup_steps) / jnp.maximum(
        1.0, oc.total_steps - oc.warmup_steps)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = oc.min_lr_frac + (1 - oc.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return oc.lr * jnp.where(step < oc.warmup_steps, warm, cos)


STATE_LEAVES = ("router_bias",)


def trained(path) -> bool:
    """Whether the optimizer updates the leaf at ``path``."""
    return getattr(path[-1], "key", None) not in STATE_LEAVES if path \
        else True


def cast_params(params, dtype=jnp.bfloat16):
    """Compute-precision copy of the parameter tree (float leaves only;
    ``STATE_LEAVES`` stay as they are)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, p: p.astype(dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) and trained(path) else p,
        params)


def init_opt_state(params):
    """m/v moments + fp32 master weights (params at the step boundary are
    the bf16 compute copies; masters only appear in the update math)."""
    zeros = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": zeros,
            "v": jax.tree.map(jnp.copy, zeros),
            "master": jax.tree.map(
                lambda p: p.astype(jnp.float32), params),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree):
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm):
    """Clip over the trained leaves; ``STATE_LEAVES`` pass unchanged."""
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    norm = global_norm([g for path, g in flat if trained(path)])
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map_with_path(
        lambda path, g: g * scale.astype(g.dtype) if trained(path) else g,
        grads), norm


@jax.named_scope("optimizer")
def apply_updates(params, grads, state, oc: OptConfig,
                  grad_transform: Optional[Callable] = None):
    """One AdamW step on the fp32 masters; returns the refreshed compute
    (bf16) params. Returns (new_params, new_state, metrics). The whole
    update, clip and global norm included, runs under the ``optimizer``
    named scope."""
    if grad_transform is not None:
        grads = grad_transform(grads)
    grads, gnorm = clip_by_global_norm(grads, oc.clip_norm)
    step = state["step"] + 1
    lr = schedule(oc, step)
    b1, b2 = oc.betas
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    # Separate maps (not one map returning tuples): param trees may contain
    # tuple nodes (hybrid 'super' stacks), so tuple leaves are ambiguous.
    def new_m_fn(g, m):
        return b1 * m + (1 - b1) * g.astype(jnp.float32)

    def new_v_fn(g, v):
        g = g.astype(jnp.float32)
        return b2 * v + (1 - b2) * g * g

    def only_trained(fn):
        """``fn(g, state)`` on trained leaves; the state unchanged else."""
        return lambda path, g, st: fn(g, st) if trained(path) else st

    new_m = jax.tree_util.tree_map_with_path(only_trained(new_m_fn), grads,
                                             state["m"])
    new_v = jax.tree_util.tree_map_with_path(only_trained(new_v_fn), grads,
                                             state["v"])

    def upd(master, m, v):
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + oc.eps)
        if master.ndim >= 2:  # decoupled weight decay on matrices only
            delta = delta + oc.weight_decay * master
        return master - lr * delta

    new_master = jax.tree_util.tree_map_with_path(
        lambda path, mst, m, v: upd(mst, m, v) if trained(path) else mst,
        state["master"], new_m, new_v)
    new_params = jax.tree.map(
        lambda p, mst: mst.astype(p.dtype), params, new_master)
    return new_params, {"m": new_m, "v": new_v, "master": new_master,
                        "step": step}, {"grad_norm": gnorm, "lr": lr}


def accumulate_grads(loss_and_grad_fn, params, microbatches):
    """Microbatch gradient accumulation via lax.scan (fixed microbatch dim).

    ``microbatches``: pytree with leading [n_micro, ...] dims.
    ``loss_and_grad_fn(params, mb)`` returns ``((loss, aux), grads)``, as
    ``jax.value_and_grad(..., has_aux=True)`` does. Returns ``((mean loss,
    aux summed over microbatches), mean grads)``.
    """
    def body(acc, mb):
        out, grads = loss_and_grad_fn(params, mb)
        acc_g, acc_out = acc
        return (jax.tree.map(jnp.add, acc_g, grads),
                jax.tree.map(jnp.add, acc_out, out)), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    first = jax.tree.map(lambda a: a[0], microbatches)
    out0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
        lambda: loss_and_grad_fn(params, first)[0]))
    (g, (loss, aux)), _ = jax.lax.scan(body, (zero, out0), microbatches)
    n = jax.tree.leaves(microbatches)[0].shape[0]
    return (loss / n, aux), jax.tree.map(lambda x: x / n, g)
