"""jit-able train / prefill / decode steps wired to sharding rules + EP.

``make_steps(cfg, mesh, …)`` returns closures whose in/out shardings come
from ``ShardingRules``; the MoE EP path and the sequence-parallel activation
constraint are installed via the ambient contexts at *trace* time, keeping
the model code mesh-agnostic (the paper's low-intrusion integration).

The train step's metrics are ``loss``, ``grad_norm``, ``lr`` and, for a
fixed-capacity MoE model, the int32 counters ``moe_routed``, ``moe_dropped``
and ``moe_slots`` (``models.moe.routing_counts``; ``moe_not_held`` too where
a layer holds a share of the experts) summed over layers and chips. With a
sigmoid router the step also reports ``moe_balance_loss`` (part of
``loss``) and moves each MoE layer's router bias by its load
(``models.moe.bias_step``) after the optimizer, which leaves the bias alone;
the bias's slot of Adam's first moment keeps its running excess load.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import model as M
from repro.models.moe import bias_step
from repro.optim import adamw
from repro.parallel.ctx import (activation_sharding, flash_decode_context,
                                head_sharding, moe_impl_context)
from repro.parallel.ep import EPConfig, make_moe_ep
from repro.parallel.sharding import ShardingRules


@dataclasses.dataclass
class StepFns:
    train_step: object
    prefill_step: object
    decode_step: object
    rules: ShardingRules
    ep_cfg: Optional[EPConfig]
    # Set when the dropless data-dependent path is active: holds the
    # process-level SSC cache handle (``dropless.cache.info()`` /
    # ``step_stats()`` for recompile-rate monitoring).
    dropless: Optional[object] = None


def make_steps(cfg, mesh, *, opt: Optional[adamw.OptConfig] = None,
               ep: Optional[EPConfig] = None,
               seq_parallel: bool = True,
               accum_steps: int = 0,
               fsdp: Optional[bool] = None,
               mode: str = "tp_sp",
               dropless=None,
               grad_transform=None) -> StepFns:
    """Build the jit-able step closures.

    ``dropless``: a :class:`repro.launch.dropless.DroplessConfig` switches
    the *training* MoE path from fixed-capacity execution to dropless,
    data-dependent schedule compilation — each batch's actual router output
    becomes a RoutingPlan whose (shape-bucketed) schedule is fetched from the
    process-level SSC cache and executed plan-sized. Serving steps keep the
    fixed-capacity/EP implementation (static shapes for decode).
    """
    rules = ShardingRules(cfg, mesh, fsdp=fsdp, mode=mode)
    if mode == "ep_dp" and ep is not None:
        ep = dataclasses.replace(ep, dp_batch=True)
    moe_impl = (make_moe_ep(mesh, ep, cfg.act)
                if (ep is not None and cfg.family == "moe") else None)
    dropless_moe = None
    if dropless is not None and cfg.family == "moe":
        from repro.launch.dropless import make_moe_dropless
        dropless_moe = make_moe_dropless(cfg, dropless)
    train_moe_impl = dropless_moe.impl if dropless_moe else moe_impl
    opt = opt or adamw.OptConfig()
    if accum_steps == 0:
        # Default policy: microbatch the big archs so train activations fit
        # HBM (grad accumulation is the standard production lever here).
        n_params = cfg.param_count()
        accum_steps = 8 if n_params > 100e9 else (4 if n_params > 10e9 else 1)

    import contextlib

    def _ctx(B, S):
        if rules.mode != "tp_sp":
            return contextlib.ExitStack()   # DP modes: no SP/TP constraints
        sp = (rules.act_spec(B) if seq_parallel and S > 1
              and S % rules.model_n == 0 else None)
        hs = None
        if cfg.n_heads and cfg.n_heads % rules.model_n == 0 and S > 1:
            hs = P(rules._batch_axis(B), None, "model", None)
        stack = contextlib.ExitStack()
        stack.enter_context(activation_sharding(sp))
        stack.enter_context(head_sharding(hs))
        return stack

    # ---- training ----------------------------------------------------------
    def train_step(params, opt_state, batch):
        B, S = batch["labels"].shape

        def loss_of(p, b):
            with _ctx(b["labels"].shape[0], S), \
                    moe_impl_context(train_moe_impl):
                return M.loss_fn(cfg, p, b, with_counts=True)

        grad_fn = jax.value_and_grad(loss_of, has_aux=True)
        if accum_steps > 1 and B % accum_steps == 0:
            mb = jax.tree.map(
                lambda a: a.reshape((accum_steps, B // accum_steps)
                                    + a.shape[1:]), batch)
            (lv, counts), grads = adamw.accumulate_grads(grad_fn, params,
                                                         mb)
        else:
            (lv, counts), grads = grad_fn(params, batch)
        # Pin gradient shardings to the parameter shardings so the
        # backward-scan accumulators don't materialize unsharded (matters
        # for FSDP expert weights: 21 GB/device without this).
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g: jax.lax.with_sharding_constraint(
                g, rules.param_spec(path, g.shape)), grads)
        params2, opt_state2, metrics = adamw.apply_updates(
            params, grads, opt_state, opt, grad_transform=grad_transform)
        if "moe_load" in counts:
            counts = dict(counts)
            moment = opt_state2["m"]["blocks"]["moe"]
            bias, moment["router_bias"] = bias_step(
                opt_state2["master"]["blocks"]["moe"]["router_bias"],
                moment["router_bias"], counts.pop("moe_load"),
                cfg.moe.bias_speed, opt.betas[0])
            for tree in (params2, opt_state2["master"]):
                tree["blocks"]["moe"]["router_bias"] = bias
        metrics["loss"] = lv
        # The MoE layers' counters (moe_routed, moe_dropped, moe_slots),
        # summed over layers and chips; absent where the MoE path records
        # none (the dropless path).
        metrics.update(counts)
        # Surface per-step SSC cache deltas (recompiles this step, hit
        # count, occupancy). Host-side counters only exist eagerly; under
        # jit read ``fns.dropless.cache.info()`` from the training loop.
        if dropless_moe is not None and not isinstance(lv, jax.core.Tracer):
            for k, v in dropless_moe.step_stats().items():
                metrics[f"ssc_{k}"] = v
        return params2, opt_state2, metrics

    # ---- serving -----------------------------------------------------------
    def prefill_step(params, batch, max_len: int):
        tokens = batch.get("tokens", batch.get("features"))
        B, S = tokens.shape[0], tokens.shape[1]
        with _ctx(B, S), moe_impl_context(moe_impl):
            if cfg.family == "audio":
                return M.forward(cfg, params, batch), None
            return M.prefill(cfg, params, batch, max_len)

    # Flash-decoding: sharded one-token attention for seq-sharded caches.
    fd_impl = None
    if rules.model_n > 1 and cfg.n_heads:
        from repro.parallel.flash_decode import make_flash_decode
        fd_impl = make_flash_decode(mesh, "model")

    def decode_step(params, token, cache):
        with moe_impl_context(moe_impl), flash_decode_context(fd_impl):
            return M.decode_step(cfg, params, token, cache)

    return StepFns(train_step=train_step, prefill_step=prefill_step,
                   decode_step=decode_step, rules=rules, ep_cfg=ep,
                   dropless=dropless_moe)


# ---------------------------------------------------------------------------
# Sharding-annotated jit wrappers (used by the launcher and the dry-run).
# ---------------------------------------------------------------------------


def state_shardings(rules: ShardingRules, params_shape):
    """(param shardings, optimizer-state shardings) for a params shape tree.
    ZeRO-1 modes shard the optimizer state even where params replicate."""
    ps = rules.param_shardings(params_shape)
    oss = rules.opt_state_shardings(params_shape)
    return ps, {"m": oss, "v": oss, "master": oss,
                "step": NamedSharding(rules.mesh, P())}


def jit_train_step(fns: StepFns, params_shape, batch_shapes):
    rules = fns.rules
    ps, os_ = state_shardings(rules, params_shape)
    bs = rules.batch_shardings(batch_shapes)
    return jax.jit(
        fns.train_step,
        in_shardings=(ps, os_, bs),
        out_shardings=(ps, os_, None),
        donate_argnums=(0, 1))


def jit_prefill_step(fns: StepFns, params_shape, batch_shapes,
                     max_len: int):
    rules = fns.rules
    ps = rules.param_shardings(params_shape)
    bs = rules.batch_shardings(batch_shapes)
    return jax.jit(partial(fns.prefill_step, max_len=max_len),
                   in_shardings=(ps, bs), out_shardings=None)


def jit_decode_step(fns: StepFns, params_shape, token_shape, cache_shape):
    rules = fns.rules
    ps = rules.param_shardings(params_shape)
    ts = NamedSharding(rules.mesh,
                       rules.batch_spec({"tokens": token_shape})["tokens"])
    cs = rules.cache_shardings(cache_shape)
    return jax.jit(fns.decode_step,
                   in_shardings=(ps, ts, cs),
                   out_shardings=(None, cs),
                   donate_argnums=(2,))
