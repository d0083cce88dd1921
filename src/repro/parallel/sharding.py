"""Per-architecture sharding rules: param / batch / cache PartitionSpecs.

Policy (DESIGN.md §5):

* ``data`` (+ ``pod``) axes — batch/data parallelism. ``pod`` is the outer
  DP axis so cross-pod traffic is gradient all-reduce only.
* ``model`` axis — tensor parallelism for dense stacks (output-dim sharding
  with divisibility fallbacks), expert parallelism for MoE stacks (expert
  dim sharding; experts are padded so E % model == 0).
* Large archs (> ``FSDP_THRESHOLD`` params) additionally shard the weight's
  other dim over ``data`` (ZeRO-3 style; XLA inserts the all-gathers).
* Activations: residual stream is sequence-sharded over ``model`` between
  blocks (Megatron sequence parallelism) via an ambient constraint context.
* Decode KV caches: batch over dp, sequence over ``model`` (flash-decoding
  style — softmax reductions over the sharded dim become all-reduces).

Optimizer state (fp32 m/v) inherits the param specs leaf-for-leaf.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import dp_axes
from repro.models.model import ModelConfig

FSDP_THRESHOLD = 10e9


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


class ShardingRules:
    """``mode``:

    * ``tp_sp`` — tensor parallel over 'model' + sequence-parallel
      activations (the initial Megatron-style mapping; the paper-faithful
      baseline recorded in EXPERIMENTS.md §Perf).
    * ``zero1`` — pure data parallelism over all mesh axes: params
      replicated, optimizer state sharded (ZeRO-1), batch over
      (pod, data, model). The right mapping for ≲3B dense archs on a
      256-chip pod — the only remaining collective is the gradient
      all-reduce.
    * ``ep_dp`` — zero1 for the dense trunk, experts sharded over 'model'
      (EP spans DP ranks: the paper's own dp=32/ep=32 production layout).
    """

    def __init__(self, cfg: ModelConfig, mesh, fsdp: bool | None = None,
                 mode: str = "tp_sp"):
        self.cfg = cfg
        self.mesh = mesh
        self.mode = mode
        self.dp = dp_axes(mesh)
        self.model_n = mesh.shape.get("model", 1)
        self.fsdp = (cfg.param_count() > FSDP_THRESHOLD
                     if fsdp is None else fsdp)
        self.data_n = mesh.shape.get("data", 1)
        self.all_axes = tuple(mesh.axis_names)

    # -- helpers -----------------------------------------------------------
    def _m(self, dim: int):
        """'model' if divisible else None."""
        return "model" if _div(dim, self.model_n) else None

    def _f(self, dim: int):
        """FSDP ('data') if enabled and divisible else None."""
        return "data" if (self.fsdp and _div(dim, self.data_n)) else None

    # -- parameter rules ----------------------------------------------------
    def param_spec(self, path: tuple[str, ...], shape: tuple[int, ...]) -> P:
        keys = [getattr(k, "key", getattr(k, "idx", k)) for k in path]
        name = keys[-1] if keys else ""
        stacked = any(k in ("blocks", "super", "dense_blocks") for k in keys)
        lead = (None,) if stacked else ()
        if self.mode in ("zero1", "ep_dp"):
            body = self._param_spec_dp(name, shape[len(lead):])
        else:
            body = self._param_spec_body(name, shape[len(lead):])
        return P(*(lead + body))

    def _param_spec_dp(self, name: str, s: tuple[int, ...]) -> tuple:
        """DP modes: replicate everything except MoE experts in ep_dp."""
        if (self.mode == "ep_dp" and name in ("w_in", "w_down")
                and len(s) == 3):
            return (self._m(s[0]), None, None)   # experts over 'model'
        return (None,) * len(s)

    def opt_state_spec(self, path, shape) -> P:
        """ZeRO-1: moments/master sharded over as many axes as divide."""
        if self.mode not in ("zero1", "ep_dp"):
            return self.param_spec(path, shape)
        base = list(self.param_spec(path, shape))
        used = {a for a in base if a}
        free = [a for a in self.all_axes if a not in used]
        # shard the largest unsharded dim over the free axes (greedy).
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in dims:
            if base[i] is not None:
                continue
            take = []
            rem = shape[i]
            for a in free:
                n = self.mesh.shape[a]
                if rem % n == 0:
                    take.append(a)
                    rem //= n
            if take:
                base[i] = tuple(take)
                break
        return P(*base)

    def opt_state_shardings(self, params_shape):
        def spec(path, leaf):
            return NamedSharding(self.mesh,
                                 self.opt_state_spec(path, leaf.shape))
        return jax.tree_util.tree_map_with_path(spec, params_shape)

    def _param_spec_body(self, name: str, s: tuple[int, ...]) -> tuple:
        cfg = self.cfg
        if name == "embed":
            return (self._m(s[0]), None)
        if name == "unembed":
            return (None, self._m(s[1]))
        if name in ("wq", "wk", "wv"):
            return (self._f(s[0]), self._m(s[1]))
        if name == "wo":
            return (self._m(s[0]), self._f(s[1]))
        if name in ("bq", "bk", "bv"):
            return (self._m(s[0]),)
        if name == "w_in" and len(s) == 3:    # MoE experts [E, d, 2f]
            return (self._m(s[0]), self._f(s[1]), None)
        if name == "w_down" and len(s) == 3:  # [E, f, d]
            return (self._m(s[0]), None, self._f(s[2]))
        if name == "w_in":
            return (self._f(s[0]), self._m(s[1]))
        if name == "w_down":
            return (self._m(s[0]), self._f(s[1]))
        if name == "router":
            return (None, None)
        if name == "in_proj":                 # ssm [d, zxbcdt]
            return (self._f(s[0]), self._m(s[1]))
        if name in ("conv_w", "conv_b"):
            return (None,) * (len(s) - 1) + (self._m(s[-1]),)
        if name == "out_proj":
            return (self._m(s[0]), self._f(s[1]))
        if name == "norm_w" and len(s) == 1 and s[0] != cfg.d_model:
            return (self._m(s[0]),)
        if name in ("in_x", "in_y"):          # rglru [d, w]
            return (self._f(s[0]), self._m(s[1]))
        if name in ("gate_a", "gate_x"):      # [w, w]
            return (None, self._m(s[1]))
        if name in ("gate_a_b", "gate_x_b", "lam"):
            return (self._m(s[0]),)
        if name == "out" and len(s) == 2:     # rglru out [w, d]
            return (self._m(s[0]), self._f(s[1]))
        if name == "feat_proj":
            return (None, None)
        # norms, scalars, A_log, D, dt_bias, ln*: replicate
        return (None,) * len(s)

    def param_shardings(self, params_shape):
        """Pytree of NamedShardings matching a params (shape) tree."""
        def spec(path, leaf):
            return NamedSharding(self.mesh,
                                 self.param_spec(path, leaf.shape))
        return jax.tree_util.tree_map_with_path(spec, params_shape)

    # -- batch rules ---------------------------------------------------------
    def _batch_axis(self, B: int):
        """Shard batch over as many (mode-appropriate) axes as divide it."""
        pool = (self.all_axes if self.mode in ("zero1", "ep_dp")
                else self.dp)
        axes = []
        rem = B
        for a in pool:
            n = self.mesh.shape[a]
            if rem % n == 0:
                axes.append(a)
                rem //= n
        return tuple(axes) if axes else None

    def batch_spec(self, batch_shapes: dict) -> dict:
        out = {}
        for k, v in batch_shapes.items():
            B = v.shape[0]
            ba = self._batch_axis(B)
            if k in ("tokens", "labels"):
                seq_m = ("model" if self.mode == "tp_sp"
                         and len(v.shape) > 1
                         and _div(v.shape[1], self.model_n)
                         and v.shape[1] > 1 else None)
                out[k] = P(ba, seq_m) if len(v.shape) == 2 else P(ba)
            elif k == "features":
                seq_m = (self._m(v.shape[1]) if self.mode == "tp_sp"
                         else None)
                out[k] = P(ba, seq_m, None)
            elif k == "patches":
                out[k] = P(ba, None, None)
            else:
                out[k] = P(*([ba] + [None] * (len(v.shape) - 1)))
        return out

    def batch_shardings(self, batch_shapes: dict) -> dict:
        return {k: NamedSharding(self.mesh, s)
                for k, s in self.batch_spec(batch_shapes).items()}

    # -- activation constraint (sequence parallelism) -------------------------
    def act_spec(self, B: int) -> P:
        return P(self._batch_axis(B), "model", None)

    # -- cache rules -----------------------------------------------------------
    def cache_spec(self, path, shape) -> P:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        name = keys[-1] if keys else ""
        stacked = len(shape) > 0
        if name in ("k", "v"):
            # [L, B, S, K, hd] (stacked) or [B, S, K, hd]
            lead = (None,) if len(shape) == 5 else ()
            B, S = shape[len(lead)], shape[len(lead) + 1]
            return P(*(lead + (self._batch_axis(B),
                               "model" if _div(S, self.model_n) else None,
                               None, None)))
        if name == "len":
            return P(*((None,) * len(shape)))
        if name == "ssm":
            lead = (None,) if len(shape) == 5 else ()
            B, H = shape[len(lead)], shape[len(lead) + 1]
            return P(*(lead + (self._batch_axis(B), self._m(H), None, None)))
        if name == "conv":
            lead = (None,) if len(shape) == 4 else ()
            B = shape[len(lead)]
            C = shape[-1]
            return P(*(lead + (self._batch_axis(B), None, self._m(C))))
        if name == "h":
            lead = (None,) if len(shape) == 3 else ()
            B, W = shape[len(lead)], shape[len(lead) + 1]
            return P(*(lead + (self._batch_axis(B), self._m(W))))
        return P(*((None,) * len(shape)))

    def cache_shardings(self, cache_shape):
        def spec(path, leaf):
            return NamedSharding(self.mesh, self.cache_spec(path, leaf.shape))
        return jax.tree_util.tree_map_with_path(spec, cache_shape)


# Re-exported ambient context (defined dependency-free in ctx.py).
from repro.parallel.ctx import (  # noqa: E402,F401
    activation_sharding, constrain_activation)
