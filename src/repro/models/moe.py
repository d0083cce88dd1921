"""Mixture-of-Experts FFN: router + expert execution paths.

Three execution paths, all numerically equivalent (tests assert it):

* ``moe_dense_ref`` — one-hot einsum over all experts; the oracle.
* ``moe_grouped``  — capacity-based dispatch/combine with sorted token
  buffers feeding a grouped GEMM (optionally the Pallas kernel); this is the
  single-device analogue of the paper's Dispatch→GMM→SwiGLU→GMM→Combine.
* EP-sharded execution lives in ``repro/parallel/ep.py`` (shard_map): the
  ``baseline`` mode uses a collective AllToAll, the ``hyperparallel`` mode
  the RATR chunked-ppermute schedule mirroring the paper's one-sided tasks.

``plan_from_routing`` bridges this layer to the scheduling stack: it turns a
batch's actual (imbalanced) top-k assignment into a compilable
``repro.core.routing.RoutingPlan``, so compiled schedules are verified
against ``moe_grouped`` on real router output, not just balanced grids.

Routing uses fixed expert capacity so shapes stay static under jit:
``capacity = ceil(tokens · top_k / E · capacity_factor)``; overflow tokens
are dropped (standard practice; the dense ref applies the same mask).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.ctx import record_moe_counts

from .layers import glu_act


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN width (branch width)
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # Experts padded up so E % ep == 0 (router never selects padding).
    n_padding_experts: int = 0

    @property
    def e_total(self) -> int:
        return self.n_experts + self.n_padding_experts


def init_moe(key, d_model: int, mc: MoEConfig, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    E = mc.e_total
    std = d_model ** -0.5
    return {
        "router": jax.random.normal(k1, (d_model, E), jnp.float32) * std,
        "w_in": jax.random.normal(k2, (E, d_model, 2 * mc.d_expert), dtype)
        * std,
        "w_down": jax.random.normal(k3, (E, mc.d_expert, d_model), dtype)
        * mc.d_expert ** -0.5,
    }


def router_topk(p_router, x, mc: MoEConfig):
    """Top-k routing with renormalized softmax probs.

    x: [T, d] → (probs [T, k], idx [T, k]).  Padding experts are masked out.
    """
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p_router)
    if mc.n_padding_experts:
        pad_mask = jnp.arange(mc.e_total) >= mc.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, mc.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i


def load_balance_loss(p_router, x, mc: MoEConfig):
    """Switch-style auxiliary load-balancing loss + router z-loss.

    aux = E · Σ_e f_e · P_e  (f: token fraction routed to e via top-1,
    P: mean router prob) — minimized at uniform routing; z-loss keeps
    router logits bounded. Returns (aux, z)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p_router)
    if mc.n_padding_experts:
        pad = jnp.arange(mc.e_total) >= mc.n_experts
        logits = jnp.where(pad[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, mc.e_total, dtype=jnp.float32),
                 axis=0)
    P = jnp.mean(probs, axis=0)
    aux = mc.n_experts * jnp.sum(f * P)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return aux, z


def capacity(tokens: int, mc: MoEConfig, ep: int = 1) -> int:
    """Per-expert capacity, rounded up to a multiple of ``ep`` so EP
    all-to-all chunks stay uniform."""
    c = int(np.ceil(tokens * mc.top_k / mc.e_total * mc.capacity_factor))
    return max(ep, ((c + ep - 1) // ep) * ep)


def routing_counts(slot, C: int, slots: int) -> dict:
    """One MoE layer's counters: top-k choices made (``moe_routed``), those
    capacity dropped (``moe_dropped``, slot ``>= C``) and rows of expert
    work issued (``moe_slots``), as int32 scalars."""
    return {"moe_routed": jnp.int32(slot.size),
            "moe_dropped": jnp.sum(slot >= C, dtype=jnp.int32),
            "moe_slots": jnp.int32(slots)}


def expert_ffn(w_in, w_down, x, act: str = "swiglu"):
    """x: [E, C, d] per-expert batches → [E, C, d]."""
    h = jnp.einsum("ecd,edf->ecf", x, w_in.astype(x.dtype))
    h = glu_act(h, act)
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(x.dtype))


def make_dispatch(top_p, top_i, T: int, E: int, C: int):
    """Position-in-expert assignment under fixed capacity.

    Returns (combine_w [T,k], slot [T,k] in [0, C) or C for dropped).
    """
    k = top_i.shape[1]
    flat_e = top_i.reshape(-1)                                  # [T*k]
    # position of each (token, choice) within its expert, in token order
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)         # [T*k, E]
    pos = jnp.cumsum(onehot, axis=0) - 1                        # running idx
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = slot < C
    return (top_p * keep.reshape(T, k)), flat_e.reshape(T, k), \
        jnp.where(keep, slot, C).reshape(T, k)


def moe_dense_ref(params, x, mc: MoEConfig, act: str = "swiglu",
                  cap: Optional[int] = None):
    """One-hot dense-einsum oracle (same capacity-drop mask, no scatter)."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    E = mc.e_total
    C = cap or capacity(T, mc)
    top_p, top_i, slot = _routed(params, xt, mc, C)
    # dispatch_mask[t, k, e, c]: token t's k-th choice occupies (e, c).
    e_oh = jax.nn.one_hot(top_i, E, dtype=xt.dtype)          # [T,k,E]
    c_oh = jax.nn.one_hot(slot, C, dtype=xt.dtype)           # [T,k,C] (C drops)
    disp_mask = jnp.einsum("tke,tkc->tec", e_oh, c_oh)
    disp = jnp.einsum("tec,td->ecd", disp_mask, xt)
    out_e = expert_ffn(params["w_in"], params["w_down"], disp, act)
    comb = jnp.einsum("tke,tkc,tk->tec", e_oh, c_oh, top_p.astype(xt.dtype))
    y = jnp.einsum("tec,ecd->td", comb, out_e)
    return y.reshape(B, S, d)


def _routed(params, xt, mc: MoEConfig, C: int):
    top_p, top_i = router_topk(params["router"], xt, mc)
    top_p, top_i, slot = make_dispatch(top_p, top_i, xt.shape[0],
                                       mc.e_total, C)
    return top_p, top_i, slot


# ---------------------------------------------------------------------------
# RoutingPlan bridge — real router output → compilable schedule input.
#
# This is the seam between the model layer (capacity-based top-k routing)
# and the scheduling stack (repro.core): the bridge turns a batch's actual
# (imbalanced) expert assignment into a RoutingPlan plus the row bookkeeping
# needed to scatter tokens into the plan's send-buffer layout and to apply
# top-k combine weights to the executor's returned rows. Tokens are split
# contiguously over EP source ranks, so a token's global order equals
# (src-major, local order) — exactly the slot order `moe_grouped` produces,
# which is what makes a compiled schedule comparable bit-for-bit against the
# grouped reference.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RoutingBridge:
    """A RoutingPlan plus token↔row maps for one routed batch."""

    plan: "object"              # repro.core.routing.RoutingPlan
    # Row index into source rank s's send buffer for choice (s, t, k);
    # -1 where the choice was dropped by capacity.
    send_row: np.ndarray        # int64 [ep, T_loc, k]

    @property
    def ep(self) -> int:
        return self.send_row.shape[0]


def _cumcount(keys: np.ndarray) -> np.ndarray:
    """Occurrence index of each element within its key group, in order.

    Vectorized (stable argsort + group starts): this runs once per routed
    batch on [T*k] choices, so no per-choice Python loop.
    """
    n = keys.shape[0]
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    order = np.argsort(keys, kind="stable")
    sorted_keys = keys[order]
    starts = np.r_[0, np.flatnonzero(np.diff(sorted_keys)) + 1]
    group_start = np.repeat(starts, np.diff(np.r_[starts, n]))
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n, dtype=np.int64) - group_start
    return rank


def bucket_counts(counts: np.ndarray, bucket_rows=1) -> np.ndarray:
    """Quantize per-(src, dst, expert) row counts into shape buckets.

    ``bucket_rows`` is any :func:`repro.core.buckets.BucketSpec.from_any`
    argument: the legacy linear bucket-size int, a :class:`BucketSpec`
    (``linear`` / ``geometric`` / fitted ``ladder``), or a parsed spec
    string like ``"geometric:8"``. Nonzero cells round *up* to their policy
    bucket (the padding rows stay zero-filled in the send buffers, so
    execution is unchanged); empty cells stay empty so plan sparsity — and
    therefore the task graph's nonzero-cell structure — is preserved. Two
    batches whose counts land in the same buckets produce identical plans
    and therefore share one SSC cache entry: this is the shape-bucketing
    layer that keeps the dropless cache hit rate high under batch-to-batch
    routing jitter.
    """
    from repro.core.buckets import BucketSpec
    spec = BucketSpec.from_any(bucket_rows)
    if spec.is_exact:
        return counts
    return spec.quantize(counts)


def routed_counts(top_i, mc: MoEConfig, ep: int) -> np.ndarray:
    """Exact per-(src, dst, expert) row counts of one batch's routing.

    The dropless-counts histogram of :func:`plan_from_routing` without
    building the bridge — what the online tuner's rolling plan population
    stores per served batch (``launch/online.py``). ``top_i`` as in
    :func:`plan_from_routing`; returns int64 ``[ep, ep, e_loc]``.
    """
    ti = np.asarray(top_i)
    if ti.ndim == 2:
        T, k = ti.shape
        if T % ep:
            raise ValueError(f"T={T} tokens not divisible by ep={ep}")
        ti = ti.reshape(ep, T // ep, k)
    if ti.shape[0] != ep:
        raise ValueError(f"leading dim {ti.shape[0]} != ep={ep}")
    if mc.e_total % ep:
        raise ValueError(f"e_total={mc.e_total} not divisible by ep={ep}")
    e_loc = mc.e_total // ep
    _, t_loc, k = ti.shape
    flat = ti.reshape(-1).astype(np.int64)
    src_idx = np.repeat(np.arange(ep, dtype=np.int64), t_loc * k)
    counts = np.zeros((ep, ep, e_loc), dtype=np.int64)
    np.add.at(counts, (src_idx, flat // e_loc, flat % e_loc), 1)
    return counts


def plan_from_routing(top_i, mc: MoEConfig, ep: int,
                      capacity: Optional[int] = None,
                      bucket_rows: int = 1, bucket=None) -> RoutingBridge:
    """Turn real router output into a compilable :class:`RoutingBridge`.

    ``top_i``: expert indices [T, k] (tokens split contiguously over ``ep``
    source ranks; T % ep == 0) or already per-rank [ep, T_loc, k].
    ``capacity``: per-(global expert) token cap applied in global token
    order, matching ``make_dispatch``; ``None`` = dropless.
    ``bucket``: a :class:`repro.core.buckets.BucketSpec` (or anything
    ``BucketSpec.from_any`` accepts) quantizing each cell's row count up to
    its shape bucket; ``bucket_rows`` is the legacy linear-bucket int shim
    (``bucket`` wins when both are given). The actual rows occupy the head
    of each padded cell and the tail rows stay zero, so a schedule compiled
    for the bucketed plan computes the same result as the exact one.
    """
    from repro.core.buckets import normalize_bucket
    from repro.core.routing import RoutingPlan

    spec = normalize_bucket(bucket, bucket_rows)

    ti = np.asarray(top_i)
    if ti.ndim == 2:
        T, k = ti.shape
        if T % ep:
            raise ValueError(f"T={T} tokens not divisible by ep={ep}")
        ti = ti.reshape(ep, T // ep, k)
    if ti.shape[0] != ep:
        raise ValueError(f"leading dim {ti.shape[0]} != ep={ep}")
    _, t_loc, k = ti.shape
    if mc.e_total % ep:
        raise ValueError(f"e_total={mc.e_total} not divisible by ep={ep}")
    e_loc = mc.e_total // ep

    flat = ti.reshape(-1).astype(np.int64)      # global (src-major) order
    src_idx = np.repeat(np.arange(ep, dtype=np.int64), t_loc * k)
    d_idx = flat // e_loc
    e_idx = flat % e_loc

    # Position of each choice within its global expert, in global order —
    # the same cumulative count `make_dispatch` computes.
    slot = _cumcount(flat)
    keep = (slot < capacity) if capacity is not None else np.ones(
        flat.shape[0], dtype=bool)

    counts = np.zeros((ep, ep, e_loc), dtype=np.int64)
    np.add.at(counts, (src_idx[keep], d_idx[keep], e_idx[keep]), 1)
    plan = RoutingPlan.from_counts(bucket_counts(counts, spec))

    # Row within the (src, dst, expert) send cell = occurrence index among
    # the *kept* choices of that cell, in local order.
    send_row = np.full(flat.shape[0], -1, dtype=np.int64)
    kept = np.nonzero(keep)[0]
    cell = (src_idx[kept] * ep + d_idx[kept]) * e_loc + e_idx[kept]
    send_row[kept] = (plan.send_offsets.reshape(-1)[cell]
                      + _cumcount(cell))
    return RoutingBridge(plan=plan,
                         send_row=send_row.reshape(ep, t_loc, k))


def bridge_dispatch(bridge: RoutingBridge, x) -> list:
    """Scatter tokens [ep, T_loc, d] into per-rank plan send buffers."""
    x = np.asarray(x, dtype=np.float32)
    k = bridge.send_row.shape[2]
    bufs = []
    for s in range(bridge.ep):
        buf = np.zeros((bridge.plan.send_rows(s), x.shape[-1]),
                       dtype=np.float32)
        rows = bridge.send_row[s].reshape(-1)
        valid = rows >= 0
        buf[rows[valid]] = np.repeat(x[s], k, axis=0)[valid]
        bufs.append(buf)
    return bufs


def bridge_combine(bridge: RoutingBridge, y_ret: list, top_p) -> np.ndarray:
    """Weight-and-gather executor return buffers back to [ep, T_loc, d].

    Applies the same per-choice accumulation ``moe_grouped`` performs;
    dropped choices contribute zero.
    """
    top_p = np.asarray(top_p, dtype=np.float32).reshape(
        bridge.send_row.shape)
    ep, t_loc, k = bridge.send_row.shape
    d = y_ret[0].shape[-1] if y_ret else 0
    y = np.zeros((ep, t_loc, d), dtype=np.float32)
    for s in range(ep):
        for j in range(k):
            rows = bridge.send_row[s, :, j]
            valid = rows >= 0
            if valid.any():
                y[s, valid] += (top_p[s, valid, j, None]
                                * y_ret[s][rows[valid]])
    return y


def fused_boundary_forward(bridge_out: RoutingBridge,
                           bridge_in: RoutingBridge,
                           top_p_out, d_model: int) -> dict:
    """Per-rank remap fns for one forward junction of a fused schedule.

    The junction composes layer i's combine-weighted gather (the rank-r
    slice of :func:`bridge_combine` under ``bridge_out``/``top_p_out``)
    with layer i+1's send-buffer scatter (the rank-r slice of
    :func:`bridge_dispatch` under ``bridge_in``). Both ops are exactly
    rank-local — a token's returned rows and its next-layer send rows live
    on its own source rank — so the per-rank restriction is *bitwise*
    identical to running the full ops sequentially; the loops below mirror
    them statement for statement to keep it that way.

    Returns ``{rank: fn}`` with the executor's LayerBoundary contract
    ``fn(full_y_ret_or_None, lo, hi) -> [hi - lo, d_model]``; the full
    remap is memoized per rank, so tile granularity costs nothing.
    """
    tp = np.asarray(top_p_out, dtype=np.float32).reshape(
        bridge_out.send_row.shape)
    ep, t_loc, k_out = bridge_out.send_row.shape
    k_in = bridge_in.send_row.shape[2]
    fns = {}
    for r in range(ep):
        def fn(data, lo, hi, r=r, _memo={}):
            if "buf" not in _memo:
                y = np.zeros((t_loc, d_model), dtype=np.float32)
                for j in range(k_out):
                    rows = bridge_out.send_row[r, :, j]
                    valid = rows >= 0
                    if valid.any():
                        y[valid] += tp[r, valid, j, None] * data[rows[valid]]
                buf = np.zeros((bridge_in.plan.send_rows(r), d_model),
                               dtype=np.float32)
                rows = bridge_in.send_row[r].reshape(-1)
                valid = rows >= 0
                buf[rows[valid]] = np.repeat(y, k_in, axis=0)[valid]
                _memo["buf"] = buf
            return _memo["buf"][lo:hi]
        fns[r] = fn
    return fns


def fused_boundary_backward(bridge_out: RoutingBridge,
                            bridge_in: RoutingBridge,
                            top_p_out, d_model: int) -> dict:
    """Backward twin of :func:`fused_boundary_forward`.

    Maps ``dx_ret`` of layer i+1's backward fragment (gradient w.r.t. that
    layer's send buffer) to ``dy_src`` of layer i's (gradient w.r.t. its
    return buffer): gather-sum the dispatched copies back to tokens
    (dispatch transpose), then scatter the combine weights' products into
    the upstream send layout (combine transpose). Rank-local for the same
    reason as the forward; mirrors the dropless backward host's
    accumulation statements bit for bit.
    """
    tp = np.asarray(top_p_out, dtype=np.float32).reshape(
        bridge_out.send_row.shape)
    ep, t_loc, k_out = bridge_out.send_row.shape
    k_in = bridge_in.send_row.shape[2]
    fns = {}
    for r in range(ep):
        def fn(data, lo, hi, r=r, _memo={}):
            if "buf" not in _memo:
                dx_tok = np.zeros((t_loc, d_model), dtype=np.float32)
                for j in range(k_in):
                    rows = bridge_in.send_row[r, :, j]
                    valid = rows >= 0
                    if valid.any():
                        dx_tok[valid] += data[rows[valid]]
                dy = np.zeros((bridge_out.plan.send_rows(r), d_model),
                              dtype=np.float32)
                rows = bridge_out.send_row[r].reshape(-1)
                valid = rows >= 0
                contrib = (tp[r][:, :, None] * dx_tok[:, None, :]).reshape(
                    -1, d_model)
                np.add.at(dy, rows[valid], contrib[valid])
                _memo["buf"] = dy
            return _memo["buf"][lo:hi]
        fns[r] = fn
    return fns


def moe_grouped(params, x, mc: MoEConfig, act: str = "swiglu",
                cap: Optional[int] = None, gmm_fn=None):
    """Sorted/capacity dispatch → grouped FFN → weighted combine.

    ``gmm_fn(x_sorted, group_sizes, w_in, w_down)`` may override the expert
    FFN with the Pallas grouped-GEMM kernel; defaults to the einsum path.
    """
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    E = mc.e_total
    C = cap or capacity(T, mc)
    with jax.named_scope("moe/router"):
        top_p, top_i = router_topk(params["router"], xt, mc)

    # Dispatch: scatter tokens into [E, C, d] expert buffers.
    with jax.named_scope("moe/dispatch"):
        top_p, top_i, slot = make_dispatch(top_p, top_i, T, E, C)
        disp = jnp.zeros((E, C + 1, d), x.dtype)
        tok_idx = jnp.broadcast_to(jnp.arange(T)[:, None], top_i.shape)
        disp = disp.at[top_i.reshape(-1), slot.reshape(-1)].add(
            xt[tok_idx.reshape(-1)])
        disp = disp[:, :C]

    with jax.named_scope("moe/expert_ffn"):
        if gmm_fn is not None:
            out_e = gmm_fn(disp, params["w_in"], params["w_down"], act)
        else:
            out_e = expert_ffn(params["w_in"], params["w_down"], disp, act)

    # Combine: gather back with routing weights.
    with jax.named_scope("moe/combine"):
        out_e = jnp.concatenate([out_e, jnp.zeros_like(out_e[:, :1])],
                                axis=1)
        y = jnp.zeros((T, d), x.dtype)
        for j in range(mc.top_k):
            y = y + (out_e[top_i[:, j], slot[:, j]]
                     * top_p[:, j][:, None].astype(x.dtype))
    record_moe_counts(routing_counts(slot, C, E * C))
    return y.reshape(B, S, d)
