"""Mixture-of-Experts FFN: router + expert execution paths.

Three execution paths, all numerically equivalent (tests assert it):

* ``moe_dense_ref`` — one-hot einsum over all experts; the oracle.
* ``moe_grouped``  — capacity-based dispatch/combine with sorted token
  buffers feeding a grouped GEMM (optionally the Pallas kernel); this is the
  single-device analogue of the paper's Dispatch→GMM→SwiGLU→GMM→Combine.
  Rows enter and leave the ``[E, C, d]`` buffer by gathers alone, forward
  and backward (``dispatch_rows``, ``combine_rows``; the EP path uses the
  same pair).
* EP-sharded execution lives in ``repro/parallel/ep.py`` (shard_map): the
  ``baseline`` mode uses a collective AllToAll, the ``hyperparallel`` mode
  the RATR chunked-ppermute schedule mirroring the paper's one-sided tasks.
  On one chip it packs the kept rows end to end instead
  (``compact_slot_map``) and runs them through a grouped GEMM
  (``grouped_expert_ffn``), with the same drops.

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
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ragged import grouped_matmul
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
    # "softmax": top-k of the softmax, renormalised. "sigmoid" (DeepSeek-V3,
    # no group limit): top-k of sigmoid scores plus the selection bias
    # ``router_bias``, weights the chosen unbiased scores renormalised and
    # times ``routed_scale``; the bias moves ``bias_speed`` a step against
    # each expert's excess load (``bias_step``), and a sequence-wise balance
    # loss of weight ``balance_alpha`` joins the loss.
    score: str = "softmax"
    routed_scale: float = 1.0
    bias_speed: float = 0.0
    balance_alpha: float = 0.0
    # Shared experts of width d_expert that every token passes (one SwiGLU
    # of their summed width).
    n_shared: int = 0
    # The experts this layer holds, [held_first, held_first + n_held) of the
    # router's e_total (one chip's share of an expert-parallel group); the
    # router still routes over all of them. 0 holds all.
    n_held: int = 0
    held_first: int = 0

    @property
    def e_total(self) -> int:
        return self.n_experts + self.n_padding_experts

    @property
    def e_held(self) -> int:
        return self.n_held or self.e_total


def init_moe(key, d_model: int, mc: MoEConfig, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    E = mc.e_total
    std = d_model ** -0.5
    p = {
        "router": jax.random.normal(k1, (d_model, E), jnp.float32) * std,
        "w_in": jax.random.normal(
            k2, (mc.e_held, d_model, 2 * mc.d_expert), dtype) * std,
        "w_down": jax.random.normal(k3, (mc.e_held, mc.d_expert, d_model),
                                    dtype) * mc.d_expert ** -0.5,
    }
    if mc.score == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if mc.n_shared:
        from .layers import init_mlp
        p["shared"] = init_mlp(jax.random.fold_in(key, 3), d_model,
                               mc.n_shared * mc.d_expert, "swiglu", dtype)
    return p


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


def route(params, x, mc: MoEConfig):
    """The router's choices for x [T, d]: ``(top_p [T, k], top_i [T, k],
    scores)``, ``scores`` the sigmoid scores [T, E] (None for softmax)."""
    if mc.score == "softmax":
        return router_topk(params["router"], x, mc) + (None,)
    if mc.score != "sigmoid":
        raise ValueError(f"unknown router score {mc.score!r}")
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x.astype(jnp.float32),
                                  params["router"]))
    bias = jax.lax.stop_gradient(params["router_bias"])
    _, top_i = jax.lax.top_k(s + bias, mc.top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    top_p = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * mc.routed_scale
    return top_p, top_i, s


def balance_stats(scores, top_i, rows: int):
    """Per sequence (``rows`` equal runs of tokens): each expert's choices
    ``[rows, E]`` and the sum over its tokens of the scores normalised over
    the experts ``[rows, E]``."""
    T, E = scores.shape
    chosen = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32), axis=1)
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    return (chosen.reshape(rows, T // rows, E).sum(1),
            share.reshape(rows, T // rows, E).sum(1))


def router_stats(chosen, share, seq_len: int, mc: MoEConfig) -> dict:
    """One layer's ``moe_load`` (choices per expert over the batch, [E]) and
    ``moe_balance_loss``: DeepSeek-V3's sequence-wise balance loss,
    ``alpha * sum_e f_e P_e`` with ``f_e = E / (k S) * choices`` and ``P_e``
    the mean normalised score over the sequence, averaged over sequences."""
    f = chosen * (mc.e_total / (mc.top_k * seq_len))
    loss = mc.balance_alpha * jnp.mean(jnp.sum(f * share / seq_len, -1))
    return {"moe_load": jnp.sum(chosen, 0), "moe_balance_loss": loss}


@jax.named_scope("moe/router")
def bias_step(bias, moment, load, speed: float, beta: float):
    """The aux-loss-free balance: each expert's bias moves ``speed`` against
    its load's excess over the mean (``load`` [..., E]). The bias's first
    moment ``moment`` keeps the running mean of that excess, weighted
    ``beta`` as Adam's first moment weights a gradient: the direction the
    bias steps against, kept where a trained leaf keeps its gradient's.
    Returns the new bias and moment."""
    excess = load - jnp.mean(load, axis=-1, keepdims=True)
    return (bias - speed * jnp.sign(excess),
            beta * moment + (1 - beta) * excess)


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


def routing_counts(slot, C: int, slots: int, issued, held=None) -> dict:
    """One MoE layer's counters: top-k choices routed to the experts held
    here (``moe_routed``; all of them without ``held``), those capacity
    dropped (``moe_dropped``, slot ``>= C``), the layer's capacity rows
    (``moe_slots``, ``slots``), the rows its expert GEMM computes
    (``moe_rows_issued``, ``issued``: the capacity rows the fixed-shape
    paths multiply, or the kept rows rounded out to the ragged kernel's row
    tiles) and, with a ``held`` mask, the choices of experts held elsewhere
    (``moe_not_held``), as int32 scalars."""
    counts = {"moe_slots": jnp.int32(slots),
              "moe_rows_issued": jnp.asarray(issued, jnp.int32)}
    if held is None:
        return {"moe_routed": jnp.int32(slot.size),
                "moe_dropped": jnp.sum(slot >= C, dtype=jnp.int32), **counts}
    routed = jnp.sum(held, dtype=jnp.int32)
    return {"moe_routed": routed,
            "moe_not_held": jnp.int32(slot.size) - routed,
            "moe_dropped": jnp.sum(held & (slot >= C), dtype=jnp.int32),
            **counts}


def expert_ffn(w_in, w_down, x, act: str = "swiglu"):
    """x: [E, C, d] per-expert batches → [E, C, d]."""
    h = jnp.einsum("ecd,edf->ecf", x, w_in.astype(x.dtype))
    h = glu_act(h, act)
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(x.dtype))


def grouped_expert_ffn(w_in, w_down, x, group_sizes, act: str = "swiglu"):
    """x: [R, d] rows sorted by expert, ``group_sizes`` [E] of them from
    row 0 on → [R, d] (``kernels.ragged``); rows past the groups are left
    undefined."""
    h = grouped_matmul(x, w_in.astype(x.dtype), group_sizes)
    return grouped_matmul(glu_act(h, act), w_down.astype(x.dtype),
                          group_sizes)


class SlotMap(NamedTuple):
    """Where each top-k choice sits in the ``[E, C]`` capacity buffer.

    ``slot`` [T, k]: position within the expert, ``C`` where dropped.
    ``keep`` [T, k]: the choice holds a slot.
    ``dest`` [T, k]: flat slot ``e * C + slot``, clamped where dropped.
    ``src`` [E, C]: flat choice ``t * k + j`` filling each slot, ``T * k``
    where the slot is empty — the inverse of ``dest``.
    """

    slot: jax.Array
    keep: jax.Array
    dest: jax.Array
    src: jax.Array


def slot_map(top_i, E: int, C: int) -> SlotMap:
    """Capacity slots of the choices ``top_i`` [T, k]: a choice's slot is
    the running count of its expert in token order, kept iff below ``C``."""
    T, k = top_i.shape
    n = T * k
    flat_e = top_i.reshape(-1)                                  # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)         # [T*k, E]
    pos = jnp.cumsum(onehot, axis=0) - 1                        # running idx
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = slot < C
    # The inverse without a scatter: a stable sort by expert lists each
    # expert's choices in token order, so expert e's c-th choice sits at
    # its group's offset + c.
    count = pos[-1] + 1                                         # [E]
    offset = jnp.cumsum(count) - count
    order = jnp.argsort(flat_e, stable=True)
    c = jnp.arange(C)
    filled = c[None, :] < jnp.minimum(count, C)[:, None]
    src = jnp.where(filled,
                    order[jnp.minimum(offset[:, None] + c, n - 1)], n)
    dest = flat_e * C + jnp.minimum(slot, C - 1)
    return SlotMap(jnp.where(keep, slot, C).reshape(T, k),
                   keep.reshape(T, k), dest.reshape(T, k), src)


def held_slot_map(top_i, mc: MoEConfig, C: int):
    """Capacity slots of the choices of the experts held here
    (``mc.held_first`` on, ``mc.e_held`` of them); a choice of an expert
    held elsewhere takes no slot. Returns ``(SlotMap over the held experts,
    held [T, k])``; without a share, the plain :func:`slot_map` and None."""
    if not mc.n_held:
        return slot_map(top_i, mc.e_total, C), None
    n = mc.e_held
    held = (top_i >= mc.held_first) & (top_i < mc.held_first + n)
    # Choices held elsewhere go to a bucket past the held experts, whose
    # slots are never issued.
    sm = slot_map(jnp.where(held, top_i - mc.held_first, n), n + 1, C)
    keep = sm.keep & held
    return SlotMap(jnp.where(keep, sm.slot, C), keep,
                   jnp.where(held, sm.dest, 0), sm.src[:n]), held


def compact_slot_map(sm: SlotMap, rows: int):
    """The kept choices of ``sm`` packed end to end, expert after expert,
    into a buffer of ``rows`` rows: expert e's choice at slot s sits at row
    ``koff[e] + s``, ``koff`` the running sum of ``kept = min(count, C)``
    over the experts before e. The drops are ``sm``'s.

    Returns ``(SlotMap, kept [E] int32)``: the map's ``dest`` [T, k] is a
    row of the buffer (0 where not kept) and its ``src`` [rows] the flat
    choice filling each row, ``T * k`` past the last kept row; ``slot`` and
    ``keep`` are ``sm``'s."""
    E, C = sm.src.shape
    n = sm.keep.size
    kept = jnp.sum(sm.src < n, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(kept)
    koff = ends - kept
    dest = jnp.where(sm.keep, koff[sm.dest // C] + sm.dest % C, 0)
    # Row r belongs to the first expert whose rows end past it; its choice
    # is that expert's slot r - koff[e] of the capacity map: no scatter.
    r = jnp.arange(rows, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(ends, r, side="right",
                                     method="compare_all"), E - 1)
    flat = jnp.clip(e * C + r - koff[e], 0, E * C - 1)
    src = jnp.where(r < ends[-1], sm.src.reshape(-1)[flat], n)
    return SlotMap(sm.slot, sm.keep, dest, src), kept


def make_dispatch(top_p, top_i, E: int, C: int):
    """Position-in-expert assignment under fixed capacity.

    Returns (combine_w [T,k], top_i [T,k], slot [T,k] in [0, C) or C for
    dropped).
    """
    sm = slot_map(top_i, E, C)
    return top_p * sm.keep, top_i, sm.slot


# Rows move between tokens and the expert buffer (the capacity buffer
# [E, C, d], or the compact one [R, d] of ``compact_slot_map``) only by
# gathers, in both directions: the slots form a permutation of the kept
# choices, so the transpose of each gather is a gather through the inverse
# map. Buffer rows no choice fills are selected away, never weighted by
# zero, so whatever they hold never reaches a token.

def _token_rows(x2d, src, k: int):
    """x2d [T, d] rows of the tokens whose choices fill ``src``'s slots;
    zero rows where a slot is empty."""
    T = x2d.shape[0]
    rows = x2d[jnp.minimum(src // k, T - 1)]
    return jnp.where((src < T * k)[..., None], rows,
                     jnp.zeros((), x2d.dtype))


def _slot_rows(buf, dest, keep):
    """buf [..., d] rows at the choices' flat slots [T, k, d]; zero where
    dropped."""
    rows = buf.reshape(-1, buf.shape[-1])[dest]
    return jnp.where(keep[..., None], rows, jnp.zeros((), buf.dtype))


@jax.custom_vjp
def dispatch_rows(x2d, sm: SlotMap):
    """Tokens [T, d] → buffer ``sm.src.shape + (d,)``: each slot holds the
    token of its choice ``sm.src``, zeros where empty."""
    return _token_rows(x2d, sm.src, sm.dest.shape[1])


def _dispatch_fwd(x2d, sm):
    return dispatch_rows(x2d, sm), sm


def _dispatch_bwd(sm, g):
    rows = _slot_rows(g, sm.dest, sm.keep)
    # Summed choice by choice in the activation dtype, the order in which a
    # scatter-add of the k copies accumulates them.
    dx = rows[:, 0]
    for j in range(1, rows.shape[1]):
        dx = dx + rows[:, j]
    return dx, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine_rows(buf, top_p, sm: SlotMap):
    """Expert outputs ``sm.src.shape + (d,)`` → tokens [T, d]:
    ``y[t] = Σ_j top_p[t, j] · buf[sm.dest[t, j]]`` over the kept choices."""
    return _combine_fwd(buf, top_p, sm)[0]


def _weighted_sum(rows, p):
    return jnp.einsum("tkd,tk->td", rows, p)


def _combine_fwd(buf, top_p, sm):
    rows = _slot_rows(buf, sm.dest, sm.keep)
    return _weighted_sum(rows, top_p.astype(buf.dtype)), (rows, top_p, sm.src)


def _combine_bwd(res, dy):
    rows, top_p, src = res
    n = top_p.size
    p = top_p.reshape(-1)[jnp.minimum(src, n - 1)].astype(dy.dtype)
    d_buf = _token_rows(dy, src, rows.shape[1]) * p[..., None]
    # top_p's gradient is the forward einsum's own transpose, in its dtype.
    _, vjp = jax.vjp(partial(_weighted_sum, rows), top_p.astype(rows.dtype))
    return d_buf, vjp(dy)[0].astype(top_p.dtype), None


combine_rows.defvjp(_combine_fwd, _combine_bwd)


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
    top_p, top_i, slot = make_dispatch(top_p, top_i, mc.e_total, C)
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
    C = cap or capacity(T, mc)
    with jax.named_scope("moe/router"):
        top_p, top_i, scores = route(params, xt, mc)

    with jax.named_scope("moe/dispatch"):
        sm, held = held_slot_map(top_i, mc, C)
        top_p = top_p * sm.keep
        disp = dispatch_rows(xt, sm)

    with jax.named_scope("moe/expert_ffn"):
        if gmm_fn is not None:
            out_e = gmm_fn(disp, params["w_in"], params["w_down"], act)
        else:
            out_e = expert_ffn(params["w_in"], params["w_down"], disp, act)

    with jax.named_scope("moe/combine"):
        y = combine_rows(out_e, top_p, sm)
    counts = routing_counts(sm.slot, C, mc.e_held * C, mc.e_held * C, held)
    if scores is not None:
        with jax.named_scope("moe/router"):
            counts.update(router_stats(*balance_stats(scores, top_i, B), S,
                                       mc))
    record_moe_counts(counts)
    return y.astype(x.dtype).reshape(B, S, d)
