"""Expert-parallel MoE execution under shard_map.

Two modes, both numerically identical to the single-device paths (tests
assert it on a multi-device CPU mesh):

* ``baseline``       — collective AllToAll dispatch, full-barrier semantics:
  the conventional host-driven path the paper profiles in §2.3.
* ``hyperparallel``  — the paper's design mapped to JAX/TPU: the AllToAll is
  decomposed into per-destination chunks moved by ``ppermute`` in a
  RATR-rotated ring (source rank r starts at destination r+k at step k),
  with each arriving chunk's expert FFN issued immediately. Data dependence
  is chunk-local, so XLA's latency-hiding scheduler overlaps the
  collective-permute of step k+1 with the GMM of step k — the tile-level
  one-sided pipeline of §4.1/§4.4, with ppermute's send/recv semantics
  standing in for put_mem_signal's remote-write + event counter.

Routing uses per-(destination, expert) fixed capacity so all comm shapes are
static. Every device routes its local tokens with the replicated router;
combine applies top-k weights back at the source — exactly the paper's
Dispatch→…→Combine boundary. An EP group of one chip exchanges nothing: it
packs the kept rows end to end instead of into the capacity buffer, and a
grouped GEMM computes only those (``models.moe.compact_slot_map``,
``kernels.ragged``), with the same drops.

Every stage runs under a ``jax.named_scope`` (``moe/router``,
``moe/dispatch``, ``moe/exchange``, ``moe/expert_ffn``, ``moe/combine``), so
each device op, forward, transposed or recomputed, names its stage in its
HLO ``op_name``. Each call hands ``ctx.record_moe_counts`` the layer's
counters (``models.moe.routing_counts``) as per-device vectors: each chip
counts its own top-k choices, capacity drops, capacity rows and the rows
its expert GEMMs compute.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.ragged import buffer_rows, rows_issued
from repro.models.moe import (MoEConfig, balance_stats, combine_rows,
                              compact_slot_map, dispatch_rows,
                              grouped_expert_ffn, held_slot_map, route,
                              router_stats, routing_counts)
from repro.models.layers import glu_act
from repro.parallel.ctx import record_moe_counts


@dataclasses.dataclass(frozen=True)
class EPConfig:
    mode: str = "hyperparallel"     # baseline | hyperparallel
    axis: str = "model"
    capacity_factor: float = 1.25
    use_pallas: bool = False        # fused gmm kernels inside the shard
    # EP-over-DP (paper's dp=32/ep=32 layout): tokens are batch-sharded over
    # every mesh axis incl. the EP axis; the a2a still runs over `axis`.
    dp_batch: bool = False


def _pair_capacity(t_loc: int, mc: MoEConfig, ep: int,
                   cap_factor: float) -> int:
    """Tokens per (destination rank, local expert) pair from one device."""
    per_slot = t_loc * mc.top_k / mc.e_total
    return max(8, int(np.ceil(per_slot * cap_factor / 8)) * 8)


def plan_from_dispatch(top_i, mc: MoEConfig, ep: int, C: int):
    """RoutingPlan for the rows the EP path's capacity buffers materialise.

    ``top_i``: per-source-rank expert choices [ep, T_loc, k]. Capacity here
    is *per (source device, global expert)* — the slot semantics of
    ``_route_local`` — so ``counts[s, d, e] = min(#choices, C)``. The
    returned plan describes the useful (non-padding) rows of the EP path's
    fixed-capacity send buffers, letting the same batch be compiled by the
    scheduling stack and profiled for skew.
    """
    from repro.core.routing import RoutingPlan

    ti = np.asarray(top_i)
    if ti.ndim != 3 or ti.shape[0] != ep:
        raise ValueError(f"expected [ep, T_loc, k] choices, got {ti.shape}")
    if mc.e_total % ep:
        raise ValueError(f"e_total={mc.e_total} not divisible by ep={ep}")
    e_loc = mc.e_total // ep
    counts = np.zeros((ep, ep, e_loc), dtype=np.int64)
    for s in range(ep):
        hist = np.bincount(ti[s].reshape(-1), minlength=mc.e_total)
        counts[s] = np.minimum(hist, C).reshape(ep, e_loc)
    return RoutingPlan.from_counts(counts)


def ring_chunk_caps(plan, ep: int, topology=None, bucket=None,
                    inter_bucket=None) -> tuple:
    """Per-ring-step row caps from a :class:`RoutingPlan`.

    ``caps[k]`` is the largest per-(dst, expert) row count any source rank
    moves at ring distance ``k`` (source ``s`` → destination ``(s + k) %
    ep``). The hyperparallel ring uses these to slice each step's ppermute
    chunk to plan size instead of the full fixed capacity — and a step whose
    cap is 0 carries only padding for *every* rank, so it is skipped
    entirely (no ppermute pair, no FFN). Caps are an upper bound per SPMD
    step: all ranks must move the same shape, so the straggler source sets
    the cap.

    With a :class:`repro.core.hardware.Topology`, each step's cap can be
    quantized per *link class*: ring step ``k`` is an **inter-node** step
    when any source's hop at distance ``k`` crosses a node boundary (one
    straggler crossing makes the whole SPMD step pay NIC rates). Intra-node
    steps quantize their caps with ``bucket``, inter-node steps with the
    (typically coarser) ``inter_bucket`` — fewer distinct cap rungs on the
    slow axis means fewer retraces of exactly the steps where a retrace
    stalls the NIC pipeline longest. Both accept anything
    ``BucketSpec.from_any`` does; ``None`` leaves that class's caps exact.
    Quantization only rounds caps *up* (rungs are upper bounds), so a
    bucketed cap never drops rows a plan-sized chunk would have carried,
    and zero caps stay zero — step skipping survives bucketing.
    """
    if plan.ep != ep:
        raise ValueError(f"plan ep={plan.ep} != mesh ep={ep}")
    c = np.asarray(plan.counts, dtype=np.int64)       # [src, dst, e_loc]
    caps = []
    for k in range(ep):
        dst = (np.arange(ep) + k) % ep
        caps.append(int(c[np.arange(ep), dst].max()))
    if bucket is None and inter_bucket is None:
        return tuple(caps)
    if inter_bucket is not None and topology is None:
        raise ValueError(
            "inter_bucket needs a topology to tell inter-node ring steps "
            "from intra-node ones")
    from repro.core.buckets import BucketSpec

    def quantize(cap: int, b) -> int:
        if b is None or cap == 0:
            return cap
        return int(BucketSpec.from_any(b).quantize(np.array([cap]))[0])

    out = []
    for k, cap in enumerate(caps):
        inter = topology is not None and any(
            not topology.same_node(s, (s + k) % ep) for s in range(ep))
        b = inter_bucket if (inter and inter_bucket is not None) else bucket
        out.append(quantize(cap, b))
    return tuple(out)


@jax.named_scope("moe/expert_ffn")
def _expert_ffn_local(w_in, w_down, x, act, use_pallas):
    if use_pallas:
        from repro.kernels.ops import moe_expert_ffn
        return moe_expert_ffn(x, w_in, w_down, act)
    h = jnp.einsum("ecd,edf->ecf", x, w_in.astype(x.dtype))
    h = glu_act(h, act)
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(x.dtype))


def _route_local(x2d, router_p, mc: MoEConfig, C: int):
    """Local routing and the choices' capacity slots.

    Returns (top_p, top_i, scores, sm, held): ``top_p`` zero where dropped,
    ``sm`` the choices' :class:`SlotMap` over the held experts' (dst,
    expert) buckets, ``held`` which choices those are (None where every
    expert is held), ``scores`` as :func:`repro.models.moe.route` gives them.
    """
    with jax.named_scope("moe/router"):
        top_p, top_i, scores = route(router_p, x2d, mc)
    with jax.named_scope("moe/dispatch"):
        sm, held = held_slot_map(top_i, mc, C)
        top_p = top_p * sm.keep
    return top_p, top_i, scores, sm, held


def _compact_moe(x2d, top_p, sm, w_in, w_down, act):
    """One chip's expert layer without the capacity buffer: the kept rows
    packed end to end (``compact_slot_map``, the same drops), a grouped
    GEMM over them, and the weighted combine. Returns (y [T, d], the rows
    the GEMM computes)."""
    T, k = sm.keep.shape
    R = buffer_rows(min(T * k, sm.src.size))
    with jax.named_scope("moe/dispatch"):
        csm, kept = compact_slot_map(sm, R)
        issued = rows_issued(kept, R)
        xin = dispatch_rows(x2d, csm)
    with jax.named_scope("moe/expert_ffn"):
        out = grouped_expert_ffn(w_in, w_down, xin, kept, act)
    with jax.named_scope("moe/combine"):
        return combine_rows(out, top_p, csm), issued


def make_moe_ep(mesh, epc: EPConfig, act: str = "swiglu", plan=None,
                bucket=None, topology=None, inter_bucket=None):
    """Returns moe_impl(params, x, mc) running EP over the model axis.

    ``plan``: an optional host-known :class:`RoutingPlan` (e.g. from
    ``plan_from_dispatch`` on this batch's routing, or a bucketed plan
    covering it). In ``hyperparallel`` mode the ring then moves *plan-sized*
    ppermute chunks — each step's chunk is sliced to the largest row count
    any source actually sends at that ring distance — and ring steps that
    would carry only padding for every rank are skipped outright (the
    ROADMAP "ragged EP path"). Chunk caps are static Python ints, so a new
    plan triggers a retrace. ``bucket`` (a
    :class:`repro.core.buckets.BucketSpec` or anything
    ``BucketSpec.from_any`` accepts) quantizes the plan's counts before the
    caps are derived, so jittered per-batch plans collapse onto a small set
    of cap tuples and the retrace count stays bounded by the policy's rung
    ladder instead of growing with every batch — the same trade the SSC
    cache makes, applied to jit traces. Buckets only ever round counts
    *up*, so a bucketed plan never undercounts the routing it was derived
    from. If the (possibly bucketed) plan undercounts the real routing —
    e.g. a stale plan reused across batches — overflow rows degrade to
    capacity-style drops (their result rows stay zero); they are never
    mis-gathered.

    ``topology`` (a :class:`repro.core.hardware.Topology`) switches cap
    quantization to per link class: ring steps whose hop crosses a node
    boundary for any source quantize with ``inter_bucket`` instead of
    ``bucket`` (see :func:`ring_chunk_caps`) — a coarser inter-node ladder
    bounds retraces of the NIC-bound steps separately from the cheap
    intra-node ones.
    """
    ep = mesh.shape[epc.axis]
    dp = tuple(a for a in mesh.axis_names if a != epc.axis)
    # One chip exchanges nothing, so nothing needs the fixed-size chunks:
    # its experts take only the kept rows, packed end to end (the
    # fixed-shape Pallas kernels keep the capacity buffer).
    compact = ep == 1 and not epc.use_pallas
    if (bucket is not None or inter_bucket is not None) and plan is None:
        raise ValueError(
            "make_moe_ep(bucket=.../inter_bucket=...) quantizes a routing "
            "plan's ring caps — pass plan= as well (without one the "
            "fixed-capacity path runs and the bucket would be silently "
            "ignored)")
    if topology is not None and plan is not None:
        # Per-link-class cap quantization: intra-node steps use ``bucket``,
        # inter-node steps the (coarser) ``inter_bucket``.
        ring_caps = ring_chunk_caps(plan, ep, topology=topology,
                                    bucket=bucket,
                                    inter_bucket=inter_bucket)
    else:
        if bucket is not None:
            from repro.core.buckets import BucketSpec
            plan = BucketSpec.from_any(bucket).apply(plan)
        ring_caps = ring_chunk_caps(plan, ep) if plan is not None else None

    def moe_impl(params, x, mc: MoEConfig):
        B, S, d = x.shape
        e_loc = mc.e_held // ep

        if epc.dp_batch and B % (ep * max(1, np.prod(
                [mesh.shape[a] for a in dp]))) == 0:
            x_spec = P(tuple(mesh.axis_names), None, None)
        else:
            x_spec = P(dp if B > 1 else None,
                       epc.axis if S % ep == 0 and S > 1 else None, None)

        # Each device's counters leave the shard as its own entry of a
        # vector over every mesh axis; a sigmoid router's per-sequence
        # balance statistics leave summed over the shards of a sequence.
        per_device = P(tuple(mesh.axis_names))
        router_p = {"router": params["router"]}
        router_specs = {"router": P(None, None)}
        out_specs = (x_spec, per_device)
        if mc.score == "sigmoid":
            router_p["router_bias"] = params["router_bias"]
            router_specs["router_bias"] = P(None)
            out_specs += ((P(x_spec[0], None),) * 2,)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(router_specs, P(epc.axis, None, None),
                           P(epc.axis, None, None), x_spec),
                 out_specs=out_specs, check_vma=False)
        def run(router_p, w_in, w_down, x_loc):
            b, s, _ = x_loc.shape
            T = b * s
            x2d = x_loc.reshape(T, d)
            C = _pair_capacity(T, mc, ep, epc.capacity_factor)
            top_p, top_i, scores, sm, held = _route_local(
                x2d, router_p, mc, C)
            if compact:
                y, rows = _compact_moe(x2d, top_p, sm, w_in, w_down, act)
            else:
                y, rows = _capacity_moe(x2d, top_p, sm, w_in, w_down, C)
            counts = {k: v[None] for k, v in routing_counts(
                sm.slot, C, mc.e_held * C, rows, held).items()}
            out = (y.astype(x_loc.dtype).reshape(b, s, d), counts)
            if scores is None:
                return out
            with jax.named_scope("moe/router"):
                stats = balance_stats(scores, top_i, b)
                if x_spec[1] is not None:       # sequences split over chips
                    stats = jax.lax.psum(stats, epc.axis)
            return out + (stats,)

        def _capacity_moe(x2d, top_p, sm, w_in, w_down, C):
            """The exchange over the EP group in fixed [e_loc, C, d]
            chunks. Returns (y [T, d], the rows the expert GEMMs
            compute)."""
            with jax.named_scope("moe/dispatch"):
                send = dispatch_rows(x2d, sm).reshape(ep, e_loc, C, d)
            if epc.mode == "baseline":
                with jax.named_scope("moe/exchange"):
                    recv = jax.lax.all_to_all(send, epc.axis, split_axis=0,
                                              concat_axis=0, tiled=True)
                    xin = recv.transpose(1, 0, 2, 3).reshape(
                        e_loc, ep * C, d)
                y = _expert_ffn_local(w_in, w_down, xin, act,
                                      epc.use_pallas)
                with jax.named_scope("moe/exchange"):
                    y = y.reshape(e_loc, ep, C, d).transpose(1, 0, 2, 3)
                    back = jax.lax.all_to_all(y, epc.axis, split_axis=0,
                                              concat_axis=0, tiled=True)
                rows = e_loc * ep * C
            else:
                back, rows = _hyperparallel_ring(
                    send, w_in, w_down, act, ep, epc)

            with jax.named_scope("moe/combine"):
                # back: results at their send slots, expert-major like send.
                return (combine_rows(back.reshape(mc.e_held, C, d), top_p,
                                     sm), rows)

        y, counts, *stats = run(router_p, params["w_in"],
                                params["w_down"], x)
        if stats:
            with jax.named_scope("moe/router"):
                counts = dict(counts, **router_stats(*stats[0], S, mc))
        record_moe_counts(counts)
        return y

    def _hyperparallel_ring(send, w_in, w_down, act, ep, epc):
        """RATR ring: step k moves the chunk for destination (r+k) and the
        FFN for the chunk that just arrived runs immediately; results ride
        the reverse ring back to their source. Step 0 is the rank-local
        chunk (an HBM copy, not link traffic — same as the simulator).

        With ``ring_caps`` (a routing plan is known), each step's chunk is
        sliced to ``min(C, caps[k])`` rows per (dst, expert) slot — tokens
        always occupy the head of each slot, so the sliced rows are exactly
        the routed ones — and all-padding steps (cap 0) are skipped.

        Returns ``(back, rows)``: the results, and the rows this device's
        expert GEMMs compute over all steps (``moe_rows_issued``; below the
        capacity rows ``moe_slots`` only where ``ring_caps`` slice chunks).
        """
        r = jax.lax.axis_index(epc.axis)
        e_loc, C, d = send.shape[1], send.shape[2], send.shape[3]
        back = jnp.zeros_like(send)
        rows = 0

        def step_cap(k):
            return C if ring_caps is None else min(C, ring_caps[k])

        # k = 0: local chunk.
        c0 = step_cap(0)
        if c0 > 0:
            with jax.named_scope("moe/dispatch"):
                chunk0 = jnp.take(send, r, axis=0)[:, :c0]  # [e_loc,c0,d]
            y0 = _expert_ffn_local(w_in, w_down, chunk0, act, epc.use_pallas)
            with jax.named_scope("moe/combine"):
                back = jax.lax.dynamic_update_slice(back, y0[None],
                                                    (r, 0, 0, 0))
            rows += e_loc * c0

        for k in range(1, ep):
            ck = step_cap(k)
            if ck == 0:
                continue        # every rank's step-k chunk is pure padding
            perm_fwd = [(i, (i + k) % ep) for i in range(ep)]
            perm_bwd = [(i, (i - k) % ep) for i in range(ep)]
            # RATR: source r's step-k chunk targets destination (r+k).
            with jax.named_scope("moe/dispatch"):
                chunk = jnp.take(send, (r + k) % ep, axis=0)[:, :ck]
            with jax.named_scope("moe/exchange"):
                arrived = jax.lax.ppermute(chunk, epc.axis, perm_fwd)
            y = _expert_ffn_local(w_in, w_down, arrived, act,
                                  epc.use_pallas)
            with jax.named_scope("moe/exchange"):
                returned = jax.lax.ppermute(y, epc.axis, perm_bwd)
            with jax.named_scope("moe/combine"):
                back = jax.lax.dynamic_update_slice(
                    back, returned[None], ((r + k) % ep, 0, 0, 0))
            rows += e_loc * ck
        return back, rows

    return moe_impl
