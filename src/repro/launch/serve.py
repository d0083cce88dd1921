"""Serving driver with continuous batching.

Production shape: a request queue feeds fixed-slot batched decoding —
finished sequences immediately release their slot to the next request
(prefill into the slot, decode continues for everyone else). Per-slot
cache state lives in one batched cache pytree; slot refill uses masked
scatter so everything stays jit-compiled at a fixed batch size.

CPU-scale demo:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --requests 12 --slots 4 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

# Serving jits the whole decode step *around* the dropless pure_callback
# executor (``--online-refit``); under async CPU dispatch the callback's
# device-to-host operand transfer can deadlock against the in-flight
# executable. The knob binds at CPU-client creation, so it is pinned at
# import — effective for the CLI and for any consumer that imports this
# module before touching jax (tests pin it in conftest.py).
jax.config.update("jax_cpu_enable_async_dispatch", False)


def decode_population(mc, ep: int, n_tokens: int, *, profile: str = "zipf",
                      steps: int = 16, seed: int = 0) -> list[np.ndarray]:
    """Synthesized decode-traffic routing-count population.

    The cold-start stand-in for a live rolling window: a short correlated
    Zipf decode trace (``launch/replay.synth_trace``) sized to this
    server's per-step token budget, reduced to exact ``[ep, ep, e_loc]``
    count matrices. Sizing, admission pricing, and the decode schedule all
    consume populations of this shape — once the server runs, the online
    tuner's window (real traffic, same shape) replaces it.
    """
    from repro.launch.replay import synth_trace
    from repro.models.moe import routed_counts
    t_loc = max(1, n_tokens // ep)
    trace = synth_trace(profile, steps, ep=ep, e_loc=mc.e_total // ep,
                        t_loc=t_loc, top_k=mc.top_k, seed=seed)
    return [routed_counts(ti, mc, ep) for ti in trace]


def resolve_decode_sched(cfg, sched: str, n_slots: int, plan=None):
    """Size the decode-traffic MoE fragment's schedule for this server.

    Decode batches are small and skewed (a few hot experts dominate
    short-request traffic), so the schedule that serves them best is a
    routing-profile question — exactly what the cost-model-guided selector
    answers. For MoE archs this compiles the decode-profile fragment with
    ``--sched`` (``"auto"`` resolves through ``core/autoselect``), runs it
    through the simulator, and reports the resolution; non-MoE archs have
    no schedulable fragment and skip. Returns the report dict (or None).

    ``plan`` is the decode profile to size against — pass the online
    tuner's ``decode_plan(rows)`` to re-resolve from the *live* rolling
    population. By default the profile is replay-derived: the population
    mean of a synthesized Zipf decode trace at this server's token budget
    (:func:`decode_population`), not an analytic skew guess.
    """
    if cfg.family != "moe":
        print(f"--sched {sched}: {cfg.name!r} has no MoE fragment; "
              f"scheduling stack not engaged")
        return None
    from repro.core.autoselect import select
    from repro.core.odg import ScheduleConfig, build_moe_ffn_forward
    from repro.core.passes import Pipeline, pipeline_arg
    from repro.core.scheduler import compile_schedule
    from repro.core.simulator import simulate_unified
    from repro.launch.online import population_plan

    mc = cfg.moe
    # Decode profile sized to a busy step: every slot decodes one token
    # routed top_k ways, batched over a scheduling window.
    rows = max(1, n_slots * mc.top_k)
    if plan is None:
        ep = next(e for e in (4, 2, 1) if mc.e_total % e == 0)
        plan = population_plan(decode_population(mc, ep, max(ep, n_slots)),
                               total_rows=rows)
    ep, e_loc = plan.ep, plan.e_loc
    scfg = ScheduleConfig(ep=ep, e_loc=e_loc, rows=0, d_model=cfg.d_model,
                          d_ff=mc.d_expert, gmm_m_split=2 * ep,
                          gmm_split_mode="source_aligned", plan=plan)
    req = pipeline_arg(sched)
    if req == "auto":
        choice = select(plan, scfg, direction="forward")
        pipe, scfg, tag = choice.pipeline, choice.cfg, choice.tag
        predicted = choice.predicted_us
    else:
        pipe, tag, predicted = Pipeline.of(*req), sched, None
    res = simulate_unified(compile_schedule(build_moe_ffn_forward(scfg),
                                            pipeline=pipe))
    pred = f" predicted={predicted:.1f}us" if predicted is not None else ""
    print(f"decode schedule [{tag}] pipeline={pipe.names()} "
          f"ep={ep} rows/cell={rows} simulated={res.makespan_us:.1f}us"
          f"{pred} straggler={res.straggler_ratio:.2f}")
    return {"tag": tag, "pipeline": pipe.spec(),
            "makespan_us": res.makespan_us, "predicted_us": predicted}


class ContinuousBatcher:
    """Fixed-slot continuous batching over a batched KV cache.

    ``moe_impl`` threads a pluggable MoE executor into the jitted
    prefill/decode steps — pass ``OnlineMoE(...).impl`` to serve through
    plan-sized compiled schedules with live bucket refitting (the impl's
    ``pure_callback`` host fns run per step under the single jit trace, so
    hot swaps never retrace; decode batch ``n_slots`` and the prompt
    length must be divisible by the impl's ``ep``). ``admission`` arms the
    :meth:`offer` gate: queue-depth shedding plus a predicted-step-latency
    check priced on the ``decode_counts`` population
    (:func:`~repro.core.autoselect.predict_plan_us` units — the gate and
    any SLO assertion must share the predictor).
    """

    def __init__(self, cfg, params, n_slots: int, max_len: int, *,
                 moe_impl=None, admission=None, decode_counts=None,
                 cost=None):
        from repro.models import model as M
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.M = M
        self.cache = M.init_cache(cfg, n_slots, max_len,
                                  per_slot_len=True)
        self.active = np.zeros(n_slots, bool)
        self.req_id = [-1] * n_slots
        self.generated: dict[int, list[int]] = {}
        self.budget = np.zeros(n_slots, np.int32)
        self.cur_tok = jnp.zeros((n_slots, 1), jnp.int32)
        self.admission = admission
        self.decode_counts = decode_counts
        self.cost = cost
        self.shed: list[int] = []        # shed request ids — reported
        self.deferred = 0                # defer verdicts (retried later)
        self.instant_done: list[int] = []

        self._decode = jax.jit(
            lambda p, t, c: M.decode_step(cfg, p, t, c, moe_impl=moe_impl))
        # Slot prefill: run the prompt through with batch=1 and scatter the
        # resulting cache slice into the batched cache at `slot`.
        self._prefill1 = jax.jit(
            lambda p, toks: M.prefill(cfg, p, {"tokens": toks},
                                      max_len=max_len, moe_impl=moe_impl))

    def _scatter_slot(self, slot: int, cache1):
        """Write a batch-1 prefill cache into slot ``slot``.

        Dispatch on the *batch-1 marker* of cache1, never on absolute sizes
        (L == n_slots is a real collision otherwise): stacked leaves are
        [L, 1, …] → batch at axis 1; unstacked are [1, …] → axis 0;
        per-slot len leaves are one dim short of their target."""
        def upd(c, c1):
            if c.ndim == 0 or c1.ndim == 0:
                return c1 if c.ndim == 0 else c
            if c.ndim == c1.ndim + 1:
                # per-slot len [L, B] ← scalar-len prefill [L]
                return c.at[:, slot].set(c1)
            if c1.ndim >= 2 and c1.shape[1] == 1 \
                    and c.shape[0] == c1.shape[0]:
                return c.at[:, slot].set(c1[:, 0])   # stacked [L, B, ...]
            if c1.shape[0] == 1:
                return c.at[slot].set(c1[0])         # unstacked [B, ...]
            raise ValueError(f"unrecognized cache leaf {c.shape}/{c1.shape}")
        self.cache = jax.tree.map(upd, self.cache, cache1)

    def _predict_step_us(self, n_active: int) -> float:
        """Predicted decode-step latency at ``n_active`` busy slots,
        priced on the decode-population profile rescaled to that size."""
        if self.decode_counts is None or self.cfg.family != "moe":
            return 0.0
        from repro.core.autoselect import predict_plan_us
        from repro.launch.online import population_plan
        mc = self.cfg.moe
        plan = population_plan(self.decode_counts,
                               total_rows=max(1, n_active) * mc.top_k)
        return predict_plan_us(plan, self.cfg.d_model, mc.d_expert,
                               cost=self.cost)

    def admit(self, rid: int, prompt: np.ndarray, max_new: int) -> bool:
        if max_new > 1 and self.active.all():
            return False
        logits, cache1 = self._prefill1(
            self.params, jnp.asarray(prompt[None, :], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
        self.generated[rid] = [tok]
        if max_new <= 1:
            # Prefill already produced the whole response: finish without
            # occupying a slot. (Routing through a slot would set the
            # budget to 0, which the decode loop treats as "decode once
            # more" — over-generating by a token.)
            self.instant_done.append(rid)
            return True
        slot = int(np.where(~self.active)[0][0])
        self._scatter_slot(slot, cache1)
        self.cur_tok = self.cur_tok.at[slot, 0].set(tok)
        self.active[slot] = True
        self.req_id[slot] = rid
        self.budget[slot] = max_new - 1
        return True

    def offer(self, rid: int, prompt: np.ndarray, max_new: int,
              queue_depth: int = 0) -> str:
        """Admission-gated :meth:`admit`: ``'admit' | 'defer' | 'shed'``.

        With no :class:`~repro.launch.online.AdmissionConfig` this is
        plain admit-or-defer (slot availability only). With one, requests
        past ``max_queue`` queued behind this offer are shed — recorded in
        ``self.shed``, never silently dropped — and a request whose
        admission would push the predicted decode-step latency past
        ``slo_us`` is deferred (unless the server is idle: the first
        request always gets in, the progress guarantee). Deferred requests
        stay the caller's to retry; shed ones are final.
        """
        adm = self.admission
        if adm is None:
            if self.admit(rid, prompt, max_new):
                return "admit"
            self.deferred += 1
            return "defer"
        if adm.shed and queue_depth > adm.max_queue:
            self.shed.append(rid)
            return "shed"
        n_active = int(self.active.sum())
        if (max_new > 1 and n_active >= 1
                and self._predict_step_us(n_active + 1) > adm.slo_us):
            self.deferred += 1
            return "defer"
        if self.admit(rid, prompt, max_new):
            return "admit"
        self.deferred += 1
        return "defer"

    def step(self) -> list[int]:
        """One batched decode step for every active slot; returns finished
        request ids."""
        done0, self.instant_done = self.instant_done, []
        if not self.active.any():
            return done0
        logits, self.cache = self._decode(self.params, self.cur_tok,
                                          self.cache)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        self.cur_tok = nxt[:, None]
        done = done0
        for s in range(self.n_slots):
            if not self.active[s]:
                continue
            self.generated[self.req_id[s]].append(int(nxt[s]))
            self.budget[s] -= 1
            if self.budget[s] <= 0:
                done.append(self.req_id[s])
                self.active[s] = False
                self.req_id[s] = -1
        return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--sched", default=None, metavar="PIPELINE",
                    help="size the decode-traffic MoE fragment's schedule "
                         "before serving: 'auto' (cost-model-guided "
                         "selection), a core.passes.SCHED_PIPELINES name, "
                         "or a comma-separated pass list")
    ap.add_argument("--online-refit", action="store_true",
                    help="serve the MoE fragment through plan-sized "
                         "compiled schedules with an OnlineTuner "
                         "observing live routing and hot-swapping the "
                         "bucket ladder (MoE archs only)")
    ap.add_argument("--slo-us", type=float, default=0.0,
                    help="arm admission control: defer admissions whose "
                         "predicted decode-step latency (cost-model "
                         "units) exceeds this, shed past --max-queue")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="queue depth beyond which offers are shed "
                         "(with --slo-us)")
    args = ap.parse_args()

    if args.sched:
        # Validate eagerly, for every arch: an unknown pipeline/pass name
        # must be an argparse error, not a traceback (or a silent no-op on
        # non-MoE archs).
        from repro.core.passes import pipeline_arg
        try:
            pipeline_arg(args.sched)
        except KeyError as e:
            ap.error(str(e))

    from repro.configs import get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M
    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    if args.sched:
        resolve_decode_sched(cfg, args.sched, args.slots)

    online = moe_impl = None
    decode_counts = None
    n_slots = args.slots
    admission = None
    if cfg.family == "moe":
        from repro.launch.online import (AdmissionConfig, size_slots,
                                         size_capacity_factor)
        mc = cfg.moe
        ep = next(e for e in (4, 2, 1)
                  if mc.e_total % e == 0 and args.slots % e == 0
                  and args.prompt_len % e == 0)
        decode_counts = decode_population(mc, ep, args.slots)
        if args.slo_us > 0:
            admission = AdmissionConfig(slo_us=args.slo_us,
                                        max_queue=args.max_queue)
            sized = size_slots(decode_counts, mc, ep, args.slo_us)
            n_slots = max(ep, min(args.slots, sized))
            cf = size_capacity_factor(decode_counts)
            print(f"admission: slo={args.slo_us:.1f}us sized slots="
                  f"{sized} -> serving {n_slots}/{args.slots}, "
                  f"p99 capacity factor={cf:.2f}")
        if args.online_refit:
            from repro.core.buckets import fit_ladder
            from repro.launch.dropless import DroplessConfig
            from repro.launch.online import OnlineMoE, OnlineTuner
            if n_slots % ep or args.prompt_len % ep:
                ap.error(f"--online-refit needs slots and prompt-len "
                         f"divisible by ep={ep}")
            tuner = OnlineTuner(initial=fit_ladder(decode_counts, 6, 1.0),
                                d_model=cfg.d_model, d_ff=mc.d_expert)
            online = OnlineMoE(DroplessConfig(ep=ep, bucket=tuner.spec,
                                              pipeline=("ratr",)), tuner)
            moe_impl = online.impl
            print(f"online refit: ep={ep} seed spec={tuner.spec}")
    elif args.online_refit:
        print(f"--online-refit: {cfg.name!r} has no MoE fragment; skipped")

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, cfg.vocab, args.prompt_len)
               for i in range(args.requests)}

    b = ContinuousBatcher(cfg, params,
                          n_slots=n_slots,
                          max_len=args.prompt_len + args.max_new + 1,
                          moe_impl=moe_impl, admission=admission,
                          decode_counts=decode_counts)
    pending = list(range(args.requests))
    finished = []
    t0 = time.perf_counter()
    steps = 0
    while pending or b.active.any() or b.instant_done:
        while pending:
            verdict = b.offer(pending[0], prompts[pending[0]],
                              args.max_new, queue_depth=len(pending))
            if verdict == "defer":
                break
            pending.pop(0)         # admitted or shed — either way consumed
        finished += b.step()
        steps += 1
        if steps > 10000:
            raise RuntimeError("serving loop did not converge")
    dt = time.perf_counter() - t0
    total_toks = sum(len(v) for v in b.generated.values())
    shed = f", {len(b.shed)} shed" if b.shed else ""
    print(f"served {len(finished)} requests / {total_toks} tokens in "
          f"{dt:.1f}s over {steps} batched steps "
          f"({n_slots} slots, continuous batching{shed})")
    assert sorted(finished + b.shed) == sorted(prompts), \
        "every request must finish or be reported shed"
    for rid in list(prompts)[:2]:
        if rid in b.generated:
            print(f"  req{rid}: …{prompts[rid][-4:].tolist()} → "
                  f"{b.generated[rid][:10]}…")
    if online is not None:
        s = online.tuner.summary()
        print(f"online tuner: steps={s['steps']} refits={s['refits']} "
              f"swaps={s['swaps']} spec={s['spec']} "
              f"selector={s['selector']}")
        if args.sched:
            # Re-resolve the decode schedule from the *live* rolling
            # population the server just observed.
            rows = max(1, n_slots * cfg.moe.top_k)
            resolve_decode_sched(cfg, args.sched, n_slots,
                                 plan=online.tuner.decode_plan(rows))
    return b


if __name__ == "__main__":
    main()
