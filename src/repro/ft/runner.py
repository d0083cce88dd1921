"""Fault-tolerant training driver.

Production loop responsibilities, all testable on CPU:

* **checkpoint/restart** — periodic atomic checkpoints; on start, auto-resume
  from the newest complete one (crash-as-restart semantics). Data order is
  counter-based (``SyntheticStream``), so a restart replays the exact batch
  sequence with no state beyond the step number.
* **straggler mitigation** — per-step wall-time watchdog with an EWMA
  baseline; steps slower than ``straggler_factor ×`` EWMA are logged and
  counted. When the step metrics carry ``rank_time_us`` (no train step of
  this package emits it; the steps of ``tests/ftharness.py`` and
  ``tests/test_elastic.py`` do), a per-rank EWMA accumulates alongside —
  the observed-time vector
  :meth:`RunState.cost_model` normalizes into ``CostModel(rank_bias=)`` so a
  persistently slow rank becomes the *compile-time* critical rank that
  ``critical_rank_first`` / ``autoselect`` schedule around.
* **fault injection** — ``inject_fault(step)`` raising mid-run simulates a
  node loss; the driver checkpoints at boundaries, so recovery loses at most
  ``ckpt_every - 1`` steps. Run history (``metrics_log``/``stragglers``)
  rides the checkpoint manifest, so a resumed run's merged log spans the
  crash instead of silently dropping pre-crash entries.
* **elastic rescale** — restore() maps logical checkpoints onto any mesh;
  with an :class:`ElasticContext` the *plan world* participates too: live
  :class:`~repro.core.routing.RoutingPlan`\\ s persisted in the manifest are
  remapped onto the surviving ranks (``core.elastic.remap_plan``) and the
  SSC cache is re-keyed — not flushed — for the new mesh size
  (``SSCCache.rekey_for_mesh``).
* **spans and counters** — each iteration runs under
  ``jax.profiler.StepTraceAnnotation("train_step", step_num=step)``, and its
  phases under ``TraceAnnotation`` spans: ``train_loop/hook``
  (``inject_fault``), ``train_loop/batch`` (``stream.sharded_batch``),
  ``train_loop/dispatch`` (``step_fn``), ``train_loop/wait`` (the wait on
  the loss), ``train_loop/log`` (logging and straggler bookkeeping) and
  ``train_loop/ckpt`` (save and clean-up). The profiler records them, when
  it runs, on the clock of the device trace; otherwise each costs about
  a microsecond of host time. The MoE counters a step's metrics carry
  (``moe_routed``, ``moe_dropped``, ``moe_slots``, ``moe_rows_issued``)
  are fetched with the loss, in one transfer, into each ``metrics_log``
  entry; no step waits on more than the loss.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.checkpoint import ckpt as CK

span = jax.profiler.StepTraceAnnotation
phase = jax.profiler.TraceAnnotation
# The train step's MoE counters (``launch/steps.py``), logged where present.
MOE_COUNTERS = ("moe_routed", "moe_dropped", "moe_slots",
                "moe_rows_issued")
LOGGED = ("loss", "grad_norm") + MOE_COUNTERS


@dataclasses.dataclass
class FTConfig:
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    keep: int = 3
    straggler_factor: float = 2.5
    ewma_alpha: float = 0.2


@dataclasses.dataclass
class ElasticContext:
    """Mesh-aware restore context: what the elastic rescale path needs.

    ``ep`` is the mesh size of *this* run. Live plans the caller registers
    in ``plans`` (name → RoutingPlan) are persisted with every checkpoint;
    on a resume whose manifest recorded a different mesh size they come
    back **remapped** onto the current mesh (survivors keep their rows,
    experts re-chunk in global order — see ``core/elastic.py``), ready to
    compile through the normal ``plan_from_routing`` → SSC path.

    ``dead_ranks`` names which old-mesh ranks were lost (shrink only);
    when ``None`` a shrink defaults to dropping the tail ranks — the
    conventional contraction of a torn-down trailing host. ``cache`` is an
    ``SSCCache`` (or anything with ``rekey_for_mesh``) to re-key on rescale.
    """

    ep: int
    cache: Optional[object] = None
    plans: dict = dataclasses.field(default_factory=dict)
    dead_ranks: Optional[tuple] = None


@dataclasses.dataclass
class RunState:
    step: int
    params: object
    opt_state: object
    metrics_log: list
    stragglers: list
    resumed_from: Optional[int] = None
    # Per-rank step-time EWMA (None until a step reports "rank_time_us").
    rank_time_ewma: Optional[list] = None
    # One record per rescale the restore path performed.
    elastic_events: list = dataclasses.field(default_factory=list)

    def cost_model(self, base=None):
        """Observed-time-biased CostModel (straggler feedback loop).

        With no per-rank observations yet this is just ``base`` (or the
        compile-time default); otherwise the EWMA vector normalizes into
        ``CostModel(rank_bias=)`` via ``core.elastic.observed_cost_model``.
        """
        from repro.core.elastic import observed_cost_model
        return observed_cost_model(self.rank_time_ewma, base)


def _run_extra(elastic: Optional[ElasticContext], metrics_log: list,
               stragglers: list, rank_ewma: Optional[list]) -> dict:
    """JSON-safe manifest ``extra``: run history + the elastic plan world."""
    extra: dict = {
        "metrics_log": metrics_log,
        "stragglers": [list(s) for s in stragglers],
    }
    if rank_ewma is not None:
        extra["rank_time_ewma"] = [float(x) for x in rank_ewma]
    if elastic is not None:
        extra["ep"] = elastic.ep
        extra["plans"] = {
            name: np.asarray(p.counts, dtype=np.int64).tolist()
            for name, p in elastic.plans.items()}
    return extra


def _elastic_restore(elastic: ElasticContext, prev_ep: int, extra: dict,
                     rank_ewma: Optional[list], start_step: int,
                     events: list) -> Optional[list]:
    """Remap the persisted plan world from ``prev_ep`` onto ``elastic.ep``.

    Mutates ``elastic.plans`` in place (remapped plans replace whatever the
    caller registered under the same names), re-keys ``elastic.cache``, and
    returns the survivor-restricted per-rank EWMA vector.
    """
    from repro.core.elastic import remap_plan, surviving_ranks
    from repro.core.routing import RoutingPlan

    if elastic.ep < prev_ep:
        dead = (tuple(int(r) for r in elastic.dead_ranks)
                if elastic.dead_ranks is not None
                else tuple(range(elastic.ep, prev_ep)))
        survivors = surviving_ranks(prev_ep, dead)
        if len(survivors) != elastic.ep:
            raise ValueError(
                f"dead_ranks={dead} leaves {len(survivors)} survivors of "
                f"the checkpoint's {prev_ep}-rank mesh, but this run has "
                f"ep={elastic.ep}")
        kw = {"dead_ranks": dead}
    else:
        survivors = tuple(range(prev_ep))
        kw = {"new_ep": elastic.ep}

    for name, counts in (extra.get("plans") or {}).items():
        old = RoutingPlan.from_counts(np.asarray(counts, dtype=np.int64))
        elastic.plans[name] = remap_plan(old, **kw)

    if rank_ewma is not None and len(rank_ewma) == prev_ep:
        kept = [float(rank_ewma[r]) for r in survivors]
        # Re-admitted ranks start at the survivors' mean — unbiased until
        # they report their own times.
        fill = float(np.mean(kept)) if kept else 0.0
        rank_ewma = kept + [fill] * (elastic.ep - len(kept))

    rekey = None
    if elastic.cache is not None:
        rekey = elastic.cache.rekey_for_mesh(elastic.ep)
    events.append({"step": start_step, "from_ep": prev_ep,
                   "to_ep": elastic.ep, "survivors": list(survivors),
                   "plans": sorted(elastic.plans), "cache": rekey})
    return rank_ewma


def train_loop(*, step_fn, params, opt_state, stream, mesh, batch_sharding,
               n_steps: int, ft: FTConfig,
               inject_fault: Optional[Callable[[int], None]] = None,
               log_every: int = 10,
               elastic: Optional[ElasticContext] = None) -> RunState:
    """Run (or resume) ``n_steps`` of training with FT behaviours."""
    start_step = 0
    resumed_from = None
    metrics_log: list = []
    stragglers: list = []
    rank_ewma: Optional[list] = None
    elastic_events: list = []
    latest = CK.latest_step_dir(ft.ckpt_dir)
    if latest is not None:
        # Restore each leaf straight onto the live state's sharding, so a
        # step compiled for those shardings accepts the restored state.
        leaves = jax.tree.leaves((params, opt_state))
        shardings = (jax.tree.map(lambda a: a.sharding, (params, opt_state))
                     if all(isinstance(a, jax.Array) for a in leaves)
                     else None)
        (params, opt_state), manifest = CK.restore(
            latest, (params, opt_state), shardings)
        start_step = manifest["step"]
        resumed_from = start_step
        extra = manifest.get("extra") or {}
        # Merged run history: pre-crash entries come back from the manifest
        # so the resumed log spans the crash (entries are logged with the
        # post-increment step, hence always <= the checkpoint's step).
        metrics_log = [m for m in extra.get("metrics_log", [])
                       if m.get("step", 0) <= start_step]
        stragglers = [tuple(s) for s in extra.get("stragglers", [])]
        rank_ewma = extra.get("rank_time_ewma")
        prev_ep = extra.get("ep")
        if elastic is not None and prev_ep and prev_ep != elastic.ep:
            rank_ewma = _elastic_restore(elastic, prev_ep, extra, rank_ewma,
                                         start_step, elastic_events)

    ewma = None
    step = start_step
    while step < n_steps:
        with span("train_step", step_num=step):
            if inject_fault is not None:
                with phase("train_loop/hook"):
                    inject_fault(step)  # may raise — simulating a node loss
            with phase("train_loop/batch"):
                batch = stream.sharded_batch(step, mesh, batch_sharding)
            t0 = time.time()
            with phase("train_loop/dispatch"):
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     batch)
            with phase("train_loop/wait"):
                jax.block_until_ready(metrics["loss"])
            dt = time.time() - t0

            with phase("train_loop/log"):
                if ewma is None:
                    ewma = dt
                elif dt > ft.straggler_factor * ewma:
                    stragglers.append((step, dt, ewma))
                ewma = (1 - ft.ewma_alpha) * ewma + ft.ewma_alpha * dt

                rt = metrics.get("rank_time_us")
                if rt is not None:
                    rt = [float(x) for x in np.ravel(np.asarray(rt))]
                    if rank_ewma is None or len(rank_ewma) != len(rt):
                        rank_ewma = rt
                    else:
                        a = ft.ewma_alpha
                        rank_ewma = [(1 - a) * e + a * x
                                     for e, x in zip(rank_ewma, rt)]

                step += 1
                if step % log_every == 0 or step == n_steps:
                    got = jax.device_get({k: metrics[k] for k in LOGGED
                                          if k in metrics})
                    entry = {"step": step, "loss": float(got["loss"]),
                             "grad_norm": float(got["grad_norm"]),
                             "step_time_s": dt}
                    entry.update((k, int(got[k])) for k in MOE_COUNTERS
                                 if k in got)
                    metrics_log.append(entry)
            if step % ft.ckpt_every == 0 or step == n_steps:
                with phase("train_loop/ckpt"):
                    CK.save(ft.ckpt_dir, step, (params, opt_state),
                            extra=_run_extra(elastic, metrics_log,
                                             stragglers, rank_ewma))
                    CK.gc_old(ft.ckpt_dir, keep=ft.keep)

    return RunState(step=step, params=params, opt_state=opt_state,
                    metrics_log=metrics_log, stragglers=stragglers,
                    resumed_from=resumed_from, rank_time_ewma=rank_ewma,
                    elastic_events=elastic_events)
