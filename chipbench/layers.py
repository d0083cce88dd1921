"""The per-step layer readings of a traced benchmark run, read from what the
program records: its named scopes, its loop's spans and its MoE counters.

``train_cell`` hands the readers under ``metrics/`` a run that holds the
device ms a ``train_step`` span under each named scope (``layer_ms``), the
device idle a step by loop phase (``idle_ms``) and the integer counters of
the checked steps (``counts``); each reader takes one of ``readings``
through ``reading``, so that each formula has one copy:

- ``moe_dispatch_ms``, ``moe_expert_ffn_ms``, ``moe_combine_ms``,
  ``attention_ms`` and ``optimizer_ms``: self time under a scope;
- ``idle_input_ms`` (idle in ``train_loop/batch``) and ``idle_loop_ms``
  (in ``train_loop/wait``, ``/log`` or ``/hook``);
- ``expert_slot_fill``: (``moe_routed`` - ``moe_dropped``) / ``moe_slots``;
- ``expert_ffn_roofline``: ``expert_ffn_least_s`` of the configuration's
  architecture module on the kept rows, per chip, over
  ``moe_expert_ffn_ms``.

The counters are those of the checked steps, the first steps from the
seed's weights, so the two readings they enter depend on the seed alone and
not on how many steps the window held. A reading whose source the program
does not record is left out.
"""

from __future__ import annotations

import numpy as np

COUNTERS = ("moe_routed", "moe_dropped", "moe_slots")
SCOPE_READINGS = {"moe_dispatch_ms": "moe/dispatch",
                  "moe_expert_ffn_ms": "moe/expert_ffn",
                  "moe_combine_ms": "moe/combine",
                  "attention_ms": "attention", "optimizer_ms": "optimizer"}
IDLE_LOOP = ("train_loop/wait", "train_loop/log", "train_loop/hook")


def int_counters(metrics: dict) -> dict:
    """The integer scalars of a step's metrics dict, by the names the
    program gave them (the MoE layer's ``moe_routed``, ``moe_dropped``,
    ``moe_slots``, and any it adds)."""
    return {k: v for k, v in metrics.items()
            if getattr(v, "ndim", None) == 0
            and np.issubdtype(v.dtype, np.integer)}


def readings(arch, cfg: dict, peaks: dict, chips: int, layer_ms, idle_ms,
             counts: list) -> dict:
    """The per-step readings from one traced run's layer times, idle times
    by phase and the counters of its checked steps; ``arch`` is the
    configuration's architecture module (``harness.Cell.arch``)."""
    out = {}
    if layer_ms is not None:
        out.update((k, layer_ms[s]) for k, s in SCOPE_READINGS.items()
                   if s in layer_ms)
    if idle_ms is not None:
        out["idle_input_ms"] = idle_ms.get("train_loop/batch", 0.0)
        out["idle_loop_ms"] = sum(idle_ms.get(p, 0.0) for p in IDLE_LOOP)
    if not counts or not all(k in c for c in counts for k in COUNTERS):
        return out
    kept = sum(c["moe_routed"] - c["moe_dropped"] for c in counts)
    out["expert_slot_fill"] = 100.0 * kept / sum(c["moe_slots"]
                                                 for c in counts)
    if out.get("moe_expert_ffn_ms"):
        least_s, bound = arch.expert_ffn_least_s(cfg, kept / len(counts),
                                                 peaks)
        out["expert_ffn_roofline"] = (100.0 * least_s / chips
                                      / (out["moe_expert_ffn_ms"] / 1e3))
        out["expert_ffn_bound"] = bound
    return out


def reading(run, name: str):
    """One of ``readings`` of a traced benchmark run (``train_cell``), or
    None where the run holds nothing to read it from."""
    return readings(run.cell.arch, run.config, run.peaks, run.chips,
                    run.layer_ms, run.idle_ms, run.counts).get(name)
