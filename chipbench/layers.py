#!/usr/bin/env python3
"""Device time by layer of one cell's train step, read from what the program
records: its named scopes, its loop's spans and its MoE counters.

    python3 chipbench/layers.py --workload <cell> --seed <n>

Builds the cell's step as ``run.py`` does, runs ``WARM_STEPS`` steps of it
through the trainer's loop, ``repro.ft.runner.train_loop``, then
``TRACE_STEPS`` more under the profiler, and prints one JSON object:

- ``steps``: the steps whose ``train_step`` span lies inside the traced
  window, and ``step_ms``, their spans' mean length with the profiler on;
- ``layer_ms``: device ms per ``train_step`` span for each named scope,
  ``other`` and ``busy_total`` (``scopes.layer_ms``);
- ``idle_ms``: device idle per step by the loop phase the host was in, on
  the chip with most idle (``scopes.idle_by_phase``);
- ``counters``: the MoE counters of each step whose span was traced;
- ``readings``: what those give, per step: ``moe_dispatch_ms``,
  ``moe_expert_ffn_ms``, ``moe_combine_ms``, ``attention_ms`` and
  ``optimizer_ms`` (self time under a scope), ``expert_ffn_roofline``
  (``expert_ffn_least_s`` over ``moe_expert_ffn_ms``), ``expert_slot_fill``
  ((``moe_routed`` - ``moe_dropped``) / ``moe_slots``), ``idle_input_ms``
  (idle in ``train_loop/batch``) and ``idle_loop_ms`` (in
  ``train_loop/wait``, ``/log`` or ``/hook``). A reading whose source the
  program does not record is left out.

Nothing is checked against the reference here; ``run.py`` does that.
Exits non-zero when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

WARM_STEPS = 8
TRACE_STEPS = 6
COUNTERS = ("moe_routed", "moe_dropped", "moe_slots")
SCOPE_READINGS = {"moe_dispatch_ms": "moe/dispatch",
                  "moe_expert_ffn_ms": "moe/expert_ffn",
                  "moe_combine_ms": "moe/combine",
                  "attention_ms": "attention", "optimizer_ms": "optimizer"}
IDLE_LOOP = ("train_loop/wait", "train_loop/log", "train_loop/hook")


class TraceDone(Exception):
    """Raised from the loop's ``inject_fault`` hook after the traced steps."""


def expert_ffn_least_s(cfg: dict, rows: float, peaks: dict):
    """``(seconds, bound)``: the least time the chip needs for the routed
    SwiGLU expert FFN, forward and backward, on ``rows`` kept routed rows
    (summed over layers), and which bound (``compute`` or ``memory``) sets
    it.

    FLOPs: 2 x d x 2f (gate and up) + 2 x f x d (down) a row forward, three
    times that with the backward; no padding rows, no recompute. Bytes: the
    forward reads the expert weights and the rows in and writes the rows
    out; the backward reads the weights, writes their gradients, reads the
    rows in and their output gradients and writes the input gradients. So
    the weights move three times and the rows five, at the configuration's
    element size; intermediates are taken to stay on the core.
    """
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    width = {"bfloat16": 2, "float32": 4}[cfg["dtype"]]
    weights = (cfg["num_hidden_layers"] * cfg["num_local_experts"]
               * 3 * d * f * width)
    compute = 3 * (2 * d * 2 * f + 2 * f * d) * rows \
        / peaks["bf16_flops_per_s"]
    memory = (3 * weights + 5 * rows * d * width) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def readings(cfg: dict, peaks: dict, chips: int, layer_ms, idle_ms,
             counts: list) -> dict:
    """The per-step readings from one traced run's layer times, idle times
    by phase and the counters of its traced steps."""
    out = {}
    if layer_ms is not None:
        out.update((k, layer_ms[s]) for k, s in SCOPE_READINGS.items())
    if idle_ms is not None:
        out["idle_input_ms"] = idle_ms.get("train_loop/batch", 0.0)
        out["idle_loop_ms"] = sum(idle_ms.get(p, 0.0) for p in IDLE_LOOP)
    if not counts or not all(k in c for c in counts for k in COUNTERS):
        return out
    kept = sum(c["moe_routed"] - c["moe_dropped"] for c in counts)
    out["expert_slot_fill"] = 100.0 * kept / sum(c["moe_slots"]
                                                 for c in counts)
    if out.get("moe_expert_ffn_ms"):
        least_s, bound = expert_ffn_least_s(cfg, kept / len(counts), peaks)
        out["expert_ffn_roofline"] = (100.0 * least_s / chips
                                      / (out["moe_expert_ffn_ms"] / 1e3))
        out["expert_ffn_bound"] = bound
    return out


class Hook:
    """The loop's step function and hook: keeps each step's MoE counters,
    and traces the ``TRACE_STEPS`` steps after the warm ones."""

    def __init__(self, compiled, warm: int, trace_dir: str):
        import jax

        self.jax, self.compiled = jax, compiled
        self.warm, self.trace_dir = warm, trace_dir
        self.counts = []            # the MoE counters of every step
        self.last = None
        self._window = None

    def step_fn(self, params, opt_state, batch):
        self.last = out = self.compiled(params, opt_state, batch)
        self.counts.append({k: out[2][k] for k in COUNTERS if k in out[2]})
        return out

    def __call__(self, step: int):
        if step == self.warm:
            self.jax.profiler.start_trace(self.trace_dir)
            self._window = self.jax.profiler.TraceAnnotation(
                "traced_window")
            self._window.__enter__()
        elif step == self.warm + TRACE_STEPS:
            self.jax.block_until_ready(self.last)
            self._window.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            raise TraceDone


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    if not 0 <= args.seed < 2 ** 64:
        ap.error("--seed must be a whole number in [0, 2**64)")
    return args


def main(argv=None, require=None) -> int:
    from chipbench import harness, program

    args = parse(argv)
    cell = harness.Cell(args.workload)
    program.import_program()
    devices = (require or harness.require_accelerator)(cell.chips)
    import jax

    from chipbench import scopes, tracereduce, traffic, train_cell
    from repro.ft.runner import FTConfig, train_loop

    tc = train_cell.TrainCell(cell, devices)
    # as_text() is None where the backend keeps no HLO text.
    op_scope = scopes.op_scopes(tc.prog.step.as_text() or "")
    trace_dir = os.path.join(train_cell.OUT_DIR, "trace",
                             cell.name + ".layers")
    ckpt_dir = os.path.join(train_cell.OUT_DIR, "ckpt")
    for d in (trace_dir, ckpt_dir):
        shutil.rmtree(d, ignore_errors=True)
    hook = Hook(tc.prog.step, WARM_STEPS, trace_dir)
    stream = traffic.Stream(tc.tr, tc.cfg["vocab_size"], tc.chips,
                            args.seed, tc.prog.batch_shardings)
    params, opt_state = tc.init(tc.ref_mod.seed_key(args.seed))
    with jax.set_mesh(tc.prog.mesh):
        try:
            train_loop(step_fn=hook.step_fn, params=params,
                       opt_state=opt_state, stream=stream,
                       mesh=tc.prog.mesh, batch_sharding=None,
                       n_steps=train_cell.NEVER,
                       ft=FTConfig(ckpt_dir=ckpt_dir,
                                   ckpt_every=train_cell.NEVER),
                       inject_fault=hook, log_every=1)
        except TraceDone:
            pass
    del params, opt_state
    counts = [{k: int(v) for k, v in c.items()}
              for c in jax.device_get(hook.counts)]
    hook.last = None

    trace = tracereduce.load(trace_dir)
    trace["program"] = scopes.program_spans(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    spans = scopes.steps(trace)
    traced = [st for st, _, _ in spans]
    layer_ms = scopes.layer_ms(trace, op_scope)
    idle_ms = scopes.idle_by_phase(trace)
    step_counts = [dict(counts[st], step=st) for st in traced]
    dev0 = devices[0]
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "device": {"kind": dev0.device_kind, "count": tc.chips},
        "steps": traced,
        "step_ms": (sum(e - s for _, s, e in spans) / len(spans) / 1e6
                    if spans else None),
        "layer_ms": layer_ms, "idle_ms": idle_ms,
        "counters": step_counts,
        "readings": readings(tc.cfg, harness.peaks(dev0.device_kind),
                             tc.chips, layer_ms, idle_ms,
                             [counts[st] for st in traced])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
