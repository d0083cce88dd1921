"""One run of a training cell: set-up, the measured window, the check.

Set-up builds the program's step as ``train.main`` does (the compile cache
in the checkout, the ahead-of-time compile), makes the weights and the
optimizer state on the device from ``--seed`` in one jitted call, and drives
that state through the trainer's own loop, ``repro.ft.runner.train_loop``,
for ``SETUP_STEPS`` steps. The first ``CHECKED_STEPS`` of them are the steps
the reference follows; their losses, the first gradient (read from Adam's
first moment) and the parameters' change are read as they pass. The same
loop then runs on into the window, which opens at the start of step
``SETUP_STEPS`` and closes, through the loop's ``inject_fault`` hook, at the
first step start ``--seconds`` after it, once the last step's whole output
(parameters, optimizer state and metrics) is ready. No checkpoint is
written: ``ckpt_every`` lies beyond any window and the loop never reaches
its final save.

With ``--trace 1`` the loop runs ``TRACE_STEPS`` more steps under the
profiler after the window closes; the per-layer metrics read that trace, the
window before it, and what the trace gives by layer: device ms per step
under each scope of the configuration's architecture (``layer_ms``) and
device idle per step by the loop's phase (``idle_ms``). The traced line
carries those two. Every run keeps the integer counters of the checked
steps (``counts``), which depend on the seed alone and not on how many
steps the window held; the line carries them (``counters``) and
``routing.dropped_choices``, the program's own count of dropped choices
over the checked steps, beside the reference's estimate.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import time
import types

import numpy as np

from chipbench import (compare, harness, layers, program, scopes,
                       tracereduce, traffic)
from chipbench.harness import BENCH_DIR, ROOT

CHECKED_STEPS = 3
SETUP_STEPS = CHECKED_STEPS + 1
TRACE_STEPS = 6
NEVER = 10 ** 12            # ckpt_every and n_steps: beyond any window
OUT_DIR = os.path.join(ROOT, ".chipbench")
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class WindowClosed(Exception):
    """Raised from the loop's ``inject_fault`` hook to end the run."""


def reference_module(cfg: dict):
    return harness.load_module(
        os.path.join(BENCH_DIR, "reference", cfg["reference"] + ".py"),
        f"reference {cfg['reference']!r}")


class Loop:
    """The step function and the hook that ``train_loop`` calls.

    ``step_fn`` calls the compiled step and keeps its output, and the
    integer counters of the checked steps; the hook, called before each
    step, takes the setup readings, opens and closes the window and the
    traced steps, and records each window step's start.
    """

    def __init__(self, compiled, seconds: float, trace_dir, readings,
                 compiles):
        import jax

        self.jax = jax
        self.compiled, self.seconds = compiled, seconds
        self.trace_dir, self.readings = trace_dir, readings
        self.compiles = compiles
        self.last = None
        self.metrics = []           # (loss, grad_norm) of every step
        self.counters = {}          # step -> its integer counters
        self.starts = []            # window step starts
        self.t0 = self.t_end = None
        self.window_steps = 0
        self.compiles_in_window = None
        self.traced_from = None

    def step_fn(self, params, opt_state, batch):
        out = self.compiled(params, opt_state, batch)
        self.last = out
        step = len(self.metrics)
        if step < CHECKED_STEPS:
            self.counters[step] = layers.int_counters(out[2])
        self.metrics.append((out[2]["loss"], out[2]["grad_norm"]))
        return out

    def hook(self, step: int):
        now = time.perf_counter()
        if step < SETUP_STEPS:
            self.readings(step, self.last)
            return
        if step == SETUP_STEPS:
            self.t0 = now
            self.compiles_at_t0 = self.compiles[0]
        elif self.traced_from is None and now - self.t0 >= self.seconds:
            self.jax.block_until_ready(self.last)
            self.t_end = time.perf_counter()
            self.window_steps = step - SETUP_STEPS
            self.compiles_in_window = self.compiles[0] - self.compiles_at_t0
            if self.trace_dir is None:
                raise WindowClosed
            self.traced_from = step
            self.jax.profiler.start_trace(self.trace_dir)
            self.trace_span = self.jax.profiler.TraceAnnotation(
                tracereduce.WINDOW_SPAN)
            self.trace_span.__enter__()
            return
        elif (self.traced_from is not None
              and step - self.traced_from >= TRACE_STEPS):
            self.jax.block_until_ready(self.last)
            self.trace_span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            raise WindowClosed
        if self.traced_from is None:
            self.starts.append(now)


class TrainCell:
    """A cell's program, built once, and what a run does with it: drive
    the trainer's loop from a seed, and run the reference on the batches
    the loop fed."""

    def __init__(self, cell: harness.Cell, devices):
        import jax
        import jax.numpy as jnp

        from repro.launch.compile_cache import enable_compile_cache

        self.cell, self.devices = cell, devices
        self.cfg, self.tr = cfg, tr = cell.config, cell.traffic
        self.ref_mod = ref_mod = reference_module(cfg)
        self.chips = cell.chips
        self.B, self.S = traffic.global_batch(tr, self.chips), int(
            tr["seq_len"])
        self.compiles = [0]

        def count(event, duration, **kw):
            if event == COMPILE_EVENT:
                self.compiles[0] += 1

        jax.monitoring.register_event_duration_secs_listener(count)
        enable_compile_cache()
        # Every program of the run in the cache, small ones too, so that
        # only a checkout's first run compiles.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

        self.prog = program.Program(cfg, cell.arch, self.B, self.S,
                                    devices)
        self.prog.check_layout(jax.eval_shape(
            lambda k: ref_mod.weights(cfg, k), ref_mod.seed_key(0)))
        self.init = self.prog.state_init(lambda k: ref_mod.weights(cfg, k))
        b1 = cfg["optimizer"]["betas"][0]

        @jax.jit
        def grad_norms(m):
            return ref_mod.leaf_norms(jax.tree.map(lambda x: x / (1 - b1),
                                                   m))

        @jax.jit
        def change_norms(master, k):
            return ref_mod.leaf_norms(jax.tree.map(
                lambda a, w: a - w.astype(jnp.float32), master,
                ref_mod.weights(cfg, k)))

        self._grad_norms, self._change_norms = grad_norms, change_norms
        self._weights0 = jax.jit(
            lambda k: ref_mod.weights(cfg, k),
            out_shardings=jax.sharding.SingleDeviceSharding(devices[0]))

    def drive(self, seed: int, seconds: float, trace_dir=None) -> dict:
        """Set-up steps and the window through ``train_loop``; returns the
        loop, the program's readings of its checked steps, the window's
        failed steps and the integer counters of the checked steps
        (step -> {name: value}). Frees the program's state before it
        returns."""
        import jax

        from repro.ft.runner import FTConfig, train_loop

        key_words = self.ref_mod.seed_key(seed)
        got = {}

        def readings(step, last):
            if step == 1:
                got["grad_norms"] = {k: float(v) for k, v in
                                     self._grad_norms(last[1]["m"]).items()}
            if step == CHECKED_STEPS:
                got["change_norms"] = {
                    k: float(v) for k, v in self._change_norms(
                        last[1]["master"], key_words).items()}

        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        loop = Loop(self.prog.step, seconds, trace_dir, readings,
                    self.compiles)
        stream = traffic.Stream(self.tr, self.cfg["vocab_size"], self.chips,
                                seed, self.prog.batch_shardings)
        ckpt_dir = os.path.join(OUT_DIR, "ckpt")
        shutil.rmtree(ckpt_dir, ignore_errors=True)  # nothing to resume
        params, opt_state = jax.block_until_ready(self.init(key_words))
        t_init = time.perf_counter()
        with jax.set_mesh(self.prog.mesh):
            try:
                train_loop(step_fn=loop.step_fn, params=params,
                           opt_state=opt_state, stream=stream,
                           mesh=self.prog.mesh, batch_sharding=None,
                           n_steps=NEVER,
                           ft=FTConfig(ckpt_dir=ckpt_dir, ckpt_every=NEVER),
                           inject_fault=loop.hook, log_every=1)
            except WindowClosed:
                pass
        del params, opt_state
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)
        vals = np.asarray(jax.device_get(loop.metrics), np.float64)
        got["losses"] = [float(x) for x in vals[:CHECKED_STEPS, 0]]
        win = vals[SETUP_STEPS:SETUP_STEPS + loop.window_steps]
        counts = {st: {k: int(v) for k, v in c.items()}
                  for st, c in jax.device_get(loop.counters).items()}
        for a in jax.tree.leaves(loop.last):
            a.delete()
        loop.last = None
        return {"loop": loop, "got": got, "peak": int(peak), "t_init": t_init,
                "failed": int(np.sum(~np.isfinite(win).all(axis=1))),
                "counts": counts}

    def reference(self, seed: int, precision: str = "float32",
                  fault=None) -> dict:
        """The reference's three steps from the seed's weights, on the
        batches the loop fed, on the first chip."""
        key_words = self.ref_mod.seed_key(seed)
        batches = [traffic.batch_np(self.tr, self.cfg["vocab_size"],
                                    self.chips, seed, t)
                   for t in range(CHECKED_STEPS)]
        return self.ref_mod.Reference(self.cfg, self.chips, precision,
                                      fault).run(
            lambda: self._weights0(key_words), batches,
            device=self.devices[0])

    def routing(self, ref: dict) -> dict:
        """Routed choices the program's capacity would drop, estimated from
        the float32 reference's routing (the program's own bf16 routing
        differs near ties) and the program's capacity rule."""
        C = self.prog.capacity(self.B * self.S // self.chips)
        counts = np.stack(ref["routed_counts"])
        return {"capacity": C, "routed_choices": int(counts.sum()),
                "dropped_choices_est": int(np.maximum(counts - C, 0).sum())}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, devices) -> int:
    t_enter = time.perf_counter()
    tc = TrainCell(cell, devices)
    t_built = time.perf_counter()
    trace_dir = os.path.join(OUT_DIR, "trace", cell.name) if trace else None
    d = tc.drive(seed, seconds, trace_dir)
    loop, got, failed = d["loop"], d["got"], d["failed"]
    setup_s = loop.t0 - t_start
    # Set-up's parts, in seconds: imports and the look for chips; the
    # step's build and its compile or cache read; weights and optimizer
    # state; the set-up steps with their readings.
    phases = {"imports_devices": t_enter - t_start,
              "build_compile": t_built - t_enter,
              "state_init": d["t_init"] - t_built,
              "setup_steps": loop.t0 - d["t_init"]}

    # The reference, once the program's state is freed, on the first chip.
    ref = tc.reference(seed)
    read = compare.readings(got, ref)
    correct, checks = compare.judge(read, cell.limits)
    routing = tc.routing(ref)

    dev0 = devices[0]
    run_ns = types.SimpleNamespace(
        cell=cell, config=tc.cfg, traffic=tc.tr, chips=tc.chips,
        seq_len=tc.S, tokens_per_step=tc.B * tc.S, setup_s=setup_s,
        window_s=loop.t_end - loop.t0, window_steps=loop.window_steps,
        step_starts=loop.starts, t_end=loop.t_end,
        peaks=harness.peaks(dev0.device_kind),
        counters={"compiles_in_window": loop.compiles_in_window},
        trace=None, layer_ms=None, idle_ms=None,
        counts=[d["counts"][st] for st in range(CHECKED_STEPS)])
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": tc.chips, "memory_peak_bytes": d["peak"]}
    breakdown = None
    if all("moe_dropped" in c for c in run_ns.counts):
        routing["dropped_choices"] = sum(c["moe_dropped"]
                                         for c in run_ns.counts)
    extra = {"routing": routing, "setup_phases": phases,
             "counters": [dict(c, step=st)
                          for st, c in enumerate(run_ns.counts)]}
    if trace:
        run_ns.trace = tracereduce.load(trace_dir)
        lo, hi = tracereduce.window(run_ns.trace)
        busy = [tracereduce.busy_ns(ev, lo, hi)
                for ev in run_ns.trace["devices"].values()]
        device["busy_s"] = float(np.mean(busy)) / 1e9 if busy else 0.0
        device["window_s"] = (hi - lo) / 1e9
        if run_ns.trace["devices"]:
            breakdown = tracereduce.breakdown(run_ns.trace)
        # What the trace held, for reading it by hand: its planes and
        # lines, and the reduced events the metrics were read from.
        with gzip.open(os.path.join(OUT_DIR, cell.name + ".trace.json.gz"),
                       "wt") as f:
            json.dump({"structure": tracereduce.structure(trace_dir),
                       "events": run_ns.trace, "breakdown": breakdown}, f)
        shutil.rmtree(trace_dir, ignore_errors=True)
        # Device time by the architecture's scopes, read with the op->scope
        # map of the compiled step (as_text() is None where the backend
        # keeps no HLO text); idle time by loop phase.
        names = cell.arch.SCOPES
        run_ns.layer_ms = scopes.layer_ms(
            run_ns.trace, scopes.op_scopes(tc.prog.step.as_text() or "",
                                           names), names)
        run_ns.idle_ms = scopes.idle_by_phase(run_ns.trace)
        extra.update(layer_ms=run_ns.layer_ms, idle_ms=run_ns.idle_ms)
    metrics = harness.read_metrics(
        cell, cell.per_layer if trace else cell.end_to_end, run_ns)

    correct = correct and failed == 0 and loop.window_steps > 0
    print(f"routing: {routing}", file=sys.stderr)
    print(f"setup phases: {phases}", file=sys.stderr)
    print(f"reference losses {ref['losses']} program {got['losses']}; "
          f"worst grad leaf {read['grad_leaf']}, worst change leaf "
          f"{read['change_leaf']}, left out of change_gap "
          f"{read['left_out']}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    line = harness.result_line(correct, loop.window_steps, failed, metrics,
                               device, checks, breakdown, extra=extra)
    print(line, flush=True)
    return 0
