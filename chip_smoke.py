#!/usr/bin/env python3
"""Bring-up smoke test of the MoE trainer on TPU chips.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # expert parallelism over four chips
    python3 chip_smoke.py --model moonlight   # Moonlight's step, one chip

One chip: trains granite-moe-3b-a800m at its published widths, its depth
cut to 4 layers, for 5 steps through ``repro.launch.train.main`` on a 1x1
mesh (the one-chip EP path: kept rows only, grouped GEMM); then runs one
full-width MoE layer through the EP path twice, with the grouped GEMM over
the kept rows and with the fixed-shape Pallas kernels over the capacity
buffer, and compares both with a float32 reference.

Moonlight: one training step of Moonlight-16B-A3B (MLA, shared experts,
sigmoid routing with its bias, a leading dense layer) at its published
widths, chip 0's share of an eight-chip expert-parallel group (8 of 64
experts, an eighth of the vocabulary), 1 dense + 5 MoE layers, through
``repro.launch.train.main``.

Four chips: trains the same model on a 1x4 expert-parallel mesh (12 experts
per chip) twice from the same seed and data, once with the all_to_all
exchange (``baseline``) and once with the ppermute ring
(``hyperparallel``), and checks that their losses and grad norms agree at
every step.

Exits non-zero on any failure, and before any phase when JAX finds no TPU.
The last line of stdout is a JSON object naming the device. Times printed
here are readings of a smoke run, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
ARCH = "granite-moe-3b-a800m"
# Published widths; depth is the only cut (4 of 32 layers fit one chip).
MODEL_ARGS = ["--arch", ARCH, "--layers", "4", "--seq", "2048"]
MOONLIGHT_ARGS = ["--arch", "moonlight-16b-a3b", "--layers", "6",
                  "--expert-share", "8", "--seq", "4096"]
# Tokens through the MoE layer check: C = 1000 rows per expert, not a
# multiple of the kernels' 128-row block.
MOE_TOKENS = 1000
# Checkpoints go to a fresh directory in the checkout (listed in
# .gitignore): train_loop resumes from whatever it finds there.
CKPT_DIR = os.path.join(REPO, ".smoke_ckpt")
# MoE layer: bf16 EP path vs the float32 reference, as a fraction of the
# reference's largest magnitude (a few bf16 roundings of a d=1536 layer).
MOE_TOL = 2e-2
# The two EP exchanges move the same arithmetic by different collectives;
# their bf16 sums differ only in order. Losses (~11) must agree within
# EP_LOSS_TOL and grad norms (~30) within EP_GNORM_RTOL of their size at
# every step. Step 1's loss checks the forward exchange; its grad norm and
# the later losses (after Adam updates from those gradients) check the
# backward through it.
EP_LOSS_TOL = 1e-2
EP_GNORM_RTOL = 1e-2


def require_tpu(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def train(steps: int, extra: list[str], model_args=None) -> list[dict]:
    """Train through the trainer's entry point; return its per-step log."""
    from repro.launch import train as T

    argv = (model_args or MODEL_ARGS) + [
        "--steps", str(steps), "--ckpt-dir", CKPT_DIR,
                         "--ckpt-every", str(steps)] + extra
    print("train.main", " ".join(argv), flush=True)
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    try:
        run = T.main(argv)
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    print(f"train.main wall {time.perf_counter() - t0:.1f}s "
          "(compile, init, steps and the final checkpoint)")
    log = run.metrics_log
    if run.resumed_from is not None or len(log) != steps:
        raise RuntimeError(f"expected {steps} fresh steps, got {len(log)} "
                           f"(resumed from {run.resumed_from})")
    for m in log:
        if not (math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"])):
            raise RuntimeError(f"non-finite step: {m}")
    return log


def print_peak_memory(devs) -> None:
    for d in devs:
        stats = d.memory_stats() or {}
        print(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
              f"bytes_limit {stats.get('bytes_limit')}")


def moe_layer_check(cfg, T: int) -> None:
    """One MoE layer of ``cfg`` on T tokens through ``make_moe_ep`` on a 1x1
    mesh, with the grouped GEMM over the kept rows and with the fixed-shape
    Pallas kernels, vs ``moe_dense_ref`` in float32."""
    import jax
    import jax.numpy as jnp

    from repro.models.moe import init_moe, moe_dense_ref
    from repro.parallel.ep import EPConfig, make_moe_ep

    mc = cfg.moe
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          init_moe(k1, cfg.d_model, mc))
    x = jax.random.normal(k2, (1, T, cfg.d_model), jnp.bfloat16)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), (params, x))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: moe_dense_ref(p, x, mc, cap=T))(*f32)
    scale = float(jnp.max(jnp.abs(ref)))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # Per-(destination, expert) capacity of every token: nothing drops.
    cf = mc.e_total / mc.top_k
    for use_pallas in (False, True):
        impl = make_moe_ep(mesh, EPConfig(capacity_factor=cf,
                                          use_pallas=use_pallas))
        with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
            y = jax.jit(lambda p, x: impl(p, x, mc))(params, x)
        err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - ref)))
        name = "pallas" if use_pallas else "ragged"
        print(f"moe_layer {name}: max_abs_err {err:.6g} max_abs_ref "
              f"{scale:.6g} tol {MOE_TOL * scale:.6g} "
              f"(T {T}, d_model {cfg.d_model}, experts {mc.e_total}, "
              f"top-{mc.top_k}, d_expert {mc.d_expert})")
        if not err <= MOE_TOL * scale:
            raise RuntimeError(f"{name} MoE layer off the float32 reference")


def one_chip(devs) -> None:
    from repro.configs import get_config

    train(5, ["--mesh", "1x1", "--global-batch", "2"])
    print_peak_memory(devs[:1])
    moe_layer_check(get_config(ARCH), MOE_TOKENS)


def moonlight(devs) -> None:
    train(1, ["--mesh", "1x1", "--global-batch", "2"], MOONLIGHT_ARGS)
    print_peak_memory(devs[:1])


def four_chips(devs) -> None:
    logs = {mode: train(3, ["--mesh", "1x4", "--mode", "ep_dp",
                            "--ep-mode", mode, "--global-batch", "4"])
            for mode in ("baseline", "hyperparallel")}
    print_peak_memory(devs[:4])
    ok = True
    for a, b in zip(logs["baseline"], logs["hyperparallel"]):
        dl = abs(a["loss"] - b["loss"])
        dg = abs(a["grad_norm"] - b["grad_norm"])
        gtol = EP_GNORM_RTOL * abs(a["grad_norm"])
        print(f"step {a['step']}: loss baseline {a['loss']:.6f} "
              f"hyperparallel {b['loss']:.6f} |diff| {dl:.6g} tol "
              f"{EP_LOSS_TOL}; grad_norm baseline {a['grad_norm']:.6f} "
              f"hyperparallel {b['grad_norm']:.6f} |diff| {dg:.6g} tol "
              f"{gtol:.6g}")
        ok &= dl <= EP_LOSS_TOL and dg <= gtol
    if not ok:
        raise RuntimeError("EP modes disagree on a loss or grad norm")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--model", choices=("granite", "moonlight"),
                    default="granite")
    args = ap.parse_args(argv)
    if args.model == "moonlight" and args.chips != 1:
        ap.error("--model moonlight runs on one chip")

    devs = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(f"devices: {len(devs)} x {devs[0].device_kind}", flush=True)
    phase = (four_chips if args.chips == 4
             else moonlight if args.model == "moonlight" else one_chip)
    phase(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
