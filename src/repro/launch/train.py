"""Production training launcher: mesh + sharded steps + data + FT loop.

On a TPU pod this is the entrypoint a scheduler (re)starts on every host;
on this CPU container it runs the same code path end-to-end on a small
forced-host mesh (that is what --force-devices does), exercising sharded
data feeding, EP execution, ZeRO-1 state, checkpoint/restart, and the
straggler watchdog.

    PYTHONPATH=src python -m repro.launch.train \
        --arch granite-moe-3b-a800m --smoke --force-devices 8 \
        --mesh 2x4 --mode ep_dp --steps 20

On one TPU chip, at a model's published widths with its depth cut to fit:

    PYTHONPATH=src python -m repro.launch.train \
        --arch granite-moe-3b-a800m --layers 4 --mesh 1x1 \
        --global-batch 2 --seq 2048 --steps 5

or with one chip's share of an eight-chip expert-parallel group:

    PYTHONPATH=src python -m repro.launch.train \
        --arch moonlight-16b-a3b --layers 6 --expert-share 8 --mesh 1x1 \
        --global-batch 2 --seq 4096 --steps 5
"""

from __future__ import annotations

import argparse
import os
import time


def build_training(cfg, mesh, *, oc, ep, mode, dropless, global_batch, seq):
    """Returns ``(fns, step, init, state_shape, batch_shapes)``.

    ``step`` is the sharded jitted train step. ``init()`` is jitted with the
    state's shardings as ``out_shardings``, so the parameters and optimizer
    state are born sharded: no device ever holds more of them than its
    sharding gives it.
    """
    import jax
    import jax.numpy as jnp

    from repro.launch import steps as St
    from repro.models import model as M
    from repro.optim import adamw

    fns = St.make_steps(cfg, mesh, opt=oc, ep=ep, mode=mode,
                        dropless=dropless)

    def init_state():
        params = adamw.cast_params(
            M.init_params(cfg, jax.random.PRNGKey(0)), cfg.compute_dtype)
        return params, adamw.init_opt_state(params)

    state_shape = jax.eval_shape(init_state)
    batch_shapes = {k: jax.ShapeDtypeStruct((global_batch, seq), jnp.int32)
                    for k in ("tokens", "labels")}
    step = St.jit_train_step(fns, state_shape[0], batch_shapes)
    init = jax.jit(init_state, out_shardings=St.state_shardings(
        fns.rules, state_shape[0]))
    return fns, step, init, state_shape, batch_shapes


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-3b-a800m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model's depth to N layers (0 = the "
                         "config's own); widths are never changed")
    ap.add_argument("--expert-share", type=int, default=1, metavar="N",
                    help="hold chip 0's share of an N-chip expert-parallel "
                         "group: the first 1/N of each MoE layer's experts "
                         "(the router still routes over all) and of the "
                         "vocabulary")
    ap.add_argument("--mesh", default="2x4",
                    help="dataxmodel (or podxdataxmodel)")
    ap.add_argument("--mode", default="tp_sp",
                    choices=["tp_sp", "zero1", "ep_dp"])
    ap.add_argument("--ep-mode", default="hyperparallel",
                    choices=["hyperparallel", "baseline"])
    ap.add_argument("--dropless", action="store_true",
                    help="compile/reuse schedules from each batch's actual "
                         "router output (capacity=None) instead of running "
                         "the fixed-capacity path")
    ap.add_argument("--dropless-ep", type=int, default=0,
                    help="EP group size of the compiled dropless fragment "
                         "(0 = the mesh's model-axis size)")
    ap.add_argument("--dropless-bucket", default="16", metavar="SPEC",
                    help="shape-bucket policy for plan row counts: a "
                         "linear bucket size int ('16'; '1' = exact plans, "
                         "recompile on every routing change), "
                         "'geometric:B[xG]' (power-of-G rungs from base "
                         "B), or 'ladder:E1,E2,...' (explicit rungs, e.g. "
                         "fitted by repro.launch.replay); see "
                         "repro.core.buckets.BucketSpec")
    ap.add_argument("--sched", default=None, metavar="PIPELINE",
                    help="schedule-pass pipeline for the dropless path: "
                         "'auto' (cost-model-guided selection per batch "
                         "plan), a named core.passes.SCHED_PIPELINES entry "
                         "(e.g. 'ratr+crit'), or a comma-separated pass "
                         "list; default keeps the DroplessConfig default")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--force-devices", type=int, default=0,
                    help="force N host devices (CPU testing only)")
    args = ap.parse_args(argv)

    if args.force_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.force_devices}")

    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.core.passes import pipeline_arg as resolve_sched_arg
    from repro.data.pipeline import DataConfig, SyntheticStream
    from repro.ft.runner import FTConfig, train_loop
    from repro.launch.compile_cache import enable_compile_cache
    from repro.optim import adamw
    from repro.parallel.ep import EPConfig

    enable_compile_cache()
    dims = [int(x) for x in args.mesh.split("x")]
    names = (("pod", "data", "model") if len(dims) == 3
             else ("data", "model"))
    mesh = jax.make_mesh(tuple(dims), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(dims))

    overrides = {"n_layers": args.layers} if args.layers else {}
    cfg = (get_smoke_config if args.smoke else get_config)(
        args.arch, **overrides)
    if args.expert_share > 1:
        import dataclasses
        n = args.expert_share
        if cfg.family != "moe" or cfg.moe.e_total % n or cfg.vocab % n:
            ap.error(f"--expert-share {n} does not divide {args.arch}'s "
                     "experts and vocabulary")
        cfg = dataclasses.replace(
            cfg, vocab=cfg.vocab // n,
            moe=dataclasses.replace(cfg.moe, n_held=cfg.moe.e_total // n))
    if cfg.family == "moe":
        # Pad experts so E % model-axis == 0 (router never selects padding).
        import dataclasses
        model_n = mesh.shape.get("model", 1)
        e_tot = cfg.moe.e_total
        extra = (-e_tot) % model_n
        if extra:
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe,
                n_padding_experts=cfg.moe.n_padding_experts + extra))
    oc = adamw.OptConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                         total_steps=args.steps)
    ep = (EPConfig(mode=args.ep_mode, capacity_factor=4.0)
          if cfg.family == "moe" else None)
    sched_pipeline = None
    if args.sched is not None:
        # Validate eagerly: an unknown pass name must fail fast, and a
        # --sched that cannot take effect must say so instead of silently
        # training with defaults.
        try:
            sched_pipeline = resolve_sched_arg(args.sched)
        except KeyError as e:
            ap.error(str(e))
        if not args.dropless:
            ap.error("--sched only applies to the dropless scheduling path; "
                     "add --dropless")
        if cfg.family != "moe":
            ap.error(f"--sched requires a MoE arch (got {args.arch!r}: "
                     f"family={cfg.family!r})")
    dropless = None
    if args.dropless and cfg.family == "moe":
        from repro.core.buckets import BucketSpec
        from repro.launch.dropless import DroplessConfig
        try:
            bucket = BucketSpec.parse(args.dropless_bucket)
        except ValueError as e:
            ap.error(str(e))
        kw = {}
        if sched_pipeline is not None:
            kw["pipeline"] = sched_pipeline
        dropless = DroplessConfig(
            ep=args.dropless_ep or mesh.shape.get("model", 1),
            bucket=bucket, **kw)
        print(f"dropless shape buckets: {bucket}")
        if sched_pipeline is not None:
            print(f"dropless schedule pipeline: {dropless.pipeline!r}")
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.param_count() / 1e9:.3f}B params; mesh "
          f"{args.mesh} ({args.mode}), batch {args.global_batch}x{args.seq}")
    fns, step, init, state_shape, batch_shapes = build_training(
        cfg, mesh, oc=oc, ep=ep, mode=args.mode, dropless=dropless,
        global_batch=args.global_batch, seq=args.seq)
    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        step = step.lower(*state_shape, batch_shapes).compile()
        print(f"train step compiled in {time.perf_counter() - t0:.1f}s")
        params, opt_state = init()

        stream = SyntheticStream(DataConfig(
            vocab=cfg.vocab, seq_len=args.seq,
            global_batch=args.global_batch))

        class _Stream:
            def sharded_batch(self, s, mesh_, sharding):
                return stream.sharded_batch(
                    s, mesh, fns.rules.batch_shardings(batch_shapes))

        run = train_loop(
            step_fn=step, params=params, opt_state=opt_state,
            stream=_Stream(), mesh=mesh, batch_sharding=None,
            n_steps=args.steps,
            ft=FTConfig(ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every),
            log_every=1)

    if run.resumed_from is not None:
        print(f"resumed from step {run.resumed_from}")
    for m in run.metrics_log:
        print(f"step {m['step']:4d} loss {m['loss']:.6f} "
              f"gnorm {m['grad_norm']:.6f} {m['step_time_s'] * 1e3:.3f}ms")
    if run.stragglers:
        print("stragglers:", run.stragglers)
    if fns.dropless is not None:
        info = fns.dropless.cache.info()
        total = max(1, info["hits"] + info["misses"])
        print(f"dropless SSC cache: {info['entries']} entries "
              f"({info['bytes'] / 1024:.0f} KiB), "
              f"hit rate {info['hits'] / total:.1%} "
              f"({info['misses']} compiles, {info['evictions']} evictions)")
    return run


if __name__ == "__main__":
    main()
