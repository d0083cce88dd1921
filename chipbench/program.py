"""The system under test: the trainer's step, built as ``train.main`` does.

Maps a configuration file onto the program's ``ModelConfig`` (through the
module of its ``model_type``, which the cell loaded), ``EPConfig`` and
``OptConfig``, builds the sharded step with
``repro.launch.train.build_training`` and compiles it ahead of time for the
cell's batch. Refuses a configuration the program cannot run as stated.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program():
    """Put the checkout's ``src`` on the path; fails where it is missing."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chipbench: no program under test at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def opt_config(cfg: dict):
    import_program()
    from repro.optim import adamw

    o = cfg["optimizer"]
    return adamw.OptConfig(
        lr=o["lr"], betas=tuple(o["betas"]), eps=o["eps"],
        weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
        warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        min_lr_frac=o["min_lr_frac"])


class Program:
    """The compiled train step of one cell and what it runs on."""

    def __init__(self, cfg: dict, arch, batch: int, seq: int, devices):
        import_program()
        import jax

        from repro.launch import steps as St
        from repro.launch.train import build_training
        from repro.parallel.ep import EPConfig

        dep = cfg["deployment"]
        data, model = dep["mesh"]
        self.mesh = jax.make_mesh(
            (data, model), ("data", "model"), devices=devices[:data * model],
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self.cfg = arch.model_config(cfg)
        if self.cfg.moe.e_total % model:
            raise SystemExit(f"chipbench: {self.cfg.moe.e_total} experts do "
                             f"not divide over {model} chips")
        self.oc = opt_config(cfg)
        self.ep = ep = EPConfig(mode=dep["ep"]["mode"],
                                capacity_factor=dep["ep"]["capacity_factor"],
                                use_pallas=cfg["program"]["use_pallas"])
        self.fns, step, _init, self.state_shape, self.batch_shapes = \
            build_training(self.cfg, self.mesh, oc=self.oc, ep=ep,
                           mode=dep["mode"], dropless=None,
                           global_batch=batch, seq=seq)
        self.state_shardings = St.state_shardings(self.fns.rules,
                                                  self.state_shape[0])
        self.batch_shardings = self.fns.rules.batch_shardings(
            self.batch_shapes)
        with jax.set_mesh(self.mesh):
            self.step = step.lower(*self.state_shape,
                                   self.batch_shapes).compile()

    def capacity(self, tokens_per_chip: int) -> int:
        """Rows per (chip, expert) slot of the program's fixed-capacity EP
        path, by the program's own rule."""
        from repro.parallel.ep import _pair_capacity

        return _pair_capacity(tokens_per_chip, self.cfg.moe,
                              self.mesh.shape["model"],
                              self.ep.capacity_factor)

    def check_layout(self, weights_shape) -> None:
        """The benchmark's weights must match the program's tree leaf for
        leaf, in shape and type."""
        import jax

        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                            self.state_shape[0])
        got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), weights_shape)
        if want != got:
            raise SystemExit(f"chipbench: weights layout {got} differs from "
                             f"the program's {want}")

    def state_init(self, weights_fn):
        """One jitted call: the weights from the seed's key words, and the
        optimizer state the program builds from them, born sharded."""
        import jax

        from repro.optim import adamw

        def init(key_words):
            w = weights_fn(key_words)
            return w, adamw.init_opt_state(w)

        return jax.jit(init, out_shardings=self.state_shardings)
