"""The program's own measurement points, on the CPU.

* **named scopes** — the compiled train step's HLO ``op_name`` metadata
  names every layer scope, on forward ops and on transposed (backward)
  ones, for the EP ``hyperparallel`` path on one device and on four.
* **MoE counters** — ``moe_dropped`` equals a numpy recount of the capacity
  drops from the router's ``top_i`` and ``C``, where capacity drops rows and
  where it drops none, on the EP path and on ``moe_grouped``; the train
  step's ``moe_rows_issued`` covers the kept rows on one device and equals
  the capacity rows over four.
* **loop spans** — ``train_loop`` under ``jax.profiler.trace`` writes one
  ``train_step`` span per step, with its step number, and the
  ``train_loop/*`` phase spans inside it, in order; the counters ride the
  metrics log.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft.runner import FTConfig, train_loop
from repro.models.moe import MoEConfig, init_moe, moe_grouped, router_topk
from repro.parallel.ctx import moe_counts_scope
from repro.parallel.ep import EPConfig, _pair_capacity, make_moe_ep

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCOPES = ("embed", "attention", "moe/router", "moe/dispatch",
          "moe/exchange", "moe/expert_ffn", "moe/combine", "unembed_ce",
          "optimizer")
MC = MoEConfig(n_experts=8, top_k=2, d_expert=32)

# Compiles the train step of a tiny MoE model (d 64, 8 experts, top-2) with
# the EP hyperparallel path over a 1 x n mesh, runs it once, and prints the
# op_names of its HLO and its MoE counters.
_STEP = r"""
import json, os, sys
n = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, re
from repro.launch.train import build_training
from repro.models.model import ModelConfig
from repro.models.moe import MoEConfig
from repro.optim.adamw import OptConfig
from repro.parallel.ep import EPConfig
cfg = ModelConfig(name="tiny", family="moe", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=32, vocab=500, vocab_pad=16,
                  tie_embeddings=True,
                  moe=MoEConfig(n_experts=8, top_k=2, d_expert=32))
mesh = jax.make_mesh((1, n), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
fns, step, init, state, batch = build_training(
    cfg, mesh, oc=OptConfig(), ep=EPConfig("hyperparallel",
                                           capacity_factor=1.0),
    mode="tp_sp" if n == 1 else "ep_dp", dropless=None, global_batch=2 * n,
    seq=64)
with jax.set_mesh(mesh):
    compiled = step.lower(*state, batch).compile()
    params, opt = init()
    b = jax.device_put(
        {k: (jnp.arange(2 * n * 64).reshape(2 * n, 64) * 7) % 500
         for k in ("tokens", "labels")}, fns.rules.batch_shardings(batch))
    _, _, m = compiled(params, opt, b)
sys.path.insert(0, ".")
from chipbench.layers import int_counters
print(json.dumps({
    "op_names": sorted(set(re.findall(r'op_name="([^"]*)"',
                                      compiled.as_text()))),
    "counters": {k: [int(m[k]), str(m[k].dtype)] for k in
                 ("moe_routed", "moe_dropped", "moe_slots",
                  "moe_rows_issued")},
    "int_counters": sorted(int_counters(jax.device_get(m)))}))
"""


def _has_scope(op_name: str, scope: str) -> bool:
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     op_name) is not None


@pytest.mark.parametrize("devices", [1, 4], ids=["one_device",
                                                 "four_devices"])
def test_train_step_hlo_names_every_scope(devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _STEP, str(devices)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    names = out["op_names"]
    # The ring over one device moves nothing, so it has no exchange ops.
    want = [s for s in SCOPES if devices > 1 or s != "moe/exchange"]
    for scope in want:
        fwd = [n for n in names if _has_scope(n, scope)
               and "transpose(" not in n]
        bwd = [n for n in names if _has_scope(n, scope)
               and "transpose(" in n]
        assert fwd, scope
        # The optimizer runs after the gradient, so it is never transposed.
        assert bwd or scope == "optimizer", scope
    assert not any(_has_scope(n, "moe/exchange") for n in names) \
        or devices > 1
    # Counters summed over 2 layers and every device: 2 * n rows of 64
    # tokens, top-2; C = 32 a (device, expert) pair at capacity factor 1.
    c = out["counters"]
    assert c["moe_routed"] == [2 * 2 * devices * 64 * 2, "int32"]
    assert c["moe_slots"] == [2 * devices * 8 * 32, "int32"]
    assert 0 <= c["moe_dropped"][0] <= c["moe_routed"][0]
    # One device computes the kept rows in whole row tiles; four exchange
    # fixed-size chunks and compute every capacity row.
    issued, dtype = c["moe_rows_issued"]
    assert dtype == "int32"
    if devices == 1:
        assert issued >= c["moe_routed"][0] - c["moe_dropped"][0]
    else:
        assert issued == c["moe_slots"][0]
    # What the benchmark's result line carries as the step's counters.
    assert {"moe_routed", "moe_dropped", "moe_slots",
            "moe_rows_issued"} <= set(out["int_counters"])


def _recount(top_i: np.ndarray, C: int) -> int:
    per_expert = np.bincount(top_i.reshape(-1), minlength=MC.e_total)
    return int(np.maximum(per_expert - C, 0).sum())


@pytest.mark.parametrize("path", ["ep", "grouped"])
@pytest.mark.parametrize("capacity_factor", [0.5, 8.0],
                         ids=["drops", "drops_none"])
def test_dropped_count_matches_numpy_recount(path, capacity_factor):
    params = init_moe(jax.random.PRNGKey(0), 64, MC)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64), jnp.float32)
    T = x.shape[0] * x.shape[1]
    if path == "ep":
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        impl = make_moe_ep(mesh, EPConfig("hyperparallel",
                                          capacity_factor=capacity_factor))
        C = _pair_capacity(T, MC, 1, capacity_factor)
    else:
        C = max(1, int(np.ceil(T * MC.top_k / MC.e_total
                               * capacity_factor)))

        def impl(p, x, mc):
            return moe_grouped(p, x, mc, cap=C)

    @jax.jit
    def run(p, x):
        with moe_counts_scope() as got:
            impl(p, x, MC)
        (counts,) = got
        return {k: jnp.sum(v) for k, v in counts.items()}

    got = jax.device_get(run(params, x))
    _, top_i = router_topk(params["router"], x.reshape(T, -1), MC)
    want = _recount(np.asarray(top_i), C)
    assert (want > 0) == (capacity_factor < 1), want
    assert int(got["moe_dropped"]) == want
    assert int(got["moe_routed"]) == T * MC.top_k
    assert int(got["moe_slots"]) == MC.e_total * C


class _Stream:
    def sharded_batch(self, step, mesh, sharding):
        return jnp.float32(step + 1)


def _step(params, opt_state, batch):
    w = params["w"] - 0.01 * batch
    return ({"w": w}, opt_state,
            {"loss": jnp.sum(w * w), "grad_norm": jnp.float32(0.1),
             "moe_routed": jnp.int32(16), "moe_dropped": jnp.int32(3),
             "moe_slots": jnp.int32(64)})


def test_train_loop_writes_step_and_phase_spans(tmp_path):
    trace_dir = str(tmp_path / "trace")
    with jax.profiler.trace(trace_dir):
        run = train_loop(step_fn=_step, params={"w": jnp.float32(1.0)},
                         opt_state=None, stream=_Stream(), mesh=None,
                         batch_sharding=None, n_steps=2,
                         ft=FTConfig(ckpt_dir=str(tmp_path / "ckpt"),
                                     ckpt_every=2),
                         inject_fault=lambda step: None, log_every=1)
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    spans = sorted(
        ((e.start_ns, e.end_ns, e.name, dict(e.stats).get("step_num"))
         for plane in jax.profiler.ProfileData.from_file(path).planes
         if plane.name.startswith("/host:") for line in plane.lines
         for e in line.events
         if e.name == "train_step" or e.name.startswith("train_loop/")),
        key=lambda s: (s[0], -s[1]))
    phases = ["train_loop/hook", "train_loop/batch", "train_loop/dispatch",
              "train_loop/wait", "train_loop/log"]
    assert [s[2] for s in spans] == (["train_step"] + phases
                                     + ["train_step"] + phases
                                     + ["train_loop/ckpt"])
    steps = [s for s in spans if s[2] == "train_step"]
    assert [s[3] for s in steps] == [0, 1]
    for s0, e0, name, _ in spans:
        if name != "train_step":
            assert any(ss <= s0 and e0 <= se for ss, se, _, _ in steps)
    assert [{k: m[k] for k in ("moe_routed", "moe_dropped", "moe_slots")}
            for m in run.metrics_log] == [
        {"moe_routed": 16, "moe_dropped": 3, "moe_slots": 64}] * 2


def test_traced_line_carries_rows_issued_on_cpu(tmp_path):
    """A traced benchmark run on the CPU (the benchmark's tiny one-chip
    cell) carries ``moe_rows_issued`` in its checked steps' counters: at
    least the kept rows of the step's two MoE layers."""
    if os.path.abspath(ROOT) not in map(os.path.abspath, sys.path):
        sys.path.insert(0, os.path.abspath(ROOT))
    from chipbench.tests import checkout

    root = checkout.make(str(tmp_path))
    p = checkout.run(root, ["--workload", checkout.CELL, "--seed",
                            str(2 ** 41 + 5), "--seconds", "1",
                            "--trace", "1"], plant=checkout.CPU_OPS)
    assert p.returncode == 0, p.stderr[-3000:]
    line = checkout.last_line(p.stdout)
    assert line["correct"] is True, line["checks"]
    assert [c["step"] for c in line["counters"]] == [0, 1, 2]
    for c in line["counters"]:
        assert c["moe_rows_issued"] >= c["moe_routed"] - c["moe_dropped"]
