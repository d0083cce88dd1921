"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole run on the CPU (the look for a chip replaced by
the CPU's devices) on a tiny cell, with one fault planted in the program
before the run builds its step: a step that returns its state unchanged,
half of the batch left out (the loss the mean over the rest), the step's
answer altered where it is produced (one leaf's update applied twice),
and, over four devices, the exchange between chips left out. A sound
traced run over four devices carries the layer readings, and a reader of
the exchange's scope added as a new file.
"""

from __future__ import annotations

import os

import pytest

from chipbench.tests import checkout

UNCHANGED = """
import repro.optim.adamw as A
def apply_updates(params, grads, state, oc, grad_transform=None):
    return params, state, {"grad_norm": A.global_norm(grads),
                           "lr": A.schedule(oc, state["step"])}
A.apply_updates = apply_updates
"""

HALF_BATCH = """
import repro.models.model as M
_loss = M.loss_fn
def loss_fn(cfg, params, batch, *a, **k):
    half = {n: v[: v.shape[0] // 2] for n, v in batch.items()}
    return _loss(cfg, params, half, *a, **k)
M.loss_fn = loss_fn
"""

LEAF_DOUBLED = """
import repro.optim.adamw as A
_apply = A.apply_updates
def apply_updates(params, grads, state, oc, grad_transform=None):
    new_p, new_s, m = _apply(params, grads, state, oc, grad_transform)
    old, new = state["master"]["embed"], new_s["master"]["embed"]
    master = dict(new_s["master"], embed=new + (new - old))
    new_p = dict(new_p, embed=master["embed"].astype(new_p["embed"].dtype))
    return new_p, dict(new_s, master=master), m
A.apply_updates = apply_updates
"""

NO_EXCHANGE = """
import jax, jax.numpy as jnp
jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
"""

EXCHANGE_MS = """
def read(run):
    if run.layer_ms is None:
        return None
    return run.layer_ms.get("moe/exchange")
"""

ARGS = ["--workload", checkout.CELL, "--seed", str(2 ** 32 + 11),
        "--seconds", "1", "--trace", "0"]


def _run(tmp_path, plant, chips=1, trace=0, add=None):
    root = checkout.make(str(tmp_path), chips=chips)
    if add:
        add(root)
    p = checkout.run(root, ARGS[:-1] + [str(trace)], plant=plant,
                     devices=chips)
    assert p.returncode == 0, p.stderr[-3000:]
    return checkout.last_line(p.stdout)


@pytest.mark.parametrize("plant", [UNCHANGED, HALF_BATCH, LEAF_DOUBLED],
                         ids=["state_unchanged", "half_batch",
                              "leaf_update_doubled"])
def test_fault_is_not_correct(tmp_path, plant):
    line = _run(tmp_path, plant)
    assert line["correct"] is False, line["checks"]


def test_exchange_left_out_is_not_correct_over_four_devices(tmp_path):
    sound = _run(tmp_path / "sound", "", chips=4)
    assert sound["correct"] is True, sound["checks"]
    line = _run(tmp_path / "fault", NO_EXCHANGE, chips=4)
    assert line["correct"] is False, line["checks"]


def _exchange_reader(root):
    """``moe_exchange_ms``, device ms a step under ``moe/exchange``, as a
    reader file and a ``per_layer`` entry of the checkout's cell."""
    with open(os.path.join(root, "chipbench", "metrics",
                           "moe_exchange_ms.py"), "w") as f:
        f.write(EXCHANGE_MS)
    b = checkout._bench(root)
    b["per_layer"].append({"name": "moe_exchange_ms", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "MoE EP path",
                           "moves": "train_tokens_per_s",
                           "workloads": [checkout.CELL]})
    checkout._dump(b, root, "BENCHMARK.json")


def test_traced_line_over_four_devices_reads_the_exchange(tmp_path):
    """A traced ``ep_dp`` run over four devices carries the nine layer
    readings and ``moe_exchange_ms`` > 0 (the CPU's XLA threads read as
    the devices' ops lines, ``checkout.CPU_OPS``)."""
    line = _run(tmp_path, checkout.CPU_OPS, chips=4, trace=1,
                add=_exchange_reader)
    assert line["correct"] is True, line["checks"]
    want = {"moe_dispatch_ms", "moe_combine_ms", "expert_slot_fill",
            "moe_expert_ffn_ms", "expert_ffn_roofline", "attention_ms",
            "optimizer_ms", "idle_input_ms", "idle_loop_ms",
            "moe_exchange_ms"}
    assert want <= set(line["metrics"])
    assert line["metrics"]["moe_exchange_ms"]["value"] > 0
    assert line["layer_ms"]["moe/exchange"] > 0
    assert line["counters"] and all(
        c["moe_slots"] > 0 for c in line["counters"])
    assert line["routing"]["dropped_choices"] >= 0
