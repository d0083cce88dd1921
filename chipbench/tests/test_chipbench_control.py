"""The control comes out not correct: the reference computed in float8,
put in the program's place, against the float32 reference, judged by each
cell's own limits. On the chip the control runs at the cells' own sizes
(``chipbench/calibrate.py --control-seeds``); here at the cells' widths
and depth with 16 experts, a vocabulary of 8192 and 2 rows of 256 tokens,
which the CPU runs in seconds a seed."""

from __future__ import annotations

import jax
import pytest

from chipbench import compare, harness, traffic
from chipbench.train_cell import reference_module

CELLS = [harness.Cell(w["name"]) for w in harness.benchmark()["workloads"]]


@pytest.fixture(scope="module")
def refs():
    cfg = dict(CELLS[0].config, num_local_experts=16, vocab_size=8192)
    cfg["deployment"] = dict(cfg["deployment"], padding_experts=0)
    mod = reference_module(cfg)
    return (cfg, mod, jax.jit(lambda k: mod.weights(cfg, k)),
            mod.Reference(cfg, 1), mod.Reference(cfg, 1, "fp8"))


@pytest.mark.parametrize("seed", [2 ** 33 + 1, 2 ** 33 + 2, 2 ** 33 + 3])
def test_control_is_not_correct(refs, seed):
    cfg, mod, make, sound_ref, control_ref = refs
    tr = dict(CELLS[0].traffic, seq_len=256)
    key = mod.seed_key(seed)
    batches = [traffic.batch_np(tr, cfg["vocab_size"], 1, seed, t)
               for t in range(3)]
    sound = sound_ref.run(lambda: make(key), batches)
    control = control_ref.run(lambda: make(key), batches)
    read = compare.readings(control, sound)
    for cell in CELLS:
        for k in ("hidden_size", "num_hidden_layers", "intermediate_size"):
            assert cell.config[k] == cfg[k]
        correct, checks = compare.judge(read, cell.limits)
        assert not correct, (cell.name, checks)
