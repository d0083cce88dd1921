"""The benchmark's own pieces on the CPU: the FLOP count, the trace
reducer, discovery by name, refusal without a TPU, the result line."""

from __future__ import annotations

import glob
import gzip
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import flops, harness, tracereduce
from chipbench.tests import checkout

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name="granite-moe-3b-a800m-4l"):
    with open(os.path.join(checkout.ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_model_flops_by_hand():
    # granite at its published widths, 4 layers, sequence 2048, per token:
    # attention projections 2*1536*(1536+512+512) + 2*1536*1536 = 12582912
    # scores, causal half: 2 * 2 * 1024.5 * 24 * 64 = 6294528
    # router 2*1536*40 = 122880; experts 8*(2*1536*1024 + 2*512*1536)
    # = 37748736; unembedding 2*1536*49155 = 151004160.
    per_layer = 12582912 + 6294528 + 122880 + 37748736
    fwd = 4 * per_layer + 151004160
    cfg = _config()
    assert flops.forward_per_token(cfg, 2048) == fwd
    assert flops.train_per_token(cfg, 2048) == 3 * fwd
    assert abs(3 * fwd / 1e9 - 1.134) < 1e-3


def _trace():
    # Two chips. Chip 0 runs a loop whose body computes twice, then
    # computes and idles. Host spans: the traced window; the loop's: two
    # step dispatches, a wait.
    return {
        "devices": {
            0: [("%while.9 = (bf16[8]) while(...)", 100, 500),
                ("%fusion.1 = bf16[8]{0} fusion(...)", 100, 300),
                ("%fusion.3", 310, 400), ("%all-reduce.4", 450, 500),
                ("%fusion.5", 700, 900)],
            1: [("%fusion.1 = bf16[8]{0} fusion(...)", 100, 600)],
        },
        "host": [("traced_window", 0, 1000)],
        "program": [("train_step", 40, 1000, 3),
                    ("train_loop/dispatch", 50, 90, None),
                    ("train_loop/dispatch", 600, 620, None),
                    ("train_loop/wait", 500, 700, None)],
    }


def test_trace_reducer_by_hand():
    t = _trace()
    idle = tracereduce.idle_share(t)
    # chip 0 busy [100, 500] and [700, 900]: 600 of 1000.
    assert idle[0] == pytest.approx(0.4)
    assert idle[1] == pytest.approx(0.5)
    b = tracereduce.breakdown(t)
    # self times: the loop op keeps what its body's ops leave (400 - 200
    # - 90 - 50 = 60)
    ops = dict(b["device_ops"])
    assert ops["%fusion.1 = bf16[8]"] == pytest.approx((200 + 500) / 2e9)
    assert ops["%while.9 = (bf16[8])"] == pytest.approx(60 / 2e9)
    assert b["device_ops"][0][0] == "%fusion.1 = bf16[8]"
    # chip 1 idles most: [0, 100] under a dispatch, [600, 1000] partly
    # under the wait.
    gaps = dict(b["idle_gaps"])
    assert gaps["train_loop/dispatch"] == pytest.approx(100 / 1e9)
    assert gaps["train_loop/wait"] == pytest.approx(400 / 1e9)
    assert "train_step" not in gaps


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    HERE, "fixtures", "trace_*.json.gz"))))
def test_trace_reducer_on_recorded_chip_trace(path):
    """Steps of a trace recorded on the chip (op names shortened): the
    reduction gives what it gave when the fixture was made, and stays
    inside its bounds."""
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    ev = rec["events"]
    t = {"devices": {int(k): [tuple(e) for e in v]
                     for k, v in ev["devices"].items()},
         "host": [tuple(e) for e in ev["host"]]}
    want = rec["expect"]
    idle = tracereduce.idle_share(t)
    assert len(idle) == want["devices"]
    assert max(idle.values()) == pytest.approx(want["idle_max"], rel=1e-9)
    assert 0 < max(idle.values()) < 0.5
    b = tracereduce.breakdown(t)
    assert len(b["device_ops"]) == 10 and b["idle_gaps"]
    lo, hi = tracereduce.window(t)
    busy = tracereduce.busy_ns(t["devices"][0], lo, hi)
    # self times of the top ops never exceed the busy time
    assert 0 < sum(v for _, v in b["device_ops"]) * 1e9 <= busy * (1 + 1e-9)


def test_missing_names_are_refused(tmp_path):
    root = checkout.make(str(tmp_path))
    with pytest.raises(SystemExit, match="no workload"):
        harness.Cell("no-such-cell", root)
    with pytest.raises(SystemExit, match="no peaks"):
        harness.peaks("TPU v99")
    with pytest.raises(SystemExit, match="has no file"):
        harness.reader(os.path.join(root, "chipbench", "metrics"), "nope")
    # A configuration whose model_type has no module is refused as its
    # cell loads, naming the file it looked for.
    path = os.path.join(root, "chipbench", "configs", "tiny.json")
    with open(path) as f:
        c = json.load(f)
    for model_type, want in (("nope", "arch/nope.py"),
                             ("../configs/tiny", "no model_type")):
        checkout._dump(dict(c, model_type=model_type), path)
        with pytest.raises(harness.SpecError, match=want):
            harness.Cell(checkout.CELL, root)


def test_granite_model_config_is_pinned():
    """The granite module maps the granite file onto the program's
    ModelConfig field for field as the benchmark has run it."""
    from chipbench import program
    from chipbench.arch import granitemoe

    program.import_program()
    from repro.models.model import ModelConfig
    from repro.models.moe import MoEConfig

    assert granitemoe.model_config(_config()) == ModelConfig(
        name="granite-moe-3b-a800m-4l", family="moe", n_layers=4,
        d_model=1536, n_heads=24, n_kv_heads=8, head_dim=64, d_ff=512,
        vocab=49155, act="swiglu", norm="rmsnorm", rope_theta=10000.0,
        tie_embeddings=True,
        moe=MoEConfig(n_experts=40, top_k=8, d_expert=512,
                      n_padding_experts=0),
        vocab_pad=256, dtype="bfloat16", remat=True, remat_policy="full",
        attn_block=1024)


def _hashes(root):
    out = {}
    for d, _, files in os.walk(root):
        for name in files:
            path = os.path.join(d, name)
            if "__pycache__" in path or ".jax_cache" in path \
                    or ".chipbench" in path:
                continue
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


# A second architecture, as a new module: granite's mapping onto the
# program, with the whole MoE layer read as one scope.
TWIN_ARCH = """
import os
from chipbench import harness
_g = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "granitemoe.py"), "granitemoe")
SCOPES = ("attention", "moe")
model_config = _g.model_config
forward_flops_per_token = _g.forward_flops_per_token
expert_ffn_least_s = _g.expert_ffn_least_s
"""

MOE_LAYER_MS = """
def read(run):
    if run.layer_ms is None:
        return None
    return run.layer_ms.get("moe")
"""


def test_new_architecture_is_found_by_name(tmp_path):
    """A model_type module, its configuration, a reference copy, a cell and
    a reader of one of the module's scopes, added as new files and entries
    in BENCHMARK.json, run with no other file changed, and the traced line
    carries the reader's metric."""
    root = checkout.make(str(tmp_path))
    here = os.path.join(root, "chipbench")
    before = _hashes(root)
    with open(os.path.join(here, "configs", "tiny.json")) as f:
        c = json.load(f)
    c.update(name="tiny-twin", model_type="twinmoe", reference="twin_ref")
    checkout._dump(c, here, "configs", "tiny-twin.json")
    with open(os.path.join(here, "arch", "twinmoe.py"), "w") as f:
        f.write(TWIN_ARCH)
    shutil.copy(os.path.join(here, "reference", "granite_moe.py"),
                os.path.join(here, "reference", "twin_ref.py"))
    shutil.copy(os.path.join(here, "workloads", checkout.CELL + ".json"),
                os.path.join(here, "workloads", "tiny-twin.s64.json"))
    with open(os.path.join(here, "metrics", "moe_layer_ms.py"), "w") as f:
        f.write(MOE_LAYER_MS)
    b = checkout._bench(root)
    b["configs"].append({"name": "tiny-twin", "source": "test",
                         "file": "chipbench/configs/tiny-twin.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-twin.s64", "config": "tiny-twin",
                           "traffic": "s64", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "moe_layer_ms", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "test", "moves": "train_tokens_per_s",
                           "workloads": ["tiny-twin.s64"]})
    checkout._dump(b, root, "BENCHMARK.json")
    p = checkout.run(root, ["--workload", "tiny-twin.s64", "--seed",
                            str(2 ** 40 + 9), "--seconds", "1", "--trace",
                            "1"], plant=checkout.CPU_OPS)
    assert p.returncode == 0, p.stderr[-3000:]
    line = checkout.last_line(p.stdout)
    assert line["correct"] is True, line["checks"]
    assert set(line["layer_ms"]) == {"attention", "moe", "other",
                                     "busy_total"}
    assert line["metrics"]["moe_layer_ms"]["value"] == \
        line["layer_ms"]["moe"] > 0
    assert {"mfu", "compiles_in_window"} <= set(line["metrics"])
    after = _hashes(root)
    # BENCHMARK.json gains the new entries; no other file changed.
    changed = {k for k in before if before[k] != after.get(k)}
    assert changed == {"BENCHMARK.json"}


def test_refuses_without_tpu(tmp_path):
    """On the CPU the benchmark exits non-zero and prints no result; so it
    does in a directory that holds only BENCHMARK.json and chipbench/."""
    for src in (True, False):
        root = checkout.make(str(tmp_path / str(src)), src=src)
        p = subprocess.run(
            [sys.executable, "chipbench/run.py", "--workload",
             "granite-4l.s2048", "--seed", str(2 ** 33 + 5), "--seconds",
             "1", "--trace", "0"], cwd=root, text=True,
            capture_output=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode != 0
        assert p.stdout.strip() == ""
        assert "chipbench:" in p.stderr


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric added
    as files (and entries in BENCHMARK.json) run with no other file
    edited; the result line has the contract's keys, checks last."""
    root = checkout.make(str(tmp_path))
    here = os.path.join(root, "chipbench")
    cfg_path = os.path.join(here, "configs", "tiny.json")
    with open(cfg_path) as f:
        c = json.load(f)
    c["name"] = "tiny-wide"
    c["num_local_experts"] = 14
    with open(os.path.join(here, "configs", "tiny-wide.json"), "w") as f:
        json.dump(c, f)
    with open(os.path.join(here, "traffic", "s64.json")) as f:
        t = json.load(f)
    t["seq_len"], t["rows_per_chip"] = 32, 3
    with open(os.path.join(here, "traffic", "s32b3.json"), "w") as f:
        json.dump(t, f)
    shutil.copy(os.path.join(here, "workloads", checkout.CELL + ".json"),
                os.path.join(here, "workloads", "tiny-wide.s32b3.json"))
    with open(os.path.join(here, "metrics", "steps_in_window.py"),
              "w") as f:
        f.write("def read(run):\n    return run.window_steps\n")
    b = checkout._bench(root)
    b["configs"].append({"name": "tiny-wide", "source": "test",
                         "file": "chipbench/configs/tiny-wide.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-wide.s32b3", "config": "tiny-wide",
                           "traffic": "s32b3", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "steps_in_window", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "test", "moves": "train_tokens_per_s",
                           "workloads": ["tiny-wide.s32b3"]})
    checkout._dump(b, root, "BENCHMARK.json")
    for trace, names in ((0, {"train_tokens_per_s", "step_ms_p90",
                              "setup_s"}),
                         (1, {"mfu", "compiles_in_window",
                              "steps_in_window"})):
        p = checkout.run(root, ["--workload", "tiny-wide.s32b3", "--seed",
                                str(2 ** 40 + 7), "--seconds", "1",
                                "--trace", str(trace)])
        assert p.returncode == 0, p.stderr[-3000:]
        line = checkout.last_line(p.stdout)
        keys = list(line)
        assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                            "device"]
        assert keys[-1] == "checks"
        assert set(line["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
        assert all(set(v) == {"value", "limit"}
                   for v in line["checks"].values())
        assert set(line["device"]) >= {"platform", "kind", "count",
                                       "memory_peak_bytes"}
        assert set(line["metrics"]) == names
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] > 0
        # the checks are the last lines of standard error
        tail = p.stderr.strip().splitlines()[-3:]
        assert [ln.split()[1] for ln in tail] == ["loss_gap", "grad_gap",
                                                  "change_gap"]
    assert line["metrics"]["steps_in_window"]["value"] > 0
