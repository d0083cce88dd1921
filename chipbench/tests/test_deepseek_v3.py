"""The DeepSeek-V3 architecture (Moonlight-16B-A3B's) on the CPU at a small
size: the program against the plain reference, the expert share, the
router's bias, the counters, the architecture module's counts and
refusals, and the cell through the benchmark's entry."""

from __future__ import annotations

import copy
import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import compare, harness, program
from chipbench.tests import checkout

program.import_program()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH = harness.load_module(
    os.path.join(checkout.ROOT, "chipbench", "arch", "deepseek_v3.py"),
    "deepseek_v3")
REF = harness.load_module(
    os.path.join(checkout.ROOT, "chipbench", "reference", "deepseek_v3.py"),
    "deepseek_v3 reference")
CONFIG = "moonlight-16b-a3b-6l-e8"
SEED = 2 ** 41 + 3


def _config():
    with open(os.path.join(checkout.ROOT, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def tiny(held=(0, 4), dtype="float32"):
    """1 dense + 2 MoE layers, d 64, 16 experts of which ``held`` (first,
    count) are held here, top-6, 2 shared, vocabulary 256."""
    c = _config()
    c.update(name="tiny-ds", hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             moe_intermediate_size=32, num_hidden_layers=3,
             n_routed_experts=held[1], vocab_size=256, dtype=dtype)
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["deployment"] = dict(c["deployment"], held_experts=list(held))
    c["program"] = dict(c["program"], vocab_pad=16, attn_block=16)
    return c


def _batches(cfg, B, S, steps):
    rng = np.random.default_rng(7)
    out = []
    for _ in range(steps):
        t = rng.integers(1, cfg["vocab_size"], (B, S + 1))
        out.append({"tokens": t[:, :-1].astype(np.int32),
                    "labels": t[:, 1:].astype(np.int32)})
    return out


def _program(cfg, B, S):
    """The benchmark's program for ``cfg`` with capacity for every choice
    (nothing drops)."""
    cfg = copy.deepcopy(cfg)
    cfg["deployment"]["ep"]["capacity_factor"] = (
        cfg["published"]["n_routed_experts"] / cfg["num_experts_per_tok"])
    return program.Program(cfg, ARCH, B, S, jax.devices()[:1])


def _bias_and_counters_of_one_step(cfg, batch, opt, m, load, bias_grad):
    """The bias takes no gradient, and each expert's moves by -gamma, 0 or
    +gamma, against its load; its slot of Adam's first moment holds its
    excess load; every choice is routed here or counted as held
    elsewhere."""
    assert not np.asarray(bias_grad).any()
    gamma = cfg["assumed"]["bias_update_speed"]
    bias = np.asarray(opt["master"]["blocks"]["moe"]["router_bias"])
    assert opt["master"]["blocks"]["moe"]["router_bias"].dtype == jnp.float32
    assert set(np.unique(bias)) <= {np.float32(v) for v in (-gamma, 0.0,
                                                            gamma)}
    # Against the load of a forward of its own (the step's compiled forward
    # may round a near-tie the other way).
    excess = load - load.mean(-1, keepdims=True)
    far = np.abs(excess) >= 2
    assert far.any()
    np.testing.assert_array_equal(np.sign(bias)[far], -np.sign(excess)[far])
    b1 = cfg["optimizer"]["betas"][0]
    moment = np.asarray(opt["m"]["blocks"]["moe"]["router_bias"])
    np.testing.assert_allclose(moment / (1 - b1), excess, atol=2)
    B, S = batch["tokens"].shape
    choices = B * S * cfg["num_experts_per_tok"] * 2      # 2 MoE layers
    assert int(m["moe_routed"]) + int(m["moe_not_held"]) == choices
    assert 0 < int(m["moe_routed"]) < choices
    assert int(m["moe_dropped"]) == 0
    assert load.sum() == choices
    assert np.isfinite(float(m["moe_balance_loss"]))


def test_program_matches_reference_over_three_steps():
    """Loss, clipped first gradient and the change over three AdamW steps
    (with the router bias's moves between them) of the benchmark's
    program, computing in float32, against the float32 reference, on
    seeded weights; the first step's bias and counters."""
    from repro.models import model as M
    from repro.parallel.ep import make_moe_ep

    cfg = tiny()
    B, S = 2, 32
    prog = _program(cfg, B, S)
    key = REF.seed_key(SEED)
    batches = _batches(cfg, B, S, 3)
    params, opt = prog.state_init(lambda k: REF.weights(cfg, k))(key)
    impl = make_moe_ep(prog.mesh, prog.ep)
    with jax.set_mesh(prog.mesh):
        (_, counts), g = jax.jit(jax.value_and_grad(
            lambda p, b: M.loss_fn(prog.cfg, p, b, with_counts=True,
                                   moe_impl=impl), has_aux=True))(
            params, batches[0])
    losses = []
    for t, b in enumerate(batches):
        params, opt, m = prog.step(params, opt, b)
        losses.append(float(m["loss"]))
        if t == 0:
            b1 = cfg["optimizer"]["betas"][0]
            grads = {k: float(v) for k, v in REF.leaf_norms(jax.tree.map(
                lambda x: x / (1 - b1), opt["m"])).items()}
            _bias_and_counters_of_one_step(
                cfg, b, opt, m, np.asarray(counts["moe_load"]),
                g["blocks"]["moe"]["router_bias"])
    w0 = REF.weights(cfg, key)
    change = {k: float(v) for k, v in REF.leaf_norms(jax.tree.map(
        lambda a, w: a - w, opt["master"], w0)).items()}
    ref = REF.Reference(cfg, 1).run(lambda: REF.weights(cfg, key), batches)
    read = compare.readings({"losses": losses, "grad_norms": grads,
                             "change_norms": change}, ref)
    assert read["loss_gap"] < 1e-4, read
    assert read["grad_gap"] < 1e-3, read
    assert read["change_gap"] < 1e-3, read
    # The bias's entry: its first excess load, so its change is compared.
    bias = "['blocks']['moe']['router_bias']"
    assert grads[bias] == pytest.approx(ref["grad_norms"][bias], rel=1e-6)
    assert grads[bias] > 0 and bias not in read["left_out"]
    assert change[bias] == pytest.approx(ref["change_norms"][bias], rel=1e-6)
    assert change[bias] > 0


def test_expert_shares_sum_to_the_uncut_layer():
    """Four shares of four experts each: their routed parts summed, with the
    shared experts counted once, give the reference's uncut layer."""
    from repro.parallel.ep import EPConfig, make_moe_ep

    full = tiny(held=(0, 16))
    s = REF.sizes(full)
    p = jax.tree.map(lambda a: a[0],
                     REF.weights(full, REF.seed_key(SEED))["blocks"]["moe"])
    p["router_bias"] = 0.01 * jax.random.normal(jax.random.PRNGKey(1),
                                                (s["E"],))
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 16, s["d"]))
    want = jax.jit(lambda p, h: REF.moe(full, s, p, h, "float32")[0])(p, h)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    impl = make_moe_ep(mesh, EPConfig(capacity_factor=16 / 6))
    got = REF.swiglu(p["shared"], h, "float32")
    for first in range(0, 16, 4):
        mc = ARCH.model_config(tiny(held=(first, 4))).moe
        share = dict(p, w_in=p["w_in"][first:first + 4],
                     w_down=p["w_down"][first:first + 4])
        with jax.set_mesh(mesh):
            got = got + jax.jit(lambda p, h, mc=mc: impl(p, h, mc))(share, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_forward_flops_by_hand():
    # Moonlight at its published widths, chip 0's share, sequence 4096,
    # per token:
    # MLA projections 2*2048*3072 + 2*2048*576 + 2*512*4096 + 2*2048*2048
    # = 27525120; causal scores 2*2048.5*16*192 + 2*2048.5*16*128
    # = 20976640; dense SwiGLU 3*2*2048*11264 = 138412032; router
    # 2*2048*64 = 262144; shared 3*2*2048*2816 = 34603008; held routed
    # 0.75 * 3*2*2048*1408 = 12976128; unembedding 2*2048*20480 = 83886080.
    mla = 27525120 + 20976640
    fwd = (6 * mla + 138412032 + 5 * (262144 + 34603008 + 12976128)
           + 83886080)
    assert ARCH.forward_flops_per_token(_config(), 4096) == fwd
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    s, bound = ARCH.mla_least_s(_config(), 4096, 8192, peaks)
    assert bound == "compute"
    assert s == pytest.approx(4 * 6 * mla * 8192 / 197e12)
    s, bound = ARCH.expert_ffn_least_s(_config(), 30000, peaks)
    assert (bound, s) == ("compute",
                          pytest.approx(3 * 3 * 2 * 2048 * 1408 * 30000
                                        / 197e12))


@pytest.mark.parametrize("key, value, match", [
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "topk_group"),
    ("q_lora_rank", 1536, "q_lora_rank"),
    ("scoring_func", "softmax", "scoring_func"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("num_key_value_heads", 8, "one key and value head"),
    ("first_k_dense_replace", 6, "no MoE layer"),
    ("n_routed_experts", 7, "not one share"),
])
def test_model_config_refuses(key, value, match):
    cfg = dict(_config(), **{key: value})
    with pytest.raises(SystemExit, match=match):
        ARCH.model_config(cfg)


def test_model_config_maps_the_share():
    c = ARCH.model_config(_config())
    assert (c.n_layers, c.n_dense_layers, c.d_model, c.d_ff, c.vocab) == \
        (6, 1, 2048, 11264, 20480)
    assert dataclasses.astuple(c.mla) == (512, 128, 64, 128)
    m = c.moe
    assert (m.e_total, m.e_held, m.held_first, m.top_k, m.n_shared,
            m.d_expert, m.score) == (64, 8, 0, 6, 2, 1408, "sigmoid")
    assert (m.routed_scale, m.bias_speed, m.balance_alpha,
            c.norm_eps) == (2.446, 0.001, 0.0001, 1e-5)


def test_granite_smoke_forward_is_pinned():
    """The granite preset's forward and gradients through the one-chip EP
    path, bit for bit as before the DeepSeek-V3 mechanisms joined the
    program."""
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.optim import adamw
    from repro.parallel.ep import EPConfig, make_moe_ep

    cfg = get_smoke_config("granite-moe-3b-a800m")
    params = adamw.cast_params(M.init_params(cfg, jax.random.PRNGKey(0)),
                               cfg.compute_dtype)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    impl = make_moe_ep(mesh, EPConfig(capacity_factor=4.0))
    with jax.set_mesh(mesh):
        logits = jax.jit(lambda p, b: M.forward(cfg, p, b, moe_impl=impl))(
            params, batch)
        (loss, counts), g = jax.jit(jax.value_and_grad(
            lambda p, b: M.loss_fn(cfg, p, b, moe_impl=impl,
                                   with_counts=True), has_aux=True))(
            params, batch)
    h = hashlib.sha256()
    for a in [logits] + jax.tree.leaves(g):
        h.update(jax.device_get(a).tobytes())
    assert float(loss).hex() == "0x1.4ad70c0000000p+2"
    assert h.hexdigest() == ("7cb1bcea2aafd4c74b9ad7a94793242d"
                             "d56a0723a172ffc407eccaa53a2b4073")
    assert {k: int(v) for k, v in counts.items()} == {
        "moe_dropped": 0, "moe_routed": 256, "moe_slots": 1056}


@pytest.mark.parametrize("reading, correct", [
    ("lower", True), ("control", False), ("half_batch", False),
    ("state_unchanged", False), ("bias_still", False)])
def test_recorded_readings_against_the_cells_limits(reading, correct):
    """The cell's calibration readings on the chip, as its file records
    them (a number a reading leaves out taken as 0), judged by its own
    limits: the largest sound readings pass; the float8 control, the half
    batch, a state left unchanged and a still router bias fail. The float8
    control itself is not run here: on the CPU its loss gap stays under
    the limit on some seeds at any size the CPU trains in seconds (0.02-0.06
    at d 64; 0.13-0.34 at the published widths with one held expert, a
    vocabulary of 8192 and 2 x 128 tokens), and reads 0.218-0.306 at the
    cell's size."""
    with open(os.path.join(checkout.ROOT, "chipbench", "workloads",
                           "moonlight-6l.s4096.json")) as f:
        spec = json.load(f)
    read = dict.fromkeys(compare.NUMBERS, 0.0)
    read.update((k, v) for k, v in spec["readings"][reading].items()
                if k in compare.NUMBERS)
    assert compare.judge(read, spec["limits"])[0] is correct


CELL = "tiny-ds.s64"
NEW_READINGS = ("mla_attention_ms", "mla_attention_roofline", "dense_ffn_ms",
                "shared_expert_ms", "moe_router_ms")


def _tiny_cell(tmp_path):
    """A checkout with a tiny configuration of this architecture as a cell
    of its own, under the Moonlight cell's limits and readings; returns
    its root and the readings that list the cell."""
    root = checkout.make(str(tmp_path))
    here = os.path.join(root, "chipbench")
    checkout._dump(tiny(dtype="bfloat16"), here, "configs", "tiny-ds.json")
    b = checkout._bench(root)
    b["configs"].append({"name": "tiny-ds", "source": "test",
                         "file": "chipbench/configs/tiny-ds.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": CELL, "config": "tiny-ds",
                           "traffic": "s64", "chips": 1, "why": "test"})
    listed = []
    for m in b["per_layer"]:
        if "moonlight-6l.s4096" in m.get("workloads", []):
            m["workloads"] = [CELL]
            listed.append(m["name"])
    checkout._dump(b, root, "BENCHMARK.json")
    with open(os.path.join(here, "workloads",
                           "moonlight-6l.s4096.json")) as f:
        checkout._dump(json.load(f), here, "workloads", CELL + ".json")
    return root, listed


def test_cell_traced_line_on_cpu(tmp_path):
    """A tiny configuration of this architecture as a cell of its own, run
    through the benchmark's entry with the trace read off the CPU's XLA
    threads: correct, and the traced line carries every reading that lists
    the Moonlight cell, their scopes and the held-share counters."""
    root, listed = _tiny_cell(tmp_path)
    p = checkout.run(root, ["--workload", CELL, "--seed", str(2 ** 40 + 11),
                            "--seconds", "1", "--trace", "1"],
                     plant=checkout.CPU_OPS)
    assert p.returncode == 0, p.stderr[-3000:]
    line = checkout.last_line(p.stdout)
    assert line["correct"] is True, line["checks"]
    assert set(NEW_READINGS) <= set(listed)
    assert set(listed) | {"mfu", "compiles_in_window", "device_idle_frac"} \
        == set(line["metrics"])
    for scope in ("mla", "attention", "dense_ffn", "moe/shared_expert",
                  "moe/router", "moe/expert_ffn"):
        assert line["layer_ms"][scope] > 0, scope
    for name in ("mla_attention_roofline", "expert_ffn_roofline"):
        assert line["metrics"][name]["value"] > 0
    assert line["metrics"]["mla_attention_ms"]["value"] == pytest.approx(
        line["layer_ms"]["mla"] + line["layer_ms"]["attention"])
    for c in line["counters"]:
        assert c["moe_routed"] + c["moe_not_held"] == 2 * 64 * 6 * 2


# The router bias never moves; its first moment is still kept.
BIAS_STILL = """
import repro.launch.steps as S
_bias_step = S.bias_step
def bias_step(bias, moment, load, speed, beta):
    return bias, _bias_step(bias, moment, load, speed, beta)[1]
S.bias_step = bias_step
"""


def test_cell_with_the_router_bias_left_still_is_not_correct(tmp_path):
    """A program whose router bias stays where it started comes out not
    correct under the cell's limits: the bias's leaf is in change_gap."""
    root, _ = _tiny_cell(tmp_path)
    p = checkout.run(root, ["--workload", CELL, "--seed", str(2 ** 40 + 12),
                            "--seconds", "1", "--trace", "0"],
                     plant=BIAS_STILL)
    assert p.returncode == 0, p.stderr[-3000:]
    line = checkout.last_line(p.stdout)
    assert line["correct"] is False
    assert line["checks"]["change_gap"]["value"] == 1.0
    assert "worst change leaf ['blocks']['moe']['router_bias']" in p.stderr
