"""The reading of the program's measurement points on the CPU: op names to
named scopes, device time by scope and idle time by loop phase over the
``train_step`` spans, the per-step readings ``chipbench/layers.py`` makes
of them, and traced runs of the benchmark on the program and on a program
that has no spans and no counters."""

from __future__ import annotations

import gzip
import json
import os
import types

import pytest

from chipbench import harness, layers, scopes
from chipbench.arch import granitemoe
from chipbench.arch.granitemoe import SCOPES
from chipbench.tests import checkout

HERE = os.path.dirname(os.path.abspath(__file__))
READINGS = ("moe_dispatch_ms", "moe_expert_ffn_ms", "moe_combine_ms",
            "attention_ms", "optimizer_ms", "expert_ffn_roofline",
            "expert_slot_fill", "idle_input_ms", "idle_loop_ms")


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp(embed)/gather", "embed"),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add", "embed"),
    ("jit(train_step)/transpose(jvp(unembed_ce))/while/body/dot_general",
     "unembed_ce"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/moe/dispatch/jit(take_along_axis)/lt",
     "moe/dispatch"),
    ("jit(train_step)/jvp()/while/body/closed_call/attention/closed_call/"
     "while/body/remat2", "attention"),
    ("jit(step)/moe/dispatch/moe/router/top_k", "moe/router"),
    ("jit(step)/optimizer/attention/add", "attention"),
    ("jit(train_step)/jvp()/while/body/add", "other"),
    ("jit(step)/moe/routerx/add", "other"),
    ("", "other"),
], ids=["fwd", "transposed", "not_embed", "remat", "nested_call",
        "nested_scope", "innermost", "unscoped", "partial_name", "empty"])
def test_scope_of_by_hand(op_name, scope):
    assert scopes.scope_of(op_name, SCOPES) == scope


def test_op_scopes_from_hlo_text():
    dispatch = 'metadata={op_name="jit(f)/transpose(jvp())/while/body/' \
        'checkpoint/moe/dispatch/gather"}'
    text = "\n".join([
        "HloModule jit_train_step",
        "%fused_computation.3 (param_0: f32[8]) -> f32[8] {",
        "  %param_0 = f32[8]{0} parameter(0)",
        '  ROOT %mul.8 = f32[8]{0} multiply(%param_0, %param_0), metadata={'
        'op_name="jit(f)/transpose(jvp())/while/body/moe/expert_ffn/mul"}',
        "}",
        "%fused_computation.12 (param_0.1: bf16[8], param_1.1: s32[4]) -> "
        "bf16[8] {",
        "  %param_0.1 = bf16[8]{0} parameter(0)",
        "  %param_1.1 = s32[4]{0} parameter(1)",
        "  %reshape.1 = s32[4]{0} reshape(%param_1.1), " + dispatch,
        "  %transpose.2 = s32[4]{0} transpose(%reshape.1), " + dispatch,
        "  ROOT %scatter.18 = bf16[8]{0} scatter(%param_0.1, %transpose.2),"
        " to_apply=%region_1",
        "}",
        "%region_1 (a: bf16[], b: bf16[]) -> bf16[] {",
        "  %a = bf16[] parameter(0)",
        "  %b = bf16[] parameter(1)",
        "  ROOT %add.1 = bf16[] add(%a, %b)",
        "}",
        "ENTRY %main (p: f32[8], q: s32[4]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %q = s32[4]{0} parameter(1)",
        '  %fusion.686 = f32[8]{0} fusion(%p), kind=kLoop, '
        'calls=%fused_computation.3, metadata={op_name="jit(f)/jvp()/'
        'moe/dispatch/scatter-add" stack_frame_id=2}',
        "  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, "
        "calls=%fused_computation.3",
        "  %fusion.9 = bf16[8]{0} fusion(%p, %q), kind=kCustom, "
        "calls=%fused_computation.12",
        "  %copy.360 = f32[8]{0} copy(%fusion.7)",
        "  %constant.1 = s32[] constant(0)",
        '  ROOT %while.9 = (s32[]) while(%constant.1), condition=%c, '
        'body=%b, metadata={op_name="jit(f)/jvp()/while"}',
        "}"])
    got = scopes.op_scopes(text, SCOPES)
    # named: its own op_name; a fusion with none: its fused root's, else
    # what most fused instructions carry; a copy: its operand's.
    assert got["fusion.686"] == "moe/dispatch"
    assert got["fusion.7"] == "moe/expert_ffn"
    assert got["fusion.9"] == "moe/dispatch"
    assert got["copy.360"] == "moe/expert_ffn"
    assert got["while.9"] == got["constant.1"] == got["p"] == "other"
    assert scopes.instruction("%fusion.686 = bf16[4,8]") == "fusion.686"


def _trace():
    # One chip, a traced window [0, 1000] holding two steps. Step 1
    # (100-500): a loop op whose body runs dispatch and the FFN, then the
    # optimizer; step 2 (500-900): attention, an op of no known scope,
    # idle. Ops before the first step span and after the last count in no
    # step.
    return {
        "devices": {0: [
            ("%fusion.0 = f32[8]", 20, 60),
            ("%while.9 = (f32[8])", 150, 350),
            ("%fusion.1 = bf16[8]", 150, 200),
            ("%fusion.2 = bf16[8]", 210, 330),
            ("%fusion.3 = f32[8]", 360, 440),
            ("%fusion.4 = bf16[8]", 560, 700),
            ("%copy.5 = bf16[8]", 700, 720),
            ("%fusion.6 = bf16[8]", 950, 990)]},
        "host": [("traced_window", 0, 1000)],
        "program": [
            ("train_loop/batch", 10, 90, None),
            ("train_step", 100, 500, 7),
            ("train_loop/batch", 100, 140, None),
            ("train_loop/dispatch", 140, 160, None),
            ("train_loop/wait", 160, 450, None),
            ("train_loop/log", 450, 500, None),
            ("train_step", 500, 900, 8),
            ("train_loop/hook", 500, 510, None),
            ("train_loop/batch", 510, 550, None),
            ("train_loop/dispatch", 550, 570, None),
            ("train_loop/wait", 570, 720, None),
            ("train_loop/log", 720, 900, None),
            ("train_step", 900, 1100, 9)],
    }


OP_SCOPE = {"while.9": "other", "fusion.1": "moe/dispatch",
            "fusion.2": "moe/expert_ffn", "fusion.3": "optimizer",
            "fusion.4": "attention", "fusion.0": "embed",
            "fusion.6": "embed"}


def test_layer_ms_by_hand():
    t = _trace()
    assert [s[0] for s in scopes.steps(t)] == [7, 8]
    ms = scopes.layer_ms(t, OP_SCOPE, SCOPES)
    # ns summed over two steps, per step, in ms.
    assert ms["moe/dispatch"] == pytest.approx(50 / 2e6)
    assert ms["moe/expert_ffn"] == pytest.approx(120 / 2e6)
    assert ms["optimizer"] == pytest.approx(80 / 2e6)
    assert ms["attention"] == pytest.approx(140 / 2e6)
    assert ms["embed"] == 0.0
    # the loop op's own 30 ns, and the copy no scope names
    assert ms["other"] == pytest.approx((30 + 20) / 2e6)
    assert ms["busy_total"] == pytest.approx((200 + 80 + 160) / 2e6)
    assert sum(ms[k] for k in SCOPES + ("other",)) == \
        pytest.approx(ms["busy_total"])
    assert set(ms) == set(SCOPES) | {"other", "busy_total"}


def test_idle_by_phase_by_hand():
    idle = scopes.idle_by_phase(_trace())
    # Each idle interval is split over the phases it overlaps. Step 7
    # idles [100, 150] (batch 40, dispatch 10), [350, 360] (wait) and
    # [440, 500] (wait 10, log 50); step 8 [500, 560] (hook 10, batch 40,
    # dispatch 10) and [720, 900] (log).
    assert idle["train_loop/batch"] == pytest.approx(80 / 2e6)
    assert idle["train_loop/dispatch"] == pytest.approx(20 / 2e6)
    assert idle["train_loop/wait"] == pytest.approx(20 / 2e6)
    assert idle["train_loop/log"] == pytest.approx(230 / 2e6)
    assert idle["train_loop/hook"] == pytest.approx(10 / 2e6)
    assert idle["other"] == 0.0
    assert sum(idle.values()) == pytest.approx(360 / 2e6)


def test_no_spans_or_scopes_read_nothing():
    t = _trace()
    no_spans = dict(t, program=[])
    assert scopes.layer_ms(no_spans, OP_SCOPE, SCOPES) is None
    assert scopes.idle_by_phase(no_spans) is None
    no_program = {k: v for k, v in t.items() if k != "program"}
    assert scopes.steps(no_program) == []
    assert scopes.layer_ms(t, {"while.9": "other"}, SCOPES) is None
    assert scopes.layer_ms(t, {}, SCOPES) is None


def _granite():
    with open(os.path.join(checkout.ROOT, "chipbench", "configs",
                           "granite-moe-3b-a800m-4l.json")) as f:
        return json.load(f)


LAYER_MS = dict(dict.fromkeys(SCOPES, 1.0), **{"moe/expert_ffn": 40.0})
IDLE_MS = {"train_loop/batch": 1.5, "train_loop/wait": 2.0,
           "train_loop/log": 0.25, "train_loop/hook": 0.25}
COUNTS = [{"moe_routed": 100, "moe_dropped": 10, "moe_slots": 400}] * 2


@pytest.mark.parametrize("layer_ms, idle_ms, counts, want", [
    (None, None, [], set()),
    (LAYER_MS, None, [], {"moe_dispatch_ms", "moe_expert_ffn_ms",
                          "moe_combine_ms", "attention_ms",
                          "optimizer_ms"}),
    (None, IDLE_MS, [], {"idle_input_ms", "idle_loop_ms"}),
    (None, None, COUNTS, {"expert_slot_fill"}),
    (None, None, [{"moe_routed": 1}], set()),
], ids=["nothing", "scopes_only", "spans_only", "counters_only",
        "counters_partial"])
def test_readings_leave_out_what_is_missing(layer_ms, idle_ms, counts,
                                            want):
    got = layers.readings(granitemoe, _granite(),
                          harness.peaks("TPU v5 lite"), 1, layer_ms,
                          idle_ms, counts)
    assert set(got) == want


def test_readings_by_hand():
    cfg, peaks = _granite(), harness.peaks("TPU v5 lite")
    got = layers.readings(granitemoe, cfg, peaks, 1, LAYER_MS, IDLE_MS,
                          COUNTS)
    assert set(got) == set(READINGS) | {"expert_ffn_bound"}
    assert got["moe_expert_ffn_ms"] == 40.0
    assert got["expert_slot_fill"] == pytest.approx(22.5)
    assert got["idle_input_ms"] == 1.5
    assert got["idle_loop_ms"] == 2.5
    # 90 kept rows a step, d 1536, f 512: 3 x 6 d f FLOPs a row; the
    # weights of 4 layers x 40 experts (3 d f bf16 each) move three times,
    # the rows five.
    flops = 3 * 6 * 1536 * 512 * 90
    nbytes = 3 * 4 * 40 * 3 * 1536 * 512 * 2 + 5 * 90 * 1536 * 2
    assert got["expert_ffn_bound"] == "memory"
    assert got["expert_ffn_roofline"] == pytest.approx(
        100 * max(flops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"]) / 0.040)


# A program whose loop opens no spans and whose step returns no counters,
# as before they were added.
NO_SPANS_OR_COUNTERS = """
import contextlib, repro.ft.runner as R, repro.launch.steps as St
R.span = R.phase = lambda *a, **k: contextlib.nullcontext()
_make = St.make_steps
def make_steps(*a, **k):
    fns = _make(*a, **k)
    step = fns.train_step
    def train_step(p, o, b):
        p, o, m = step(p, o, b)
        return p, o, {k: v for k, v in m.items() if not k.startswith('moe_')}
    fns.train_step = train_step
    return fns
St.make_steps = make_steps
"""


def _traced_line(root, seconds, plant=""):
    p = checkout.run(root, ["--workload", checkout.CELL, "--seed",
                            str(2 ** 41 + 3), "--seconds", str(seconds),
                            "--trace", "1"], plant=checkout.CPU_OPS + plant)
    assert p.returncode == 0, p.stderr[-3000:]
    line = checkout.last_line(p.stdout)
    assert line["correct"] is True, line["checks"]
    return line


@pytest.mark.parametrize("plant", ["", NO_SPANS_OR_COUNTERS],
                         ids=["program", "program_without_spans_or_counters"])
def test_traced_line_reads_the_checked_steps_on_cpu(tmp_path, plant):
    """A traced run carries the layer readings, with the counters of the
    checked steps (0-2): two runs of one seed whose windows hold different
    numbers of steps read the same counters, slot fill and dropped
    choices. A program without spans or counters leaves out what they
    give, and is not an error."""
    root = checkout.make(str(tmp_path))
    line = _traced_line(root, 1, plant)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if plant:
        assert line["counters"] == [{"step": st} for st in range(3)]
        assert line["idle_ms"] is None and line["layer_ms"] is None
        assert "dropped_choices" not in line["routing"]
        assert not set(READINGS) & set(got)
        return
    assert [c["step"] for c in line["counters"]] == [0, 1, 2]
    # tiny: 2 layers, top-2 of 8 experts over 2 x 64 tokens a step, 128
    # rows an expert (4 x the mean of 32).
    for c in line["counters"]:
        assert c["moe_routed"] == 2 * 128 * 2
        assert c["moe_slots"] == 2 * 8 * 128
        assert 0 <= c["moe_dropped"] <= c["moe_routed"]
    assert set(READINGS) <= set(got)
    kept = sum(c["moe_routed"] - c["moe_dropped"] for c in line["counters"])
    assert got["expert_slot_fill"] == pytest.approx(
        100 * kept / (3 * 2 * 8 * 128), rel=1e-12)
    assert line["routing"]["dropped_choices"] == sum(
        c["moe_dropped"] for c in line["counters"])
    longer = _traced_line(root, 3)
    assert longer["attempted"] > line["attempted"]
    assert longer["counters"] == line["counters"]
    assert longer["routing"] == line["routing"]
    assert longer["metrics"]["expert_slot_fill"] == \
        line["metrics"]["expert_slot_fill"]


def _recorded():
    with gzip.open(os.path.join(HERE, "fixtures",
                                "scoped_steps_1chip.json.gz"), "rt") as f:
        rec = json.load(f)
    ev = rec["events"]
    t = {"devices": {int(k): [tuple(e) for e in v]
                     for k, v in ev["devices"].items()},
         "host": [tuple(e) for e in ev["host"]],
         "program": [tuple(e) for e in ev["program"]]}
    return rec, t


# Counters of one granite-4l.s2048 step on one chip: 4 layers x 4096
# tokens x top-8 choices, 40 x 3280 slots a layer, about 11600 dropped.
CHIP_COUNTS = [{"moe_routed": 131072, "moe_dropped": 11590,
                "moe_slots": 524800}] * 2


def test_readers_equal_layers_readings_on_recorded_steps():
    """The benchmark's readers of the nine layer readings, on the recorded
    chip steps, read what ``layers.readings`` reads from the same input,
    and the roofline share stays under 100%."""
    rec, t = _recorded()
    cfg, peaks = _granite(), harness.peaks("TPU v5 lite")
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(arch=granitemoe), config=cfg,
        peaks=peaks, chips=1,
        layer_ms=scopes.layer_ms(t, rec["scopes"], SCOPES),
        idle_ms=scopes.idle_by_phase(t), counts=CHIP_COUNTS)
    want = layers.readings(granitemoe, cfg, peaks, 1, run.layer_ms,
                           run.idle_ms, CHIP_COUNTS)
    metrics = os.path.join(checkout.ROOT, "chipbench", "metrics")
    got = {name: harness.reader(metrics, name)(run) for name in READINGS}
    assert got == {k: want[k] for k in READINGS}
    assert 0 < got["expert_ffn_roofline"] <= 100
    assert got["moe_dispatch_ms"] == rec["expect"]["layer_ms"][
        "moe/dispatch"]
    bench = harness.benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in READINGS:
        assert set(per_layer[name]["workloads"]) == {
            w["name"] for w in bench["workloads"]}


def test_scoped_steps_recorded_on_chip():
    """Two steps of a `--trace 1` run of `granite-4l.s2048` on a v5e chip
    (op names shortened), with the trainer loop's spans and the op→scope
    map of the compiled step: the reduction gives what it gave when the
    fixture was made, the scopes and ``other`` add up to the busy time,
    and the named scopes hold at least 90% of it."""
    rec, t = _recorded()
    want = rec["expect"]
    assert [s[0] for s in scopes.steps(t)] == want["steps"]
    ms = scopes.layer_ms(t, rec["scopes"], SCOPES)
    assert ms == pytest.approx(want["layer_ms"], rel=1e-9)
    named = sum(ms[k] for k in SCOPES)
    assert named + ms["other"] == pytest.approx(ms["busy_total"], rel=0.01)
    assert named >= 0.9 * ms["busy_total"]
    assert scopes.idle_by_phase(t) == pytest.approx(want["idle_ms"],
                                                         rel=1e-9)
