"""Device time by layer: each device operation's named scope, its self time
summed per scope over the traced steps, and the device's idle time by the
phase the trainer's loop was in.

The program names its layers with ``jax.named_scope``; the module of the
configuration's architecture lists those names (``SCOPES`` of
``chipbench/arch/<model_type>.py``), and every function here that needs
them takes them as ``scopes``. JAX writes the scope path into each HLO
instruction's ``op_name`` metadata, through ``jvp``, ``transpose`` and
remat alike (``transpose(jvp(attention))``,
``.../checkpoint/rematted_computation/moe/dispatch/...``). A v5e trace's
XLA Ops events carry the instruction's name (``%fusion.686 = bf16[...]``)
and no scope, so ``op_scopes`` reads the map from instruction name to scope
from the compiled step's HLO text (``Compiled.as_text()``), and
``layer_ms`` sums each op's self time under its scope. An instruction takes
the innermost known scope on its ``op_name``. The TPU compiler leaves some
instructions without an ``op_name``: a fusion then takes the scope of its
fused computation's root, or else the scope most of the instructions fused
into it carry; a copy or other op takes its first operand's. What finds no
known scope counts as ``other``.

The trainer's loop (``repro.ft.runner.train_loop``) opens a ``train_step``
span per step (a ``StepTraceAnnotation`` whose ``step_num`` is kept) and
``train_loop/*`` spans for its phases; ``tracereduce.load`` reads them into
the trace's ``program`` key.
"""

from __future__ import annotations

import collections
import re

from chipbench import tracereduce

OTHER = "other"

_SEP = re.compile(r"[/()]")
_HEADER = re.compile(r"(?:ENTRY\s+)?%([^\s(]+) \(.*\{\s*$")
_INSTR = re.compile(r"\s*(ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str, scopes) -> str:
    """The innermost of ``scopes`` on an ``op_name`` path, or ``other``.
    Transformations wrap scopes in parentheses (``transpose(jvp(embed))``),
    so the path is split on ``/``, ``(`` and ``)``."""
    segs = [s for s in _SEP.split(op_name) if s]
    for end in range(len(segs), 0, -1):
        for parts in (tuple(s.split("/")) for s in scopes):
            if tuple(segs[end - len(parts):end]) == parts:
                return "/".join(parts)
    return OTHER


def instruction(event_name: str) -> str:
    """An event's HLO instruction name: ``%fusion.686 = bf16[...]`` gives
    ``fusion.686``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def _parse(hlo_text: str, scopes):
    """Per instruction: its ``op_name`` scope (None without one), the
    computations it calls, its operands; per computation: its root and its
    instructions."""
    own, calls, operands, roots, members = {}, {}, {}, {}, {}
    comp = None
    lines = []
    for line in hlo_text.splitlines():
        h = _HEADER.match(line)
        if h is not None:
            comp = h.group(1)
            members[comp] = []
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        name = m.group(2)
        n = _OP_NAME.search(line)
        own[name] = scope_of(n.group(1), scopes) if n is not None else None
        members[comp].append(name)
        if m.group(1):
            roots[comp] = name
        lines.append((name, line[m.end():]))
    for name, rest in lines:
        refs = _REF.findall(rest.split(", metadata=")[0])
        calls[name] = [r for r in refs if r in members]
        operands[name] = [r for r in refs if r in own]
    return own, calls, operands, roots, members


def op_scopes(hlo_text: str, scopes) -> dict:
    """``{instruction name: scope}`` for every instruction of an HLO
    module's text, each one of ``scopes`` or ``other``."""
    own, calls, operands, roots, members = _parse(hlo_text, scopes)
    memo: dict = {}

    def votes(comp, seen):
        out = []
        for i in members[comp]:
            if own[i] not in (None, OTHER):
                out.append(own[i])
            for c in calls[i]:
                if c not in seen:
                    seen.add(c)
                    out += votes(c, seen)
        return out

    def resolve(name):
        if name in memo:
            return memo[name]
        memo[name] = OTHER              # guards a cycle; HLO has none
        s = own[name]
        if s is None and calls[name]:
            comp = calls[name][0]
            s = resolve(roots[comp]) if comp in roots else OTHER
            if s == OTHER:
                v = collections.Counter(votes(comp, {comp}))
                s = v.most_common(1)[0][0] if v else OTHER
        elif s is None:
            s = resolve(operands[name][0]) if operands[name] else OTHER
        memo[name] = s
        return s

    return {name: resolve(name) for name in own}


def steps(trace: dict) -> list:
    """``(step, start, end)`` of each ``train_step`` span that lies inside
    the traced window; empty where the program opens none."""
    lo, hi = tracereduce.window(trace)
    return [(st, s, e) for n, s, e, st in trace.get("program", [])
            if n == tracereduce.STEP_SPAN and s >= lo and e <= hi]


def idle_by_phase(trace: dict):
    """Device idle in ms per ``train_step`` span, of the chip with most
    idle inside the spans, by the loop phase the host was in: each idle
    interval is split over the ``train_loop/*`` spans it overlaps, and what
    no phase covers is ``other``. ``{phase: ms}`` with every phase seen in
    the trace, or None without step spans."""
    spans = steps(trace)
    if not spans or not trace["devices"]:
        return None
    phases = [p for p in trace["program"]
              if p[0].startswith(tracereduce.PHASE_PREFIX)]
    worst = None
    for ev in trace["devices"].values():
        by = dict.fromkeys([n for n, *_ in phases] + [OTHER], 0.0)
        for _, s, e in spans:
            for gs, ge in tracereduce.gaps(ev, s, e):
                left = ge - gs
                for n, ps, pe, _ in phases:
                    ov = min(ge, pe) - max(gs, ps)
                    if ov > 0:
                        by[n] += ov
                        left -= ov
                by[OTHER] += left
        if worst is None or sum(by.values()) > sum(worst.values()):
            worst = by
    return {n: v / 1e6 / len(spans) for n, v in worst.items()}


def layer_ms(trace: dict, op_scope: dict, scopes):
    """Device ms per ``train_step`` span of the traced window, mean over
    devices: self time under each of ``scopes``, ``other``, and
    ``busy_total``, the union of the ops' intervals. None without step
    spans, or where no op maps to a known scope."""
    spans = steps(trace)
    if (not spans or not trace["devices"]
            or not any(v != OTHER for v in op_scope.values())):
        return None
    out = dict.fromkeys(tuple(scopes) + (OTHER,), 0.0)
    busy = 0.0
    for ev in trace["devices"].values():
        for _, s, e in spans:
            clipped = tracereduce.clip(ev, s, e)
            for name, t in tracereduce.self_times(clipped):
                out[op_scope.get(instruction(name), OTHER)] += t
            busy += tracereduce.busy_ns(ev, s, e)
    per = 1e6 * len(trace["devices"]) * len(spans)
    out = {k: v / per for k, v in out.items()}
    out["busy_total"] = busy / per
    return out
