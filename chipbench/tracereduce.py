"""Reduction of a profiler trace to device busy time, idle gaps, the
device operations that took most time and the host activity under each
gap.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events: per device, the XLA operations as ``(name, start_ns, end_ns)``; for
the host, the benchmark's ``traced_window`` span, and the trainer loop's
own spans (``train_step`` with its ``step_num``, and the ``train_loop/*``
phases). The rest works on those events only, so a small recorded trace
kept as a fixture checks it.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
# Operations as the core runs them; a loop or conditional op spans the ops
# of its body, which the same line holds nested inside it.
OPS_LINE = "XLA Ops"
# The span the benchmark opens around the traced steps.
WINDOW_SPAN = "traced_window"
# The trainer loop's spans: one per step, and one per phase of a step.
STEP_SPAN = "train_step"
PHASE_PREFIX = "train_loop/"


def load(trace_dir: str) -> dict:
    """``{"devices": {id: [(name, start, end), ...]}, "host": [...],
    "program": [(name, start, end, step_num or None), ...]}``, times in ns
    on the trace's clock; ``program`` is empty where the loop opens no
    spans."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host, program = {}, [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[dev] = sorted(
                        ((e.name, float(e.start_ns), float(e.end_ns))
                         for e in line.events), key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, float(e.start_ns), float(e.end_ns))
                    if e.name == WINDOW_SPAN:
                        host.append(span)
                    elif e.name == STEP_SPAN:
                        program.append(span + (dict(e.stats).get(
                            "step_num"),))
                    elif e.name.startswith(PHASE_PREFIX):
                        program.append(span + (None,))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1]),
            "program": sorted(program, key=lambda e: e[1])}


def short_name(name: str) -> str:
    """An op's HLO instruction name and result type, without its operands:
    ``%fusion.707 = bf16[131376,1536]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    return head + " = " + rest.split("{", 1)[0].split(" ", 1)[0]


def self_times(events) -> list:
    """``(name, self_ns)`` for each event: a loop op's self time is its
    span less the ops nested in it."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    self_ns = [e - s for _, s, e in evs]
    stack = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            self_ns[p] -= min(e, evs[p][2]) - s
        stack.append(i)
    return [(n, t) for (n, _, _), t in zip(evs, self_ns)]


def structure(trace_dir: str, top: int = 25) -> dict:
    """What a trace holds: each plane's lines with their event counts and
    most frequent names. For reading a trace by hand."""
    import collections

    import jax

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            c = collections.Counter(e.name for e in evs)
            lines[line.name] = {
                "events": len(evs),
                "first_ns": min((e.start_ns for e in evs), default=None),
                "last_ns": max((e.end_ns for e in evs), default=None),
                "names": c.most_common(top)}
        out[plane.name] = lines
    return out


def window(trace: dict) -> tuple[float, float]:
    """The traced window: the benchmark's ``traced_window`` span."""
    spans = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace has no traced_window span")
    return spans[0][1], spans[0][2]


def clip(events, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(intervals) -> list:
    """Merged ``(start, end)`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union((s, e) for _, s, e in
                                       clip(events, lo, hi)))


def gaps(events, lo: float, hi: float) -> list:
    """Idle ``(start, end)`` intervals of one device inside the window."""
    out, t = [], lo
    for s, e in union((s, e) for _, s, e in clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def idle_share(trace: dict) -> dict:
    """Per device: 1 - busy / window."""
    lo, hi = window(trace)
    return {d: 1.0 - busy_ns(ev, lo, hi) / (hi - lo)
            for d, ev in trace["devices"].items()}


def host_label(trace: dict, s: float, e: float) -> str:
    """The trainer loop's phase span that overlaps an interval most, or
    ``other``."""
    best, name = 0.0, "other"
    for n, hs, he, _ in trace.get("program", []):
        if not n.startswith(PHASE_PREFIX) or he <= s or hs >= e:
            continue
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time (self time in seconds,
    summed over the traced window, mean over devices; loop ops count only
    the time their body's ops leave) and the idle time of the device with
    most idle, by what the host was doing in each gap."""
    lo, hi = window(trace)
    n_dev = max(1, len(trace["devices"]))
    ops: dict = {}
    for ev in trace["devices"].values():
        for n, t in self_times(clip(ev, lo, hi)):
            k = short_name(n)
            ops[k] = ops.get(k, 0.0) + t / 1e9 / n_dev
    idle = idle_share(trace)
    worst = max(idle, key=idle.get)
    by_host: dict = {}
    for s, e in gaps(trace["devices"][worst], lo, hi):
        lab = host_label(trace, s, e)
        by_host[lab] = by_host.get(lab, 0.0) + (e - s) / 1e9
    rank = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gap_rank = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v] for n, v in rank],
            "idle_gaps": [[n, v] for n, v in gap_rank]}
