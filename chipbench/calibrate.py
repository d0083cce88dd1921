#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] \
        [--faults half_batch,altered_token] [--out <file.json>]

For each seed of ``--seeds``: the program's set-up steps through the
trainer's loop (a window of one step), then the float32 reference, and the
three numbers ``compare.readings`` gives; their largest over the seeds is
the lower reading. For each seed of ``--control-seeds``: the control, the
reference computed in float8 in the program's place, against the float32
reference. For each seed of ``--fault-seeds`` and each fault: the reference
with the fault planted, against the sound reference. The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def seeds(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def main(argv=None, require=None) -> int:
    from chipbench import compare, harness, program

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload)
    program.import_program()
    devices = (require or harness.require_accelerator)(cell.chips)
    from chipbench import train_cell

    tc = train_cell.TrainCell(cell, devices)
    rows = []

    def emit(kind, seed, read, extra=None):
        row = {"kind": kind, "seed": seed,
               **{k: read[k] for k in compare.NUMBERS},
               "grad_leaf": read["grad_leaf"],
               "change_leaf": read["change_leaf"], **(extra or {})}
        rows.append(row)
        print(json.dumps(row), flush=True)

    refs = {}

    def ref(seed):
        if seed not in refs:
            refs[seed] = tc.reference(seed)
        return refs[seed]

    for s in args.seeds:
        t0 = time.perf_counter()
        d = tc.drive(s, 1e-3)
        r = ref(s)
        emit("program", s, compare.readings(d["got"], r),
             {"losses": d["got"]["losses"], "ref_losses": r["losses"],
              "routing": tc.routing(r), "peak": d["peak"],
              "seconds": time.perf_counter() - t0})
    for s in args.control_seeds:
        c = tc.reference(s, precision="fp8")
        emit("control", s, compare.readings(c, ref(s)))
    for fault in [f for f in args.faults.split(",") if f]:
        for s in args.fault_seeds:
            f = tc.reference(s, fault=fault)
            emit(fault, s, compare.readings(f, ref(s)))

    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r for r in rows if r["kind"] == kind]
        agg = max if kind == "program" else min
        summary[kind] = {k: agg(r[k] for r in sel) for k in compare.NUMBERS}
        summary[kind]["seeds"] = len(sel)
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
