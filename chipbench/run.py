#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in the checkout's ``BENCHMARK.json``.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, the numbers compared with the plain
reference beside their limits. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not 0 <= args.seed < 2 ** 64:
        ap.error("--seed must be a whole number in [0, 2**64)")
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    return args


def main(argv=None, require=None) -> int:
    from chipbench import harness, program

    args = parse(argv)
    cell = harness.Cell(args.workload)
    program.import_program()
    devices = (require or harness.require_accelerator)(cell.chips)
    from chipbench import train_cell

    return train_cell.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, devices)


if __name__ == "__main__":
    sys.exit(main())
