"""What the benchmark reads by name: the cell, its configuration, traffic
and limits, and one reader per metric.

Everything that belongs to one cell, configuration, traffic mix or metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the model and deployment as run;
- ``arch/<model_type>.py``: how the configuration's architecture maps onto
  the program, counts its FLOPs and names its layers (``arch``);
- ``traffic/<traffic>.json``: parameters of the traffic generator;
- ``workloads/<cell>.json``: the limits of the cell's comparison;
- ``metrics/<metric>.py``: a ``read(run)`` that returns the metric's
  value, or None where the run holds nothing to read it from.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(SystemExit):
    pass


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"chipbench: missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with every file it names loaded."""

    def __init__(self, name: str, root: str = ROOT):
        bench = benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SpecError(f"chipbench: no workload {name!r}; known: "
                            f"{sorted(cells)}")
        here = os.path.join(root, "chipbench")
        w = cells[name]
        self.name, self.chips = name, int(w["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
        self.config = _load_json(os.path.join(root, conf["file"]))
        self.traffic = _load_json(os.path.join(here, "traffic",
                                               w["traffic"] + ".json"))
        self.limits = _load_json(os.path.join(here, "workloads",
                                              name + ".json"))["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.metrics_dir = os.path.join(here, "metrics")
        self.arch = arch(self.config, root)
        if self.config["deployment"]["mesh"][0] \
                * self.config["deployment"]["mesh"][1] != self.chips:
            raise SpecError(f"chipbench: {name} asks for {self.chips} chips "
                            f"and its configuration's mesh for "
                            f"{self.config['deployment']['mesh']}")


def load_module(path: str, what: str):
    """The Python file at ``path``, imported under a name of its own."""
    if not os.path.isfile(path):
        raise SpecError(f"chipbench: {what} has no file at "
                        f"{os.path.relpath(path, ROOT)}")
    name = "chipbench_" + os.path.relpath(path, BENCH_DIR)
    spec = importlib.util.spec_from_file_location(
        "".join(c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ARCHS = {}


def arch(cfg: dict, root: str = ROOT):
    """The module of the configuration's architecture,
    ``chipbench/arch/<model_type>.py``: ``SCOPES``, ``model_config``,
    ``forward_flops_per_token`` and ``expert_ffn_least_s`` (the granite
    module, ``arch/granitemoe.py``, says what each is). Loaded once a
    process for each file; ``Cell.arch`` is the one its cell uses."""
    name = str(cfg.get("model_type"))
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]*", name):
        raise SpecError(f"chipbench: configuration {cfg.get('name')!r} "
                        f"states no model_type a module can be named by: "
                        f"{name!r}")
    path = os.path.join(root, "chipbench", "arch", name + ".py")
    if path not in _ARCHS:
        _ARCHS[path] = load_module(path, f"model_type {name!r}")
    return _ARCHS[path]


def reader(metrics_dir: str, name: str):
    return load_module(os.path.join(metrics_dir, name + ".py"),
                       f"metric {name!r}").read


def read_metrics(cell: Cell, entries: list, run) -> dict:
    """Each metric's reader on the run; a reader that finds nothing to read
    leaves its metric out."""
    out = {}
    for m in entries:
        v = reader(cell.metrics_dir, m["name"])(run)
        if v is None:
            continue
        v = float(v)
        if not math.isfinite(v):
            raise RuntimeError(f"metric {m['name']} read {v}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def peaks(kind: str) -> dict:
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise SpecError(f"chipbench: no peaks for device kind {kind!r} in "
                        f"peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][kind]


def require_accelerator(chips: int):
    """The TPU devices of this run; exits non-zero when JAX finds none or
    fewer than the cell asks for (never falls back to the CPU)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"chipbench: needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown=None,
                extra=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out.update(extra or {})
    out["checks"] = checks          # last: the numbers compared and limits
    return json.dumps(out)
