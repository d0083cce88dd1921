"""A small checkout of the benchmark for tests on the CPU: the benchmark's
files, the program's ``src`` linked in, and one cell of a tiny granite-
style model that the CPU runs in seconds."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "tiny.s64"


def make(dst: str, chips: int = 1, src: bool = True) -> str:
    """Copy the benchmark into ``dst`` and add the cell ``tiny.s64``."""
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    if src:
        os.symlink(os.path.join(ROOT, "src"), os.path.join(dst, "src"))
    here = os.path.join(dst, "chipbench")
    with open(os.path.join(here, "configs",
                           "granite-moe-3b-a800m-4l.json")) as f:
        c = json.load(f)
    c.update(name="tiny", hidden_size=64, intermediate_size=32,
             num_attention_heads=4, num_key_value_heads=2,
             num_hidden_layers=2, num_local_experts=8,
             num_experts_per_tok=2, vocab_size=500,
             attention_multiplier=0.25)
    c["deployment"].update(chips=chips, mesh=[1, chips],
                           mode="tp_sp" if chips == 1 else "ep_dp")
    c["program"]["vocab_pad"] = 16
    _dump(c, here, "configs", "tiny.json")
    with open(os.path.join(here, "traffic", "s2048.json")) as f:
        t = json.load(f)
    t["seq_len"] = 64
    _dump(t, here, "traffic", "s64.json")
    shutil.copy(os.path.join(here, "workloads", "granite-4l.s2048.json"),
                os.path.join(here, "workloads", CELL + ".json"))
    with open(os.path.join(here, "peaks.json")) as f:
        p = json.load(f)
    p["devices"]["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    _dump(p, here, "peaks.json")
    b = _bench(dst)
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": CELL, "config": "tiny", "traffic": "s64",
                           "chips": chips, "why": "test"})
    for m in b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    _dump(b, dst, "BENCHMARK.json")
    return dst


def _bench(dst):
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        return json.load(f)


def _dump(obj, *path):
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f, indent=1)


# Runs the benchmark's entry with the look for a chip replaced by the CPU's
# devices, and, where given, a fault planted in the program first.
DRIVER = r'''
import os, runpy, sys
root, plant = sys.argv[1], sys.argv[2]
sys.path.insert(0, root)
sys.path.insert(0, os.path.join(root, "src"))
import jax
jax.config.update("jax_cpu_enable_async_dispatch", False)
if plant:
    exec(plant)
mod = runpy.run_path(os.path.join(root, "chipbench", "run.py"),
                     run_name="chipbench_test")
sys.exit(mod["main"](sys.argv[3:], require=lambda n: jax.devices()[:n]))
'''


# On the CPU the XLA ops run on the host's XLA threads, not on a device's
# ops line, so a trace holds no device ops. Planted before a traced run, this
# reads each XLA thread's op events (named by their HLO instruction) as one
# device's ops line, so that the layer times come through on the CPU.
CPU_OPS = r"""
import glob, re
import chipbench.tracereduce as _T
_load = _T.load
def _cpu_load(trace_dir):
    out = _load(trace_dir)
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            lines += [sorted(((e.name, float(e.start_ns), float(e.end_ns))
                              for e in line.events
                              if re.fullmatch(r"[\w\-]+\.\d+|wrapped_\w+",
                                              e.name)), key=lambda e: e[1])
                      for line in plane.lines
                      if line.name.startswith("tf_XLA")]
    out["devices"] = dict(enumerate(ev for ev in lines if ev))
    return out
_T.load = _cpu_load
"""


def run(root: str, args: list, plant: str = "", devices: int = 1,
        timeout: float = 300) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    return subprocess.run([sys.executable, "-c", DRIVER, root, plant]
                          + list(args), cwd=root, env=env, text=True,
                          capture_output=True, timeout=timeout)


def last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])
