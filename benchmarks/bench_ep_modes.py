"""EP execution-mode comparison through the real JAX EP code (not the
simulator), on the CPU: no number here is a device time.

Lowers the paper-style MoE block through the real shard_map EP paths on an
8-device (forced-host) CPU mesh in a subprocess and reports, from the
optimized HLO: collective op mix, per-device collective bytes, and wall
time — demonstrating baseline AllToAll vs the RATR chunked-ppermute ring
produce identical numerics with different collective schedules.
"""

from __future__ import annotations

import os
import subprocess
import sys

_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.configs.deepseek_moe_paper import smoke_config
from repro.launch.mesh import make_test_mesh
from repro.models import model as M
from repro.parallel.ep import EPConfig, make_moe_ep
from repro.parallel.roofline import parse_collectives

mesh = make_test_mesh(2, 4)
cfg = smoke_config()
params = M.init_params(cfg, jax.random.PRNGKey(0))
moe_params = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model), jnp.float32)
results = {}
for mode in ("baseline", "hyperparallel"):
    impl = make_moe_ep(mesh, EPConfig(mode=mode, capacity_factor=8.0))
    with jax.set_mesh(mesh):
        compiled = jax.jit(lambda p, x: impl(p, x, cfg.moe)).lower(moe_params, x).compile()
        y = compiled(moe_params, x); jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(compiled(moe_params, x))
        us = (time.perf_counter() - t0) / 5 * 1e6
    colls = parse_collectives(compiled.as_text())
    results[mode] = np.asarray(y)
    print(f"ep_mode_{mode},{us:.2f},collectives={colls.counts}"
          f" bytes={colls.total_bytes}")
np.testing.assert_allclose(results["baseline"], results["hyperparallel"],
                           rtol=2e-4, atol=2e-4)
print("ep_modes_numerics,0.00,baseline==hyperparallel allclose ok")
"""


def run() -> None:
    # The child measures a forced-host CPU mesh. Pinning it to the CPU
    # keeps it off an accelerator that this process may already hold.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUB],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=env, capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"ep_modes child exited {out.returncode}: "
                           f"{out.stderr.strip()[-2000:]}")
    for line in out.stdout.splitlines():
        if line.startswith(("ep_mode", "ep_modes")):
            print(line)


if __name__ == "__main__":
    run()
