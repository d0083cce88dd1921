"""Compile checks for one TPU v5e chip, made without the chip.

The TPU compiler compiles for a described (not attached) v5e topology, so
these tests catch what interpret mode cannot: blocks not aligned to the
(8, 128) tile, kernels that need more VMEM than a kernel may use, and a
train step that does not fit the chip's memory. Nothing runs; no number
here is a device time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import contextlib
import faulthandler
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.deepseek_moe_paper import config as deepseek_config
from repro.kernels.gmm import gmm
from repro.kernels.gmm_swiglu import gmm_swiglu
from repro.kernels.gmm_swiglu_bwd import gmm_swiglu_bwd
from repro.parallel.ep import _pair_capacity

V5E_HBM_BYTES = 16 * 2**30
# The one-chip smoke trains 2 x 2048 tokens per step with the trainer's
# capacity factor.
SMOKE_TOKENS, CAPACITY_FACTOR = 2 * 2048, 4.0


def _granite_shape():
    """(E, C, K, F) of granite's expert GEMMs on one chip (C = 2736)."""
    mc = get_config("granite-moe-3b-a800m").moe
    C = _pair_capacity(SMOKE_TOKENS, mc, 1, CAPACITY_FACTOR)
    return mc.e_total, C, 1536, mc.d_expert


def _deepseek_shape():
    """(E, C, K, F) of one rank of the paper's ep=8 DeepSeek module: its 8
    local experts, each receiving ring chunks of C rows."""
    cfg = deepseek_config(ep=8)
    mc = cfg.moe
    C = _pair_capacity(SMOKE_TOKENS, mc, 8, CAPACITY_FACTOR)
    return mc.e_total // 8, C, cfg.d_model, mc.d_expert


@contextlib.contextmanager
def _time_limit(seconds: int):
    """Kill the process (with a traceback) if the body outlives ``seconds``:
    a compile that hangs in the compiler never returns to Python."""
    faulthandler.dump_traceback_later(seconds, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep these out of it.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _kernel_args(kernel, shape, sharding):
    E, C, K, F = shape

    def arr(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=sharding)

    if kernel == "gmm":          # the down projection, [E,C,F] x [E,F,K]
        return gmm, (arr(E, C, F), arr(E, F, K))
    if kernel == "gmm_swiglu":
        return gmm_swiglu, (arr(E, C, K), arr(E, K, 2 * F))
    return gmm_swiglu_bwd, (arr(E, C, K), arr(E, K, 2 * F), arr(E, C, F))


@pytest.mark.parametrize("kernel", ["gmm", "gmm_swiglu", "gmm_swiglu_bwd"])
@pytest.mark.parametrize("shape_fn", [_granite_shape, _deepseek_shape],
                         ids=["granite", "deepseek"])
# "highest" is how a float32 reference comparison calls the bf16 kernels.
@pytest.mark.parametrize("precision", ["default", "highest"])
def test_expert_kernel_compiles_for_v5e(kernel, shape_fn, precision,
                                        one_chip):
    fn, args = _kernel_args(kernel, shape_fn(), one_chip)
    with _time_limit(120), jax.default_matmul_precision(precision):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_train_step_compiles_for_one_v5e(one_chip):
    """One layer of granite-moe-3b-a800m at full width, trained on the
    smoke's batch by the trainer's own step on one described chip."""
    from repro.launch.train import build_training
    from repro.optim import adamw
    from repro.parallel.ep import EPConfig

    cfg = get_config("granite-moe-3b-a800m", n_layers=1)
    mesh = jax.sharding.Mesh(
        np.array(list(one_chip.device_set)).reshape(1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    _, step, _, state_shape, batch_shapes = build_training(
        cfg, mesh, oc=adamw.OptConfig(total_steps=5),
        ep=EPConfig(capacity_factor=CAPACITY_FACTOR), mode="tp_sp",
        dropless=None, global_batch=2, seq=SMOKE_TOKENS // 2)
    with _time_limit(300), jax.set_mesh(mesh):
        compiled = step.lower(*state_shape, batch_shapes).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES
