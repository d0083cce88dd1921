"""MoE layer tests + multi-device EP equivalence (subprocess: the EP test
needs forced host devices, which must not leak into this process)."""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe import (MoEConfig, capacity, expert_ffn, init_moe,
                              make_dispatch, moe_dense_ref, moe_grouped,
                              router_topk, slot_map)
from repro.parallel.ctx import moe_counts_scope
from repro.parallel.ep import EPConfig, _pair_capacity, make_moe_ep

KEY = jax.random.PRNGKey(0)
MC = MoEConfig(n_experts=6, top_k=2, d_expert=16, capacity_factor=8.0,
               n_padding_experts=2)


def test_router_masks_padding_and_normalizes():
    params = init_moe(KEY, 32, MC)
    x = jax.random.normal(KEY, (64, 32))
    p, i = router_topk(params["router"], x, MC)
    assert int(i.max()) < MC.n_experts          # padding never selected
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, rtol=1e-5)


def test_grouped_equals_dense_ref():
    params = init_moe(KEY, 32, MC)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    a = moe_dense_ref(params, x, MC, cap=512)
    b = moe_grouped(params, x, MC, cap=512)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_capacity_drop_consistency():
    """With a tiny capacity, both paths drop the same tokens."""
    params = init_moe(KEY, 32, MC)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 32))
    a = moe_dense_ref(params, x, MC, cap=4)
    b = moe_grouped(params, x, MC, cap=4)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_slots_unique_per_expert():
    p = jnp.ones((16, 2)) / 2
    i = jnp.stack([jnp.arange(16) % 4, (jnp.arange(16) + 1) % 4], 1)
    w, ii, slot = make_dispatch(p, i, 4, 100)
    pairs = set()
    for t in range(16):
        for k in range(2):
            key = (int(ii[t, k]), int(slot[t, k]))
            assert key not in pairs, "slot collision"
            pairs.add(key)


def _slot_loop(top_i: np.ndarray, C: int) -> np.ndarray:
    """src [E, C] by a loop over make_dispatch's slots: the flat choice
    t * k + j in each kept slot, T * k in empty ones."""
    T, k = top_i.shape
    E = int(top_i.max()) + 2
    _, _, slot = make_dispatch(jnp.ones(top_i.shape), jnp.asarray(top_i),
                               E, C)
    slot = np.asarray(slot)
    src = np.full((E, C), T * k)
    for t in range(T):
        for j in range(k):
            if slot[t, j] < C:
                src[top_i[t, j], slot[t, j]] = t * k + j
    return src


@pytest.mark.parametrize("C", [1, 3, 8])
def test_slot_map_inverts_dispatch_slots(C):
    """The sorted inverse map equals a loop over the cumsum slots, with
    experts over capacity, an expert never chosen (the last) and drops;
    dest points back at each kept choice."""
    rng = np.random.default_rng(C)
    top_i = rng.choice(6, size=(24, 3), p=[.4, .25, .15, .1, .06, .04])
    top_i = np.stack([rng.permutation(6)[:3] if t % 5 == 0 else top_i[t]
                      for t in range(24)])
    E = int(top_i.max()) + 2
    sm = slot_map(jnp.asarray(top_i), E, C)
    want = _slot_loop(top_i, C)
    np.testing.assert_array_equal(np.asarray(sm.src), want)
    counts = np.bincount(top_i.reshape(-1), minlength=E)
    assert counts[-1] == 0 and (counts > C).any()
    keep = np.asarray(sm.keep).reshape(-1)
    dest = np.asarray(sm.dest).reshape(-1)
    np.testing.assert_array_equal(want.reshape(-1)[dest[keep]],
                                  np.flatnonzero(keep))
    assert (np.asarray(sm.slot).reshape(-1)[~keep] == C).all()


MC_DROP = MoEConfig(n_experts=8, top_k=2, d_expert=16)


def _scatter_reference(params, x, mc, C):
    """Dispatch by a scatter-add into an [E, C + 1, d] buffer whose slot C
    takes the dropped choices, and combine by gathering from it (the
    gradient then scatter-adds both ways). Returns (y, counters)."""
    B, S, d = x.shape
    T, k, E = B * S, mc.top_k, mc.e_total
    xt = x.reshape(T, d)
    top_p, top_i = router_topk(params["router"], xt, mc)
    flat_e = top_i.reshape(-1)
    pos = jnp.cumsum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32), 0) - 1
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = slot < C
    slot = jnp.where(keep, slot, C).reshape(T, k)
    top_p = top_p * keep.reshape(T, k)
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k)).reshape(-1)
    disp = jnp.zeros((E, C + 1, d), x.dtype).at[
        flat_e, slot.reshape(-1)].add(xt[tok])[:, :C]
    out = expert_ffn(params["w_in"], params["w_down"], disp)
    out = jnp.concatenate([out, jnp.zeros_like(out[:, :1])], axis=1)
    y = jnp.einsum("tkd,tk->td", out[top_i, slot], top_p.astype(x.dtype))
    counts = {"moe_routed": T * k, "moe_dropped": jnp.sum(~keep),
              "moe_slots": E * C}
    return y.reshape(B, S, d), counts


def _moe_path(path, mc, T, capacity_factor):
    """(impl(params, x), C) for ``moe_grouped`` or an EP mode on a
    one-device mesh, at the EP path's capacity for T tokens."""
    C = _pair_capacity(T, mc, 1, capacity_factor)
    if path == "grouped":
        return (lambda p, x: moe_grouped(p, x, mc, cap=C)), C
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ep_impl = make_moe_ep(mesh, EPConfig(path,
                                         capacity_factor=capacity_factor))
    return (lambda p, x: ep_impl(p, x, mc)), C


MOE_PATHS = ["grouped", "baseline", "hyperparallel"]


@pytest.mark.parametrize("path", MOE_PATHS)
def test_gather_dispatch_matches_scatter_reference_with_drops(path):
    """Capacity factor 0.5 drops choices; the gather-only dispatch and
    combine give the scatter formulation's output bit for bit, its
    gradients within f32 rounding, and the same counters."""
    mc = MC_DROP
    params = init_moe(KEY, 32, mc)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 32), jnp.float32)
    impl, C = _moe_path(path, mc, x.shape[0] * x.shape[1], 0.5)

    @jax.jit
    def run(p, x):
        with moe_counts_scope() as got:
            y = impl(p, x)
        (counts,) = got
        return y, {k: jnp.sum(v) for k, v in counts.items()}

    y, counts = run(params, x)
    want_y, want_counts = jax.jit(
        lambda p, x: _scatter_reference(p, x, mc, C))(params, x)
    assert int(counts["moe_dropped"]) > 0
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
    for k in want_counts:
        assert int(counts[k]) == int(want_counts[k]), k

    def loss(f):
        return lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    g = jax.jit(jax.grad(loss(impl), argnums=(0, 1)))(params, x)
    want_g = jax.jit(jax.grad(loss(
        lambda p, x: _scatter_reference(p, x, mc, C)[0]),
        argnums=(0, 1)))(params, x)
    for name in ("router", "w_in", "w_down"):
        np.testing.assert_allclose(np.asarray(g[0][name]),
                                   np.asarray(want_g[0][name]),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(want_g[1]),
                               rtol=1e-5, atol=1e-5, err_msg="x")


def _under(op_name: str, scope: str) -> bool:
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     op_name) is not None


@pytest.mark.parametrize("path", MOE_PATHS)
def test_moe_gradient_has_no_row_scatter(path):
    """Rows enter and leave the capacity buffer by gathers alone: the
    compiled gradient has no scatter under ``moe/dispatch`` or
    ``moe/combine`` (the router's top-k transpose may scatter)."""
    mc = MC_DROP
    params = init_moe(KEY, 32, mc, dtype=jnp.bfloat16)
    x = jax.random.normal(KEY, (2, 64, 32), jnp.bfloat16)
    impl, _ = _moe_path(path, mc, 128, 1.0)
    grad = jax.jit(jax.grad(
        lambda p, x: jnp.sum(impl(p, x).astype(jnp.float32) ** 2),
        argnums=(0, 1)))
    hlo = grad.lower(params, x).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("moe/dispatch", "moe/combine"):
        assert any(_under(n, scope) and "transpose(" in n
                   for n in op_names), scope
    scatters = [line for line in hlo.splitlines()
                if re.search(r"= \S+ scatter\(", line)]
    for line in scatters:
        name = re.search(r'op_name="([^"]*)"', line)
        assert name, line
        assert not any(_under(name.group(1), s)
                       for s in ("moe/dispatch", "moe/combine")), line


def _counted(impl):
    """jit of ``impl(params, x)`` returning (y, the layer's counters
    summed over devices, router load left out)."""
    def run(p, x):
        with moe_counts_scope() as got:
            y = impl(p, x)
        (counts,) = got
        return y, {k: jnp.sum(v) for k, v in counts.items()
                   if k != "moe_load"}
    return jax.jit(run)


def _one_chip_ep(mc, capacity_factor, **kw):
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    impl = make_moe_ep(mesh, EPConfig("hyperparallel",
                                      capacity_factor=capacity_factor, **kw))
    return lambda p, x: impl(p, x, mc)


COMPACT_CASES = {
    "softmax": MoEConfig(n_experts=8, top_k=2, d_expert=16),
    "softmax_held": MoEConfig(n_experts=8, top_k=2, d_expert=16, n_held=3,
                              held_first=2),
    "sigmoid": MoEConfig(n_experts=8, top_k=2, d_expert=16, score="sigmoid",
                         routed_scale=2.5),
    "sigmoid_held": MoEConfig(n_experts=8, top_k=2, d_expert=16,
                              score="sigmoid", routed_scale=2.5, n_held=3,
                              held_first=1),
}


def _interpret_tpu_kernel(monkeypatch):
    """Have ``kernels.ragged`` take the TPU's kernel (megablox) and count
    its row tiles, with the kernel run by Pallas's interpreter, so the
    TPU's path runs on the CPU."""
    from jax.experimental.pallas.ops.tpu.megablox import ops
    from repro.kernels import ragged

    gmm = ops.gmm
    monkeypatch.setattr(
        ops, "gmm", lambda *a, **kw: gmm(*a, **kw, interpret=True))
    monkeypatch.setattr(ragged, "on_tpu", lambda: True)


@pytest.mark.parametrize("kernel", ["ragged_dot", "megablox"])
@pytest.mark.parametrize("case", sorted(COMPACT_CASES))
@pytest.mark.parametrize("capacity_factor", [1.0, 4.0],
                         ids=["drops", "drops_none"])
def test_compact_path_matches_capacity_path(case, capacity_factor, kernel,
                                            monkeypatch):
    """On one chip the EP path packs the kept rows end to end and runs a
    grouped GEMM over them, by either kernel; it gives the capacity
    buffer's output, gradients and counters (``moe_grouped`` at the same
    capacity), with and without drops, a held share and the sigmoid
    router."""
    if kernel == "megablox":
        _interpret_tpu_kernel(monkeypatch)
    mc = COMPACT_CASES[case]
    params = init_moe(KEY, 32, mc)
    if mc.score == "sigmoid":
        params["router_bias"] = jax.random.normal(KEY, (8,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 32), jnp.float32)
    C = _pair_capacity(128, mc, 1, capacity_factor)
    compact = _one_chip_ep(mc, capacity_factor)

    def capacity_path(p, x):
        return moe_grouped(p, x, mc, cap=C)

    y, counts = _counted(compact)(params, x)
    want_y, want_counts = _counted(capacity_path)(params, x)
    assert (int(counts["moe_dropped"]) > 0) == (capacity_factor == 1.0)
    for k in ("moe_routed", "moe_dropped", "moe_slots", "moe_not_held"):
        if k in want_counts:
            assert int(counts[k]) == int(want_counts[k]), k
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)

    def grads(f):
        return jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(f(p, x))),
                                argnums=(0, 1)))(params, x)
    g, want_g = grads(compact), grads(capacity_path)
    for name in ("router", "w_in", "w_down"):
        np.testing.assert_allclose(np.asarray(g[0][name]),
                                   np.asarray(want_g[0][name]),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(want_g[1]),
                               rtol=1e-5, atol=1e-5, err_msg="x")


def _poison_tail(matmul):
    """``matmul`` with NaN in the rows past the last group of its output
    and of its gradient by ``lhs``: rows a kernel may leave undefined."""
    def nan_tail(a, group_sizes):
        tail = jnp.arange(a.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(tail[:, None], jnp.nan, a)

    @jax.custom_vjp
    def poisoned(lhs, rhs, group_sizes):
        return nan_tail(matmul(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        out, vjp = jax.vjp(lambda a, b: matmul(a, b, group_sizes), lhs, rhs)
        return nan_tail(out, group_sizes), (vjp, group_sizes)

    def bwd(res, g):
        vjp, group_sizes = res
        dlhs, drhs = vjp(g)
        return nan_tail(dlhs, group_sizes), drhs, None

    poisoned.defvjp(fwd, bwd)
    return poisoned


@pytest.mark.parametrize("kernel", ["ragged_dot", "megablox"])
@pytest.mark.parametrize("capacity_factor", [1.0, 4.0],
                         ids=["drops", "drops_none"])
def test_compact_path_selects_away_the_rows_past_the_groups(
        monkeypatch, capacity_factor, kernel):
    """The grouped GEMM may leave the rows past the last group undefined,
    forward and in its gradient by ``lhs``: with a kernel that writes NaN
    there, the layer's output and its gradients by ``x``, ``w_in``,
    ``w_down`` and the router stay finite and equal to the clean run's."""
    from repro.models import moe

    if kernel == "megablox":
        _interpret_tpu_kernel(monkeypatch)
    mc = COMPACT_CASES["softmax_held"]
    params = init_moe(KEY, 32, mc)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 32), jnp.float32)
    impl = _one_chip_ep(mc, capacity_factor)

    def run():
        y = jax.jit(impl)(params, x)
        g = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(impl(p, x))),
                             argnums=(0, 1)))(params, x)
        return y, g

    clean = run()
    monkeypatch.setattr(moe, "grouped_matmul",
                        _poison_tail(moe.grouped_matmul))
    dirty = run()
    leaves = jax.tree.leaves(dirty)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in leaves)
    for a, b in zip(jax.tree.leaves(clean), leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tpu_grouped_matmul_matches_ragged_dot(monkeypatch):
    """The TPU's grouped GEMM (megablox, in interpret mode here) with the
    tiling ``kernels.ragged`` gives it, against ``jax.lax.ragged_dot``:
    groups that end short of the buffer, share row tiles and include an
    empty one; forward and both gradients over the groups' rows, and a
    zero weight gradient for the empty group."""
    from repro.kernels import ragged

    _interpret_tpu_kernel(monkeypatch)
    m, k, n = 1024, 128, 256
    sizes = jnp.asarray([300, 0, 211, 17, 100], jnp.int32)
    rows = int(sizes.sum())
    kx, kw, kg = jax.random.split(KEY, 3)
    lhs = jax.random.normal(kx, (m, k), jnp.float32)
    rhs = jax.random.normal(kw, (5, k, n), jnp.float32)
    dy = jax.random.normal(kg, (m, n), jnp.float32).at[rows:].set(0.0)

    def run(f):
        y, vjp = jax.vjp(lambda a, b: f(a, b, sizes), lhs, rhs)
        return (y[:rows],) + vjp(dy)

    y, dlhs, drhs = run(ragged.tpu_grouped_matmul)
    want_y, want_dlhs, want_drhs = run(
        lambda a, b, s: jax.lax.ragged_dot(a, b, s))
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(dlhs[:rows], want_dlhs[:rows], rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(drhs, want_drhs, rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(drhs[1]))


def _tile_rows(group_sizes: np.ndarray, tm: int) -> int:
    """Rows of the row tiles each nonempty group touches, by a loop."""
    rows, start = 0, 0
    for n in group_sizes:
        if n:
            rows += (-(-(start + n) // tm) - start // tm) * tm
        start += n
    return rows


@pytest.mark.parametrize("path", ["compact", "compact_megablox", "grouped",
                                  "pallas"])
def test_rows_issued_counts_what_the_expert_gemm_computes(path,
                                                          monkeypatch):
    """``moe_rows_issued``: on the compact path the rows its kernel
    computes, the kept rows by ``ragged_dot`` and by the TPU's kernel those
    rounded out to its row tiles, group by group (at most the capacity
    rows); on the capacity paths the capacity rows."""
    from repro.kernels.ragged import buffer_rows, row_tile

    mc = MoEConfig(n_experts=8, top_k=2, d_expert=16)
    params = init_moe(KEY, 32, mc)
    # Enough rows a group that the kernel's row tiles stay below capacity.
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 1024, 32), jnp.float32)
    T, cf = 2048, 4.0
    C = _pair_capacity(T, mc, 1, cf)
    if path == "compact_megablox":
        _interpret_tpu_kernel(monkeypatch)
    if path == "grouped":
        impl = lambda p, x: moe_grouped(p, x, mc, cap=C)  # noqa: E731
    else:
        impl = _one_chip_ep(mc, cf, use_pallas=path == "pallas")
    _, counts = _counted(impl)(params, x)
    issued, slots = int(counts["moe_rows_issued"]), int(counts["moe_slots"])
    assert slots == mc.e_total * C
    if not path.startswith("compact"):
        assert issued == slots
        return
    _, top_i = router_topk(params["router"], x.reshape(T, -1), mc)
    kept = np.minimum(np.bincount(np.asarray(top_i).reshape(-1),
                                  minlength=mc.e_total), C)
    if path == "compact":
        assert issued == kept.sum()
        return
    R = buffer_rows(T * mc.top_k)
    assert issued == _tile_rows(kept, row_tile(R))
    assert kept.sum() < issued <= slots


def test_capacity_rounding():
    mc = MoEConfig(n_experts=8, top_k=2, d_expert=8, capacity_factor=1.0)
    assert capacity(100, mc, ep=4) % 4 == 0


_EP_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.parallel.ep import EPConfig, make_moe_ep
from repro.models.moe import MoEConfig, init_moe, moe_dense_ref

mesh = make_test_mesh(data=2, model=4)
mc = MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=8.0)
params = init_moe(jax.random.PRNGKey(0), 32, mc)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)
ref = moe_dense_ref(params, x, mc, cap=1000)
for mode in ("baseline", "hyperparallel"):
    impl = make_moe_ep(mesh, EPConfig(mode=mode, capacity_factor=16.0))
    with jax.set_mesh(mesh):
        y = jax.jit(lambda p, x: impl(p, x, mc))(params, x)
        g = jax.jit(jax.grad(lambda p, x: jnp.sum(impl(p, x, mc)**2)))(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4, atol=1e-4)
    gr = jax.grad(lambda p, x: jnp.sum(moe_dense_ref(p, x, mc, cap=1000)**2))(params, x)
    for k in g:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gr[k]),
                                   rtol=1e-3, atol=1e-3)
print("EP_SUBPROCESS_OK")

# --- Pallas fused kernels inside the EP shard (production TPU path) ------
impl_pl = make_moe_ep(mesh, EPConfig(mode="hyperparallel",
                                     capacity_factor=16.0, use_pallas=True))
with jax.set_mesh(mesh):
    y_pl = jax.jit(lambda p, x: impl_pl(p, x, mc))(params, x)
np.testing.assert_allclose(np.asarray(y_pl), np.asarray(ref),
                           rtol=1e-4, atol=1e-4)
print("PALLAS_EP_OK")

# --- flash-decoding equivalence on a seq-sharded cache -------------------
from repro.parallel.flash_decode import make_flash_decode
B, S, H, K, hd = 4, 32, 4, 2, 16
ks = jax.random.split(jax.random.PRNGKey(7), 5)
q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
kc = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
vc = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
nk = jax.random.normal(ks[3], (B, 1, K, hd), jnp.float32)
nv = jax.random.normal(ks[4], (B, 1, K, hd), jnp.float32)
clen = 17
from repro.models.layers import decode_attention
kc_ref = kc.at[:, clen].set(nk[:, 0])
vc_ref = vc.at[:, clen].set(nv[:, 0])
want = decode_attention(q, kc_ref, vc_ref, jnp.int32(clen + 1))
fd = make_flash_decode(mesh, "model")
with jax.set_mesh(mesh):
    o, kc2, vc2 = jax.jit(lambda *a: fd(*a))(q, kc, vc, nk, nv, clen)
np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(kc2), np.asarray(kc_ref), rtol=1e-6, atol=1e-6)
print("FLASH_DECODE_OK")
"""


def test_ep_modes_multidevice_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _EP_SUBPROCESS],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=env, capture_output=True, text=True, timeout=600)
    assert "EP_SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]
    assert "PALLAS_EP_OK" in out.stdout, out.stderr[-2000:]
    assert "FLASH_DECODE_OK" in out.stdout, out.stderr[-2000:]


def test_load_balance_loss_minimized_at_uniform():
    from repro.models.moe import load_balance_loss
    d, E = 16, 8
    mc2 = MoEConfig(n_experts=E, top_k=2, d_expert=8)
    x = jax.random.normal(KEY, (512, d))
    # collapsed router (all tokens to expert 0) vs near-uniform router
    r_collapsed = jnp.zeros((d, E)).at[:, 0].set(5.0)
    r_uniform = jnp.zeros((d, E))
    aux_c, z_c = load_balance_loss(r_collapsed, x, mc2)
    aux_u, z_u = load_balance_loss(r_uniform, x, mc2)
    assert float(aux_c) > float(aux_u)
    assert abs(float(aux_u) - 1.0) < 0.2      # ≈1 at uniform
    assert float(z_c) > float(z_u) >= 0.0


def test_load_balance_loss_masks_padding():
    from repro.models.moe import load_balance_loss
    mc2 = MoEConfig(n_experts=6, top_k=2, d_expert=8, n_padding_experts=2)
    x = jax.random.normal(KEY, (128, 16))
    r = jax.random.normal(jax.random.PRNGKey(3), (16, mc2.e_total))
    aux, _ = load_balance_loss(r, x, mc2)
    assert np.isfinite(float(aux))
