"""Ambient activation-sharding context — dependency-free so both the model
code and the sharding rules can import it without cycles."""

from __future__ import annotations

import contextlib
import threading

import jax

_CTX = threading.local()


@contextlib.contextmanager
def activation_sharding(spec):
    """Set the residual-stream PartitionSpec for traces under this context."""
    prev = getattr(_CTX, "spec", None)
    _CTX.spec = spec
    try:
        yield
    finally:
        _CTX.spec = prev


def constrain_activation(x):
    spec = getattr(_CTX, "spec", None)
    if spec is None:
        return x
    if x.ndim != len(spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


@contextlib.contextmanager
def head_sharding(spec):
    """PartitionSpec for [B, S, H, hd] attention tensors (TP over heads)."""
    prev = getattr(_CTX, "head_spec", None)
    _CTX.head_spec = spec
    try:
        yield
    finally:
        _CTX.head_spec = prev


def constrain_heads(x, n_heads_axis=2):
    spec = getattr(_CTX, "head_spec", None)
    if spec is None or x.ndim != len(spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


@contextlib.contextmanager
def flash_decode_context(impl):
    """Ambient sharded one-token-decode attention override."""
    prev = getattr(_CTX, "flash_decode", None)
    _CTX.flash_decode = impl
    try:
        yield
    finally:
        _CTX.flash_decode = prev


def current_flash_decode():
    return getattr(_CTX, "flash_decode", None)


@contextlib.contextmanager
def moe_impl_context(impl):
    """Ambient MoE execution override (EP path injection, same pattern)."""
    prev = getattr(_CTX, "moe_impl", None)
    _CTX.moe_impl = impl
    try:
        yield
    finally:
        _CTX.moe_impl = prev


def current_moe_impl():
    return getattr(_CTX, "moe_impl", None)


@contextlib.contextmanager
def moe_counts_scope():
    """Collect the MoE counters recorded while tracing under this context.

    Yields the list that :func:`record_moe_counts` appends to. Open it at
    the trace level of the MoE layer's caller (a scan body over the layers),
    so that the counters can leave as that body's outputs."""
    prev = getattr(_CTX, "moe_counts", None)
    got: list = []
    _CTX.moe_counts = got
    try:
        yield got
    finally:
        _CTX.moe_counts = prev


def record_moe_counts(counts: dict):
    """Hand one MoE layer's counters to the innermost open
    :func:`moe_counts_scope`; without one they are dropped."""
    sink = getattr(_CTX, "moe_counts", None)
    if sink is not None:
        sink.append(counts)
