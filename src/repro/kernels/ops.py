"""Jit'd dispatch wrappers around the Pallas kernels.

``impl`` (``IMPLS``):
  * "auto"      — Pallas-compiled on TPU, jnp reference elsewhere (XLA-fused;
                  the interpreter would be orders of magnitude slower),
  * "kernel"    — Pallas compiled (real TPU lowering),
  * "interpret" — Pallas interpret mode (CPU-executable kernel body; what the
                  kernel sweep tests use against the refs),
  * "ref"       — pure-jnp oracle.

The Hadamard rotation (``fwht``) has one XLA implementation on every
backend and takes no ``impl``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import IMPLS
from repro.kernels import ref as kref
from repro.kernels.block_pull import pull_pallas
from repro.kernels.pairwise_dist import pairwise_dist_pallas


def _resolve(impl: str) -> str:
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r} (want one of {IMPLS})")
    if impl != "auto":
        return impl
    return "kernel" if jax.default_backend() == "tpu" else "ref"


@jax.jit
def fwht(x: jax.Array) -> jax.Array:
    """Normalized fast Walsh–Hadamard transform along the last axis, the
    §IV-B rotation. x (..., d), d a power of two. Decimation-in-frequency
    butterfly, accumulated in fp32."""
    d = x.shape[-1]
    assert d & (d - 1) == 0, f"d={d} not a power of two"
    orig_shape = x.shape
    orig_dtype = x.dtype
    y = x.astype(jnp.float32).reshape(-1, d)
    r = y.shape[0]
    blocks = 1
    while blocks < d:
        y = y.reshape(r, blocks, 2, d // (2 * blocks))
        a = y[:, :, 0, :]
        b = y[:, :, 1, :]
        y = jnp.concatenate([a + b, a - b], axis=-1)
        blocks *= 2
    return (y.reshape(orig_shape) / np.sqrt(d)).astype(orig_dtype)


@functools.partial(jax.jit, static_argnames=("block", "metric", "impl"))
def block_pull(x, q, arm_idx, blk_idx, *, block: int, metric: str = "l2",
               impl: str = "auto"):
    """Single-query pull: arm_idx (B,), blk_idx (B, P) → (B, P)."""
    impl = _resolve(impl)
    if impl == "ref":
        return kref.block_pull_ref(x, q, arm_idx, blk_idx, block, metric)
    return pull_pallas(x, q[None], arm_idx[None], blk_idx[None], block=block,
                       metric=metric, stats=False,
                       interpret=(impl == "interpret"))[0]


@functools.partial(jax.jit, static_argnames=("block", "metric", "impl"))
def block_pull_multi(x, qs, arm_idx, blk_idx, *, block: int, metric: str = "l2",
                     impl: str = "auto"):
    """Cross-query batched pull: arm_idx (Q, B), blk_idx (Q, B, P) → (Q, B, P)."""
    impl = _resolve(impl)
    if impl == "ref":
        return kref.block_pull_multi_ref(x, qs, arm_idx, blk_idx, block, metric)
    return pull_pallas(x, qs, arm_idx, blk_idx, block=block, metric=metric,
                       stats=False, interpret=(impl == "interpret"))


@functools.partial(jax.jit,
                   static_argnames=("block", "metric", "impl", "n_buf"))
def fused_epoch_pull(x, qs, arm_idx, blk_idx, *, block: int,
                     metric: str = "l2", impl: str = "auto",
                     n_buf: int = 2):
    """Round-fused epoch pull: arm_idx (Q, B), blk_idx (Q, B, R·P) →
    (Q, B, 2) per-arm (mean, M2) Welford batch statistics. ``n_buf`` is
    the Pallas kernel's VMEM streaming depth (``BMOConfig.kernel_buffers``,
    a ``repro.tune`` knob on real hardware; the jnp reference ignores it)."""
    impl = _resolve(impl)
    if impl == "ref":
        return kref.fused_epoch_pull_ref(x, qs, arm_idx, blk_idx, block, metric)
    return pull_pallas(x, qs, arm_idx, blk_idx, block=block, metric=metric,
                       stats=True, n_buf=n_buf,
                       interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("metric", "impl"))
def pairwise_dist(qs, x, *, metric: str = "l2", impl: str = "auto"):
    impl = _resolve(impl)
    if impl == "ref":
        return kref.pairwise_dist_ref(qs, x, metric)
    m = metric
    if impl == "kernel" and metric == "l2":
        m = "l2_dot"  # MXU form on real hardware
    return pairwise_dist_pallas(qs, x, metric=m, interpret=(impl == "interpret"))
