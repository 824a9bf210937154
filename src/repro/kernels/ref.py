"""Pure-jnp oracles for every Pallas kernel (the correctness references the
kernel sweep tests assert against, and the fast XLA path off the TPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def block_pull_ref(x: jax.Array, q: jax.Array, arm_idx: jax.Array,
                   blk_idx: jax.Array, block: int, metric: str = "l2") -> jax.Array:
    """Sampled coordinate-block distances (the paper's Monte-Carlo pull,
    block form).  x (n, d_pad); q (d_pad,); arm_idx (B,); blk_idx (B, P).
    Returns (B, P) per-block mean coordinate-wise distances."""
    n, d_pad = x.shape
    nb = d_pad // block
    xb = x.reshape(n, nb, block)
    qb = q.reshape(nb, block)
    rows = xb[arm_idx[:, None], blk_idx]          # (B, P, block)
    qs = qb[blk_idx]                              # (B, P, block)
    diff = rows.astype(jnp.float32) - qs.astype(jnp.float32)
    if metric == "l1":
        v = jnp.sum(jnp.abs(diff), axis=-1)
    else:
        v = jnp.sum(diff * diff, axis=-1)
    return (v / block).astype(jnp.float32)


def block_pull_multi_ref(x: jax.Array, qs: jax.Array, arm_idx: jax.Array,
                         blk_idx: jax.Array, block: int,
                         metric: str = "l2") -> jax.Array:
    """Cross-query batched pull (the index-serving hot loop): one gather
    serves every query's arm frontier.  x (n, d_pad); qs (Q, d_pad);
    arm_idx (Q, B); blk_idx (Q, B, P).  Returns (Q, B, P)."""
    n, d_pad = x.shape
    Q = qs.shape[0]
    nb = d_pad // block
    xb = x.reshape(n, nb, block)
    qb = qs.reshape(Q, nb, block)
    rows = xb[arm_idx[:, :, None], blk_idx]              # (Q, B, P, block)
    qrows = qb[jnp.arange(Q)[:, None, None], blk_idx]    # (Q, B, P, block)
    diff = rows.astype(jnp.float32) - qrows.astype(jnp.float32)
    if metric == "l1":
        v = jnp.sum(jnp.abs(diff), axis=-1)
    else:
        v = jnp.sum(diff * diff, axis=-1)
    return (v / block).astype(jnp.float32)


def fused_epoch_pull_ref(x: jax.Array, qs: jax.Array, arm_idx: jax.Array,
                         blk_idx: jax.Array, block: int,
                         metric: str = "l2") -> jax.Array:
    """Round-fused epoch pull (kernels/block_pull.py, ``stats=True``): T = R·P block pulls
    per selected arm, reduced to per-arm Welford batch statistics.
    x (n, d_pad); qs (Q, d_pad); arm_idx (Q, B); blk_idx (Q, B, T).
    Returns (Q, B, 2) fp32: (mean, M2) of each arm's T pulled values."""
    vals = block_pull_multi_ref(x, qs, arm_idx, blk_idx, block, metric)
    mean = jnp.mean(vals, axis=-1)
    m2 = jnp.sum(jnp.square(vals - mean[..., None]), axis=-1)
    return jnp.stack([mean, m2], axis=-1)


def pairwise_dist_ref(qs: jax.Array, x: jax.Array, metric: str = "l2",
                      chunk: int = 2048) -> jax.Array:
    """Exact distances. qs (Q, d), x (n, d) -> (Q, n) SUM-form distances
    (ℓ2² or ℓ1), accumulated in fp32 over d-chunks."""
    Q, d = qs.shape
    n = x.shape[0]
    out = jnp.zeros((Q, n), jnp.float32)
    for start in range(0, d, chunk):
        qc = qs[:, start:start + chunk].astype(jnp.float32)
        xc = x[:, start:start + chunk].astype(jnp.float32)
        if metric == "l1":
            out = out + jnp.sum(jnp.abs(qc[:, None, :] - xc[None, :, :]), axis=-1)
        else:
            # MXU-form: ‖q‖² + ‖x‖² − 2 q·x
            out = out + (jnp.sum(qc * qc, -1)[:, None] + jnp.sum(xc * xc, -1)[None, :]
                         - 2.0 * qc @ xc.T)
    return out
