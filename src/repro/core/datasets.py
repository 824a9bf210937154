"""Dataset containers for BMO-NN: dense (blocked layout) and sparse
(padded-CSR) corpora, plus the §IV-B randomized-Hadamard rotation."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def next_pow2(x: int) -> int:
    return 1 << (x - 1).bit_length()


@dataclasses.dataclass
class DenseDataset:
    """Corpus (n, d), padded so d is a multiple of the sampling block."""

    x: jax.Array               # (n, d_pad) float32
    d: int                     # true dimension (exact-eval cost)
    block: int                 # sampling block width

    @property
    def n(self) -> int:
        return self.x.shape[0]

    @property
    def d_pad(self) -> int:
        return self.x.shape[1]

    @property
    def n_blocks(self) -> int:
        return self.d_pad // self.block

    @classmethod
    def build(cls, x, block: int = 128) -> "DenseDataset":
        x = jnp.asarray(x, jnp.float32)
        n, d = x.shape
        pad = (-d) % block
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        return cls(x=x, d=d, block=block)

    def pad_query(self, q) -> jax.Array:
        q = jnp.asarray(q, jnp.float32)
        pad = self.d_pad - q.shape[-1]
        if pad:
            q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])
        return q


@dataclasses.dataclass
class SparseDataset:
    """Padded-CSR corpus for the §IV-A sparse Monte-Carlo box (ℓ1).

    ``indices`` rows are sorted, padded with d (a sentinel larger than any
    real coordinate); ``values`` padded with 0. Membership tests and value
    lookups are binary searches — the TPU-friendly analogue of the paper's
    O(1) hash-map (same estimator distribution, see DESIGN.md)."""

    indices: jax.Array         # (n, m) int32, sorted, pad = d
    values: jax.Array          # (n, m) float32, pad = 0
    nnz: jax.Array             # (n,) int32
    d: int

    @property
    def n(self) -> int:
        return self.indices.shape[0]

    @property
    def m(self) -> int:
        return self.indices.shape[1]

    @classmethod
    def build(cls, dense_or_coo, d: Optional[int] = None) -> "SparseDataset":
        """From a dense (n, d) numpy array (zeros dropped)."""
        x = np.asarray(dense_or_coo)
        n, d_ = x.shape
        d = d or d_
        nnz = (x != 0).sum(axis=1)
        m = int(max(nnz.max(), 1))
        indices = np.full((n, m), d, np.int32)
        values = np.zeros((n, m), np.float32)
        for i in range(n):
            idx = np.nonzero(x[i])[0]
            indices[i, : len(idx)] = idx
            values[i, : len(idx)] = x[i, idx]
        return cls(indices=jnp.asarray(indices), values=jnp.asarray(values),
                   nnz=jnp.asarray(nnz, jnp.int32), d=d)


def hadamard_rotate(x: jax.Array, rng: jax.Array):
    """§IV-B: x' = H D x per row (D = random ±1 diag, H = normalized FWHT).
    Pads d to the next power of two (paper: 'zero padding'). Preserves
    pairwise ℓ2 distances up to the common padding. Returns (x', signs)."""
    from repro.kernels import ops as kops
    n, d = x.shape
    dp = next_pow2(d)
    if dp != d:
        x = jnp.pad(x, ((0, 0), (0, dp - d)))
    signs = jax.random.rademacher(rng, (dp,), jnp.float32)
    return kops.fwht(x * signs[None, :]), signs
