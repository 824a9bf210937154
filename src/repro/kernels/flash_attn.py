"""Pallas TPU kernel: fused flash attention.

The §Roofline analysis shows every LM cell memory-bound on unfused
attention intermediates (scores/probabilities round-tripping HBM in the
XLA-scan lowering of online softmax). This kernel is the fix on real
hardware: the (bq, bk) score tile, running max/normalizer and the output
accumulator all live in VMEM scratch across the (sequential) KV-block grid
dimension; HBM traffic is exactly q + k + v + out.

grid = (B, H, nq, nk), nk innermost/sequential. Scratch persists across nk:
  m (bq,)   running row max
  l (bq,)   running normalizer
  acc (bq, D) output accumulator
Causal masking handled by absolute positions (q_offset for decode).
Validated against models.common._sdpa in interpret mode (tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                  bq: int, bk: int, nk: int, causal: bool, q_offset: int,
                  sm_scale: float):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)           # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)           # (bk, Dv)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

    iq = pl.program_id(2)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        s = jnp.where(k_pos <= q_pos, s, -1e30)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    scale = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * scale + jnp.sum(p, axis=1)
    acc_s[...] = acc_s[...] * scale[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[...] /
                       jnp.maximum(l_s[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, q_offset: int = 0,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q (B, H, Sq, D); k, v (B, H, Sk, D) [GQA: repeat kv heads in the
    wrapper]. Returns (B, H, Sq, Dv)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    Dv = v.shape[-1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    grid = (B, H, nq, nk)
    sm_scale = 1.0 / np.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
        q_offset=q_offset, sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
