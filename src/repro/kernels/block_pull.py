"""Pallas TPU kernel: the BMO Monte-Carlo *pull* — sampled coordinate-block
distances between queries and their selected arms.

This is the paper's hot loop adapted to the TPU memory system: instead of
per-coordinate scalar gathers (CPU-friendly, TPU-hostile), each pull DMAs
one coordinate block of the arm's row from HBM into VMEM, at an address
driven by *scalar-prefetched* (query, arm, block) index operands.

One kernel serves both pull entry points (``pull_pallas``):

  * ``stats=False`` returns every pull's value (Q, B, P) — the per-round
    driver's ``block_pull_multi`` launch;
  * ``stats=True`` folds a program's T = R·P pulls into on-chip Welford
    (mean, M2) statistics (Q, B, 2) — the epoch-fused driver's
    ``fused_epoch_pull`` launch (DESIGN.md §4): a whole epoch of R rounds
    in one launch, with the next tile's DMA in flight while the current
    one reduces (``n_buf`` VMEM slots).

Layout, as the TPU compiler requires it:

  * one program per (query, arm) pair, on a 1-D grid over the flattened
    pairs. The index operands live in SMEM, which holds 1 MiB, so a large
    launch (the wide init pulls every arm of every query) is cut into
    chunks of at most ``_SMEM_WORDS`` block indices, run by ``lax.map``;
  * an HBM slice must be whole (sublane, lane) tiles — (8, 128) for fp32 —
    so each pull fetches the tile that holds the arm's block (``rows``
    consecutive rows × ``width`` lanes) and masks the reduction down to
    the arm's row and the block's lanes. At fp32 and block 128 that is a
    4 KiB DMA for 512 useful bytes. The corpus must therefore be whole
    tiles. ``build_index``'s default capacity, a power of two, is whole
    rows for n > 4; a rotated d_pad, a power of two, is whole lanes for
    d > 64; a plain d_pad, a block multiple, is whole lanes for blocks of
    128 and up. Any other store is zero-padded to whole tiles inside
    every launch — a copy of the corpus, logged when the compiled kernel
    is traced;
  * the output block is lane-dense, (n_out, chunk) with one lane per pair,
    resident across the grid; each program writes its own lane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import get_logger

log = get_logger("repro.kernels")

_LANES = 128
_SMEM_WORDS = 32768     # block indices per launch: 128 KiB of the 1 MiB SMEM
_MAX_CHUNK = 2048       # (query, arm) pairs per launch: lanes of the output


def _pull_kernel(qid_ref, arm_ref, blk_ref, x_ref, q_ref, o_ref, buf, sem, *,
                 block: int, metric: str, n_buf: int, T: int, stats: bool):
    del qid_ref  # consumed by the query BlockSpec's index map
    j = pl.program_id(0)
    rows, width = buf.shape[1], buf.shape[2]
    arm = arm_ref[j]
    row0 = pl.multiple_of((arm // rows) * rows, rows)

    def start_col(t):
        return blk_ref[j * T + t] * block

    def dma(slot, t):
        col0 = pl.multiple_of((start_col(t) // width) * width, width)
        return pltpu.make_async_copy(
            x_ref.at[pl.ds(row0, rows), pl.ds(col0, width)],
            buf.at[slot],
            sem.at[slot],
        )

    dma(0, 0).start()
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)

    def body(t, carry):
        mean, m2 = carry
        cur = jax.lax.rem(t, n_buf)

        # stream the next tile while the current one is reduced
        @pl.when(t + 1 < T)
        def _():
            dma(jax.lax.rem(t + 1, n_buf), t + 1).start()

        dma(cur, t).wait()
        col0 = pl.multiple_of((start_col(t) // width) * width, width)
        qv = q_ref[:, pl.ds(col0, width)].astype(jnp.float32)   # (1, width)
        diff = buf[cur].astype(jnp.float32) - qv                # (rows, width)
        e = jnp.abs(diff) if metric == "l1" else diff * diff
        keep = row_id == arm - row0
        if width != block:          # block narrower than a lane tile
            off = start_col(t) - col0
            keep &= (lane >= off) & (lane < off + block)
        v = jnp.sum(jnp.where(keep, e, 0.0)) / block

        if not stats:
            o_ref[pl.ds(t, 1), :] = jnp.where(
                out_lane[:1] == j, v, o_ref[pl.ds(t, 1), :])

        # running Welford over the program's T pulls
        delta = v - mean
        mean = mean + delta / (t + 1).astype(jnp.float32)
        m2 = m2 + delta * (v - mean)
        return mean, m2

    mean, m2 = jax.lax.fori_loop(0, T, body, (0.0, 0.0))
    if stats:
        out_row = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        o_ref[...] = jnp.where(out_lane == j,
                               jnp.where(out_row == 0, mean, m2), o_ref[...])


def _pull_call(x, qs, qid, arm, blk, *, block: int, metric: str, n_buf: int,
               T: int, stats: bool, interpret: bool):
    """One launch over ``chunk`` (query, arm) pairs → (n_out, chunk)."""
    d_pad = x.shape[1]
    chunk = arm.shape[0]
    n_out = 2 if stats else T
    rows = 8 * 4 // x.dtype.itemsize        # sublanes of one HBM tile
    width = max(block, _LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(chunk,),
        in_specs=[
            # corpus stays off-chip; tiles are DMA'd manually
            pl.BlockSpec(memory_space=pl.ANY),
            # the pair's query row; consecutive pairs of one query reuse it
            pl.BlockSpec((None, 1, d_pad),
                         lambda j, qid, arm, blk: (qid[j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((n_out, chunk),
                               lambda j, qid, arm, blk: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_buf, rows, width), x.dtype),
            pltpu.SemaphoreType.DMA((n_buf,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_pull_kernel, block=block, metric=metric,
                          n_buf=n_buf, T=T, stats=stats),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, chunk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_epoch_pull" if stats else "block_pull_multi",
    )(qid, arm, blk, x, qs)


def pull_pallas(x: jax.Array, qs: jax.Array, arm_idx: jax.Array,
                blk_idx: jax.Array, *, block: int, metric: str = "l2",
                stats: bool, n_buf: int = 2,
                interpret: bool = False) -> jax.Array:
    """x (n, d_pad); qs (Q, d_pad); arm_idx (Q, B); blk_idx (Q, B, T).
    Returns (Q, B, T) per-pull block-mean distances, or with ``stats``
    (Q, B, 2) per-arm Welford (mean, M2) of the T pulls."""
    n, d_pad = x.shape
    Q, B, T = blk_idx.shape
    assert d_pad % block == 0 and arm_idx.shape == (Q, B)
    assert block % _LANES == 0 or _LANES % block == 0, \
        f"block {block} must tile the {_LANES}-lane width"
    assert n_buf >= 2, f"need at least 2 streaming slots, got {n_buf}"
    assert T <= _SMEM_WORDS, f"{T} pulls per arm exceed one launch"
    rows = 8 * 4 // x.dtype.itemsize
    width = max(block, _LANES)
    pad_r, pad_c = (-n) % rows, (-d_pad) % width
    if pad_r or pad_c:              # a store that is not whole tiles
        if not interpret:
            log.warning("pull kernel pads a (%d, %d) store to whole (%d, %d) "
                        "tiles in every launch", n, d_pad, rows, width)
        x = jnp.pad(x, ((0, pad_r), (0, pad_c)))
    if pad_c:
        qs = jnp.pad(qs, ((0, 0), (0, pad_c)))
    qs = qs.reshape(Q, 1, d_pad + pad_c)

    N = Q * B
    qid = jnp.repeat(jnp.arange(Q, dtype=jnp.int32), B)
    arm = arm_idx.astype(jnp.int32).reshape(N)
    blk = blk_idx.astype(jnp.int32).reshape(N * T)   # flat: no lane padding
    chunk = min(N, _MAX_CHUNK, max(1, _SMEM_WORDS // T))
    n_chunks = -(-N // chunk)
    pad = n_chunks * chunk - N
    if pad:
        qid = jnp.pad(qid, (0, pad), mode="edge")
        arm = jnp.pad(arm, (0, pad))
        blk = jnp.pad(blk, (0, pad * T))
    call = functools.partial(_pull_call, x, qs, block=block, metric=metric,
                             n_buf=n_buf, T=T, stats=stats,
                             interpret=interpret)
    out = jax.lax.map(
        lambda c: call(*c),
        (qid.reshape(n_chunks, chunk), arm.reshape(n_chunks, chunk),
         blk.reshape(n_chunks, chunk * T)))          # (n_chunks, n_out, chunk)
    out = jnp.moveaxis(out, 1, 2).reshape(n_chunks * chunk, -1)[:N]
    return out.reshape(Q, B, -1)
