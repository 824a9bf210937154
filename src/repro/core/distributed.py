"""Distributed BMO-NN on the production mesh — now a thin wrapper over the
``repro.index.sharded`` subsystem (DESIGN.md §5), which owns the shard-local
racing + certified all-gather top-k merge this module pioneered.

Sharding: arms (corpus rows) over the data axis — each data row of the mesh
races its own n/D arms via the cross-query batched driver
(``index.sharded.local_dense_race``); coordinates over the model axis —
every pull samples one block per model shard (stratified) and ``pmean``s the
partial block-means, so a single pull costs block×M coordinate reads spread
across the TP group. Queries are replicated across data shards and
coordinate-sharded.

Final merge: every shard's certified local top-k is exact-evaluated (see
sharded.py on why the merge needs exact values), ``all_gather``ed over the
data axis and reduced to the global top-k. Collectives per round: one
(Q, B, P) fp32 pmean over "model"; at the end one (D, Q, 2k) gather over
"data" — the collective pattern the roofline analysis studies.

This path stays a single jittable program (launch/dryrun.py lowers it for
roofline cells); the *persistent* sharded index in ``index/sharded.py`` is
the stateful sibling with the host-side epoch loop.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import BMOConfig
from repro.index.sharded import (flat_axis_index, guard_local_topk,
                                 local_dense_race, merge_local_topk,
                                 model_exact_theta)


class DistKNNResult(NamedTuple):
    indices: jax.Array    # (Q, k) global corpus indices
    values: jax.Array     # (Q, k)
    coord_ops: jax.Array  # () total coordinate-wise computations
    rounds: jax.Array     # () max rounds across shards


def _local_knn(x_loc, qs_loc, rng, *, cfg: BMOConfig, d: int, n_loc: int,
               dp_axes, impl: str):
    """Body run per device under shard_map: the shard-local batched race of
    the index subsystem, with pulls additionally stratified over "model"."""
    Q = qs_loc.shape[0]
    shard = flat_axis_index(dp_axes)
    rng = jax.random.fold_in(rng, shard)
    alive = jnp.ones((n_loc,), bool)
    prior = jnp.zeros((n_loc,), jnp.float32)
    res = local_dense_race(x_loc, qs_loc, alive, prior, rng, cfg=cfg,
                           block=cfg.block, d=d, impl=impl, eliminate=True,
                           prior_weight=0.0, model_axis="model")
    # exact-evaluate the certified local top-k so the merge compares exact
    # θ values (per coordinate slice → pmean over "model"), then gather +
    # reduce
    theta = model_exact_theta(x_loc, qs_loc, res.indices, cfg.metric, "model")
    vals = guard_local_topk(res.indices, theta, alive)
    topk_g = res.indices.astype(jnp.int32) + shard * n_loc
    merged_idx, merged_vals = merge_local_topk(vals, topk_g, dp_axes, cfg.k)

    axes = ("model",) + ((dp_axes,) if isinstance(dp_axes, str)
                         else tuple(dp_axes))
    total_ops = jax.lax.psum(jnp.sum(res.coord_ops)
                             + float(cfg.k * x_loc.shape[1]) * Q, axes)
    max_rounds = jax.lax.pmax(jnp.max(res.rounds), axes)
    return merged_idx, merged_vals, total_ops, max_rounds


def distributed_knn(x, queries, cfg: BMOConfig, mesh: Mesh, rng, *,
                    impl: str = "auto", multi_pod: Optional[bool] = None):
    """x (n, d) sharded P(dp, "model"); queries (Q, d) sharded P(None,
    "model"). Returns DistKNNResult replicated."""
    if multi_pod is None:
        multi_pod = "pod" in mesh.axis_names
    dp_axes = ("pod", "data") if multi_pod else "data"
    n, d = x.shape
    dp_size = int(np.prod([mesh.shape[a] for a in
                           ((dp_axes,) if isinstance(dp_axes, str) else dp_axes)]))
    n_loc = n // dp_size
    # each shard races at δ/D so the per-interval budget matches the
    # single-machine union bound over all n arms (sharded.py)
    import dataclasses

    from repro.core.confidence import shard_delta
    cfg_loc = dataclasses.replace(cfg, delta=shard_delta(cfg.delta, dp_size))

    fn = functools.partial(_local_knn, cfg=cfg_loc, d=d, n_loc=n_loc,
                           dp_axes=dp_axes, impl=impl)
    sm = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp_axes, "model"), P(None, "model"), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    idx, vals, ops, rounds = sm(x, queries, rng)
    return DistKNNResult(idx, vals, ops, rounds)
