"""Index subsystem tests: batched-racing parity with per-query knn(),
mutation (insert/delete/compact) correctness, checkpoint round-trip, and
warm-start plumbing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import BMOConfig
from repro.core import bmo_nn, oracle
from repro.core.datasets import SparseDataset
from repro.data.synthetic import clustered_sparse, make_knn_benchmark_data
from repro.index import (IndexStore, build_index, compact, delete, index_knn,
                         insert, load_index, maybe_compact, save_index)
from repro.index.batched_race import _dense_exact_theta, fused_race_topk
from repro.kernels import ops as kops


def _sets(idx):
    return [set(np.asarray(idx[i]).tolist()) for i in range(idx.shape[0])]


# ---------------------------------------------------------------------------
# batched racing parity: index.batched_race == per-query knn() top-k
# ---------------------------------------------------------------------------


def test_batched_parity_dense():
    corpus, queries = make_knn_benchmark_data("dense", 400, 1024, 6, seed=1)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16,
                    pulls_per_round=2, metric="l2")
    per = bmo_nn.knn(corpus, queries, cfg, jax.random.PRNGKey(0))
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    res = index_knn(store, queries, jax.random.PRNGKey(1))
    assert _sets(res.indices) == _sets(per.indices)
    # both exact → values agree too (sorted ascending per row)
    np.testing.assert_allclose(np.asarray(res.values), np.asarray(per.values),
                               rtol=1e-4, atol=1e-5)


def test_batched_parity_rotated():
    corpus, queries = make_knn_benchmark_data("dense", 300, 512, 4, seed=2)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16, metric="l2",
                    rotate=True)
    per = bmo_nn.knn(corpus, queries, cfg, jax.random.PRNGKey(0))
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    res = index_knn(store, queries, jax.random.PRNGKey(1))
    assert _sets(res.indices) == _sets(per.indices)


@pytest.mark.parametrize("rotate,d", [(True, 96), (False, 100)])
def test_exact_theta_on_the_block_pull_scale(rotate, d):
    """Where the store pads d (to a power of two rotated, to a block
    multiple otherwise), an arm's exact θ equals the mean of its block
    pulls over every block — exact and sampled arms race on one scale."""
    corpus, queries = make_knn_benchmark_data("dense", 64, d, 3, seed=5)
    cfg = BMOConfig(k=3, block=16, metric="l2", rotate=rotate)
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    assert store.d_pad != d
    qs = store.prepare_queries(queries)
    nb = store.d_pad // store.block
    arms = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (3, 8))
    blk = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32), (3, 8, nb))
    pulls = kops.block_pull_multi(store.x, qs, arms, blk, block=16,
                                  impl="ref")
    np.testing.assert_allclose(np.mean(np.asarray(pulls), -1),
                               _dense_exact_theta(store.x, qs, arms, "l2"),
                               rtol=1e-5)


def test_session_epoch_hlo_is_the_stepped_launch():
    """``epoch_hlo`` compiles the program ``step()`` launches, from the same
    builder, and leaves the session's race untouched."""
    from repro.api import Index
    corpus, queries = make_knn_benchmark_data("dense", 256, 512, 4, seed=3)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16, metric="l2",
                    rotate=True)
    index = Index.build(corpus, cfg, jax.random.PRNGKey(0))
    lowered = index.race(queries, jax.random.PRNGKey(1), impl="ref")
    plain = index.race(queries, jax.random.PRNGKey(1), impl="ref")
    hlo = lowered.epoch_hlo()
    assert hlo.startswith("HloModule jit__fused_epoch_snapshot")
    # one program: the epoch's pull, then the snapshot's exactify
    assert "/repro.fused_epoch_pull/" in hlo and "/repro.exactify/" in hlo
    for s in (lowered, plain):
        while s.step():
            pass
    for a, b in zip(lowered.snapshot, plain.snapshot):
        np.testing.assert_array_equal(a, b)


def _fused_session(metric, seed=7):
    from repro.api import Index
    # 128 blocks a row, as the dense set has: arms are accepted on
    # estimates, so the snapshot's exactify has work in every race
    corpus, queries = make_knn_benchmark_data("dense", 256, 2048, 4,
                                              seed=seed)
    cfg = BMOConfig(k=3, delta=0.01, block=16, batch_arms=16,
                    pulls_per_round=2, metric=metric,
                    rotate=metric == "l2")
    index = Index.build(corpus, cfg, jax.random.PRNGKey(0))
    return lambda: index.race(queries, jax.random.PRNGKey(1), impl="ref")


@pytest.mark.parametrize("metric", ["l2", "l1"],
                         ids=["rotated_l2", "l1"])
def test_fused_epoch_snapshot_matches_step_then_partial(metric):
    """An epoch of one launch gives, epoch by epoch, the snapshot and the
    survivor count of the epoch step followed by the snapshot program, on
    the same schedule of widths and rounds."""
    from repro.index.anytime import _fused_partial, _to_host
    from repro.index.batched_race import _fused_epoch_step
    from repro.index.frontier import compact_frontier
    from repro.utils.hostsync import host_fetch
    new_session = _fused_session(metric)
    s, ref = new_session(), new_session()
    assert s.kind == "fused"
    for a, b in zip(s.snapshot, ref.snapshot):
        np.testing.assert_array_equal(a, b)
    epochs, alive = 0, True
    while alive:
        alive = s.step()
        epochs += 1
        # the parent composition, at the width and rounds the session took
        if s._st.width < ref._st.width:
            ref._st = compact_frontier(ref._st, W_new=s._st.width)
        st, n_surv, _ = _fused_epoch_step(
            ref._x, ref._qs, ref._st, ref._pool, cfg=ref._cfg,
            block=ref._block, d=ref._d, impl=ref._impl,
            eliminate=ref._eliminate, prior_weight=ref._prior_weight,
            log_term=ref._log_term, T=s._last_R * ref._cfg.pulls_per_round)
        ref._st, summ = _fused_partial(
            ref._x, ref._qs, st, ref._pool, cfg=ref._cfg, d=ref._d,
            log_term=ref._log_term, prior_weight=ref._prior_weight)
        want = _to_host(summ)
        for a, b in zip(s.snapshot, want):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)
        n_surv = host_fetch(n_surv)
        assert s._n_surv.dtype == n_surv.dtype
        np.testing.assert_array_equal(s._n_surv, n_surv)
    assert epochs > 1
    assert s.done.all()


def test_fused_epoch_snapshot_state_has_the_snapshot_programs_avals():
    """The epoch program's state is the snapshot program's, aval for aval
    (shape, dtype, weak type): a warm-up that feeds either one back into
    the epoch program compiles what the serving loop runs."""
    from repro.index.anytime import _fused_partial
    s = _fused_session("l2")()
    fn, args, kwargs = s._epoch_launch(s._R0)
    st, _, _ = jax.eval_shape(lambda *a: fn(*a, **kwargs), *args)
    want, _ = jax.eval_shape(lambda *a: _fused_partial(
        *a, cfg=s._cfg, d=s._d, log_term=s._log_term,
        prior_weight=s._prior_weight), *args)
    aval = lambda a: (a.shape, a.dtype, a.weak_type)
    assert (jax.tree_util.tree_map(aval, st)
            == jax.tree_util.tree_map(aval, want))
    # and the state the session starts from is the same again
    assert (jax.tree_util.tree_map(aval, st)
            == jax.tree_util.tree_map(
                lambda a: aval(jax.typeof(a)), s._st))


def test_batched_parity_sparse():
    corpus = clustered_sparse(200, 2048, seed=4)
    ds = SparseDataset.build(corpus)
    qi, qv, qn = ds.indices[:4], ds.values[:4], ds.nnz[:4]
    cfg = BMOConfig(k=3, delta=0.01, block=1, batch_arms=16,
                    pulls_per_round=8, init_pulls=16, metric="l1", sparse=True)
    per = bmo_nn.knn(ds, (qi, qv, qn), cfg, jax.random.PRNGKey(3))
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    res = index_knn(store, (qi, qv, qn), jax.random.PRNGKey(5))
    assert _sets(res.indices) == _sets(per.indices)


def test_k_exceeding_live_slots_raises():
    corpus = np.random.default_rng(0).normal(size=(8, 256)).astype(np.float32)
    cfg = BMOConfig(k=5, delta=0.05, block=32, batch_arms=4, metric="l2")
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    store = delete(store, [0, 1, 2, 3, 4, 5])
    with pytest.raises(ValueError, match="live slots"):
        index_knn(store, corpus[:1], jax.random.PRNGKey(1))


def test_batched_respects_k_override_and_cold_start():
    corpus, queries = make_knn_benchmark_data("dense", 128, 256, 2, seed=7)
    cfg = BMOConfig(k=5, delta=0.05, block=32, batch_arms=16, metric="l2")
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    ex = oracle.exact_knn(corpus, queries, 2, "l2")
    res = index_knn(store, queries, jax.random.PRNGKey(1), k=2,
                    warm_start=False)
    assert res.indices.shape == (2, 2)
    assert _sets(res.indices) == _sets(ex.indices)


# ---------------------------------------------------------------------------
# epoch-fused driver (DESIGN.md §4): parity + frontier-compaction invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rotate", [False, True])
def test_fused_vs_rounds_driver_parity(rotate):
    """The epoch-fused survivor-compacted driver and the PR-1 per-round
    driver certify the same top-k (both exact w.h.p.) on dense/rotated."""
    corpus, queries = make_knn_benchmark_data("dense", 500, 1024, 5, seed=21)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16,
                    pulls_per_round=2, metric="l2", rotate=rotate)
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    fused = index_knn(store, queries, jax.random.PRNGKey(1), mode="fused")
    rounds = index_knn(store, queries, jax.random.PRNGKey(1), mode="rounds")
    ex = oracle.exact_knn(corpus, queries, 3, "l2")
    assert _sets(fused.indices) == _sets(rounds.indices) == _sets(ex.indices)
    np.testing.assert_allclose(np.asarray(fused.values),
                               np.asarray(rounds.values), rtol=1e-4, atol=1e-5)


def test_fused_mode_rejected_for_sparse():
    corpus = clustered_sparse(50, 256, seed=9)
    cfg = BMOConfig(k=2, delta=0.05, block=1, batch_arms=8, pulls_per_round=8,
                    init_pulls=16, metric="l1", sparse=True)
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    ds = SparseDataset.build(corpus[:1])
    with pytest.raises(ValueError, match="sparse"):
        index_knn(store, (ds.indices, ds.values, ds.nnz),
                  jax.random.PRNGKey(1), mode="fused")


def test_frontier_compaction_invariant():
    """Compaction only drops rejected/padding entries: the race must make
    *identical* decisions with and without it — same accepted ids, same
    surviving candidate ids, same top-k, same rounds and coordinate-ops."""
    corpus, queries = make_knn_benchmark_data("dense", 300, 1024, 4, seed=33)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16,
                    pulls_per_round=2, metric="l2")
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    qs = store.prepare_queries(queries)
    kw = dict(cfg=cfg, block=store.block, d=store.d, impl="auto",
              eliminate=True, prior_weight=store.prior_weight,
              _return_state=True)
    res_c, st_c = fused_race_topk(store.x, qs, store.alive, store.prior_var,
                                  jax.random.PRNGKey(5), compaction=True, **kw)
    res_u, st_u = fused_race_topk(store.x, qs, store.alive, store.prior_var,
                                  jax.random.PRNGKey(5), compaction=False, **kw)
    assert st_c.width < st_u.width  # compaction actually shrank the buffers

    def id_sets(st, mask):
        m, ids = np.asarray(mask), np.asarray(st.ids)
        return [set(ids[q][m[q]].tolist()) for q in range(ids.shape[0])]

    acc_c = id_sets(st_c, st_c.accepted & st_c.valid)
    acc_u = id_sets(st_u, st_u.accepted & st_u.valid)
    assert acc_c == acc_u
    surv_c = id_sets(st_c, st_c.valid & ~st_c.rejected & ~st_c.accepted)
    surv_u = id_sets(st_u, st_u.valid & ~st_u.rejected & ~st_u.accepted)
    assert surv_c == surv_u
    np.testing.assert_array_equal(np.asarray(res_c.indices),
                                  np.asarray(res_u.indices))
    np.testing.assert_allclose(np.asarray(res_c.values),
                               np.asarray(res_u.values), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(res_c.rounds),
                                  np.asarray(res_u.rounds))
    np.testing.assert_array_equal(np.asarray(res_c.n_exact),
                                  np.asarray(res_u.n_exact))
    np.testing.assert_allclose(np.asarray(res_c.coord_ops),
                               np.asarray(res_u.coord_ops), rtol=1e-6)


def test_fused_driver_respects_tombstones_and_k_override():
    corpus, queries = make_knn_benchmark_data("dense", 200, 512, 3, seed=12)
    cfg = BMOConfig(k=4, delta=0.01, block=64, batch_arms=16, metric="l2")
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    ex = oracle.exact_knn(corpus, queries, 4, "l2")
    kill = np.asarray(ex.indices[0])[:2].tolist()
    store = delete(store, kill)
    res = index_knn(store, queries, jax.random.PRNGKey(2), k=2, mode="fused")
    assert res.indices.shape == (3, 2)
    for row in _sets(res.indices):
        assert not (row & set(kill))


# ---------------------------------------------------------------------------
# mutation: insert / delete / compact
# ---------------------------------------------------------------------------


def _fresh_equals(store, corpus_rows, queries, cfg, slot_of_row):
    """Post-mutation top-k == fresh build on the mutated corpus (slot ids
    mapped through ``slot_of_row``)."""
    fresh = build_index(np.asarray(corpus_rows), cfg, jax.random.PRNGKey(0))
    want = index_knn(fresh, queries, jax.random.PRNGKey(9))
    got = index_knn(store, queries, jax.random.PRNGKey(9))
    want_slots = [set(int(slot_of_row[j]) for j in row)
                  for row in np.asarray(want.indices)]
    got_slots = _sets(got.indices)
    assert got_slots == want_slots


def test_mutation_round_trip_dense():
    corpus, queries = make_knn_benchmark_data("dense", 200, 512, 3, seed=11)
    cfg = BMOConfig(k=3, delta=0.01, block=64, batch_arms=16, metric="l2")
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    ex = oracle.exact_knn(corpus, queries, 3, "l2")

    # delete the two best arms of query 0: they must disappear from results
    kill = np.asarray(ex.indices[0])[:2].tolist()
    store = delete(store, kill)
    res = index_knn(store, queries, jax.random.PRNGKey(1))
    for row in _sets(res.indices):
        assert not (row & set(kill))
    # equivalent fresh build on the corpus without the deleted rows
    mask = np.ones(len(corpus), bool)
    mask[kill] = False
    slot_of_row = np.nonzero(mask)[0]
    _fresh_equals(store, corpus[mask], queries, cfg, slot_of_row)

    # insert near-duplicates of the queries: they must become the top-1,
    # landing in the freed slots
    store, slots = insert(store, queries + 1e-3)
    assert set(slots.tolist()) <= set(kill) | set(
        range(200, store.capacity))
    res = index_knn(store, queries, jax.random.PRNGKey(2))
    for i in range(len(queries)):
        assert int(np.asarray(res.indices[i])[0]) == int(slots[i])

    # compact: same results through the old→new slot mapping
    before = index_knn(store, queries, jax.random.PRNGKey(3))
    store2, old_ids = compact(store)
    assert store2.n_live == store.n_live
    after = index_knn(store2, queries, jax.random.PRNGKey(3))
    remapped = [set(int(old_ids[j]) for j in row)
                for row in np.asarray(after.indices)]
    assert remapped == _sets(before.indices)


def test_mutation_growth_and_widen_sparse():
    corpus = clustered_sparse(60, 512, seed=6)
    cfg = BMOConfig(k=2, delta=0.01, block=1, batch_arms=16,
                    pulls_per_round=8, init_pulls=16, metric="l1", sparse=True)
    store = build_index(corpus, cfg, jax.random.PRNGKey(0), capacity=64)
    m0 = store.m
    # a denser row than any existing one forces a column widen; 5 rows force
    # a capacity growth (64 - 60 = 4 free)
    rng = np.random.default_rng(0)
    dense_rows = np.where(rng.random((5, 512)) < 0.5,
                          rng.exponential(1.0, (5, 512)), 0).astype(np.float32)
    store, slots = insert(store, dense_rows)
    assert store.capacity > 64 and store.m > m0 and len(slots) == 5
    ds_q = SparseDataset.build(dense_rows[:1])
    res = index_knn(store, (ds_q.indices, ds_q.values, ds_q.nnz),
                    jax.random.PRNGKey(1))
    assert int(np.asarray(res.indices[0])[0]) == int(slots[0])


def test_maybe_compact_threshold_policy():
    """Auto-compaction (ROADMAP): no-op below the tombstone threshold, a
    real capacity-shrinking compact above it, old→new map returned."""
    corpus, queries = make_knn_benchmark_data("dense", 120, 256, 2, seed=17)
    cfg = BMOConfig(k=2, delta=0.05, block=32, batch_arms=16, metric="l2")
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))   # cap 128
    same, old_ids = maybe_compact(store, threshold=0.5)
    assert old_ids is None and same is store                  # 8/128 dead

    store = delete(store, list(range(60, 120)))               # 68/128 dead
    compacted, old_ids = maybe_compact(store, threshold=0.5)
    assert old_ids is not None
    assert compacted.capacity == 64 and compacted.n_live == 60
    # results identical through the slot map
    want = index_knn(store, queries, jax.random.PRNGKey(3))
    got = index_knn(compacted, queries, jax.random.PRNGKey(3))
    remapped = [set(int(old_ids[j]) for j in row)
                for row in np.asarray(got.indices)]
    assert remapped == _sets(want.indices)


# ---------------------------------------------------------------------------
# persistence via checkpoint/manager.py
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind_cfg", [
    ("dense", dict(metric="l2", block=64)),
    ("rotated", dict(metric="l2", block=64, rotate=True)),
    ("sparse", dict(metric="l1", block=1, pulls_per_round=8, init_pulls=16,
                    sparse=True)),
])
def test_save_load_round_trip(tmp_path, kind_cfg):
    kind, kw = kind_cfg
    cfg = BMOConfig(k=3, delta=0.01, batch_arms=16, **kw)
    if kind == "sparse":
        corpus = clustered_sparse(100, 512, seed=3)
        ds = SparseDataset.build(corpus)
        queries = (ds.indices[:2], ds.values[:2], ds.nnz[:2])
    else:
        corpus, queries = make_knn_benchmark_data("dense", 100, 256, 2, seed=3)
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    path = os.path.join(tmp_path, "idx")
    save_index(store, path)
    store2 = load_index(path)
    assert isinstance(store2, IndexStore) and store2.kind == store.kind
    r1 = index_knn(store, queries, jax.random.PRNGKey(1))
    r2 = index_knn(store2, queries, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(r1.indices), np.asarray(r2.indices))
    np.testing.assert_allclose(np.asarray(r1.values), np.asarray(r2.values))


# ---------------------------------------------------------------------------
# degenerate sparse arms (satellite regression: empty-support path)
# ---------------------------------------------------------------------------


def test_sparse_empty_support_arm():
    """All-zero corpus rows (nnz == 0) must race cleanly: θ̂ pulls are 0 when
    the support union is empty, finite otherwise, and the racer returns the
    right neighbours."""
    d = 64
    corpus = np.zeros((6, d), np.float32)
    corpus[0, [1, 5]] = [1.0, 2.0]
    corpus[1, [2]] = [0.5]
    # rows 2..5 all-zero
    ds = SparseDataset.build(corpus)
    assert int(ds.nnz[2]) == 0

    # pulls against an empty query AND an empty arm are exactly 0
    key = jax.random.PRNGKey(0)
    empty_q = SparseDataset.build(np.zeros((1, d), np.float32))
    vals = jax.vmap(lambda kk: bmo_nn.sparse_pull_one(
        ds, empty_q.indices[0], empty_q.values[0], empty_q.nnz[0], 2, kk))(
        jax.random.split(key, 32))
    np.testing.assert_array_equal(np.asarray(vals), 0.0)

    # a zero query's nearest neighbours are the zero rows (θ = 0)
    cfg = BMOConfig(k=3, delta=0.05, block=1, batch_arms=4, pulls_per_round=4,
                    init_pulls=8, metric="l1", sparse=True)
    res = bmo_nn.knn(ds, (empty_q.indices, empty_q.values, empty_q.nnz),
                     cfg, jax.random.PRNGKey(1))
    assert set(np.asarray(res.indices[0]).tolist()) <= {2, 3, 4, 5}
    np.testing.assert_allclose(np.asarray(res.values[0]), 0.0, atol=1e-6)

    # and the batched index path handles tombstoned + empty rows together
    store = build_index(corpus, cfg, jax.random.PRNGKey(0))
    store = delete(store, [2])
    bres = index_knn(store, (empty_q.indices, empty_q.values, empty_q.nnz),
                     jax.random.PRNGKey(2))
    got = set(np.asarray(bres.indices[0]).tolist())
    assert got <= {3, 4, 5} and 2 not in got
