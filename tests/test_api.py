"""repro.api (DESIGN.md §6): the unified Index handle — typed QuerySpec
protocol, lifecycle (build/open/load/save), payload riding every remap,
cache + policies, deprecation shims over repro.index, and the PR-4 admin
ops: LIVE elastic re-sharding (bit-identical to the save→load-at-S′ path,
no checkpoint) and read-replica fan-out.

Device-needing parity tests skip unless the interpreter sees enough devices
(the CI job `sharded-mesh` runs this file under
XLA_FLAGS=--xla_force_host_platform_device_count=8); one subprocess test
covers the critical live-reshard parity on every tier-1 run.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import numpy as np
import pytest

from repro.api import (CachePolicy, CompactionPolicy, Index, KNNResult,
                       QuerySpec, ServeStats)
from repro.configs.base import BMOConfig
from repro.core import oracle
from repro.data.synthetic import make_knn_benchmark_data

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _devices(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (run under XLA_FLAGS="
               f"--xla_force_host_platform_device_count={n})")


def _cfg(**kw):
    base = dict(k=3, delta=0.01, block=32, batch_arms=16, metric="l2")
    base.update(kw)
    return BMOConfig(**base)


def _data(n=200, d=256, Q=4, seed=0):
    return make_knn_benchmark_data("dense", n, d, Q, seed=seed)


# ---------------------------------------------------------------------------
# QuerySpec: boundary validation + overrides
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad", [
    dict(mode="warp"), dict(impl="cuda"), dict(impl="pallas"),
    dict(impl="xla"), dict(cache="maybe"),
    dict(k=0), dict(delta=0.0), dict(delta=1.5), dict(max_rounds=0),
])
def test_query_spec_rejects_bad_fields(bad):
    with pytest.raises(ValueError):
        QuerySpec(**bad)


def test_query_spec_impls_are_the_kernel_dispatchers():
    from repro.kernels import IMPLS
    assert IMPLS == ("auto", "kernel", "interpret", "ref")
    for impl in IMPLS:
        assert QuerySpec(impl=impl).impl == impl


def test_query_spec_bind_and_cacheable():
    cfg = _cfg()
    assert QuerySpec().bind(cfg) is cfg            # no-op stays identical
    bound = QuerySpec(k=7, delta=0.2, max_rounds=9).bind(cfg)
    assert (bound.k, bound.delta, bound.max_rounds) == (7, 0.2, 9)
    assert QuerySpec().cacheable
    assert QuerySpec(mode="rounds").cacheable      # driver choice is free
    for spec in (QuerySpec(k=2), QuerySpec(delta=0.5),
                 QuerySpec(max_rounds=4), QuerySpec(warm_start=False),
                 QuerySpec(prior_hint=np.zeros((1, 8)))):
        assert not spec.cacheable                  # changes the contract


def test_policy_validation():
    with pytest.raises(ValueError):
        CachePolicy(capacity=-1)
    with pytest.raises(ValueError):
        CachePolicy(near_threshold=1.5)
    with pytest.raises(ValueError):
        CompactionPolicy(threshold=0.0)
    assert CompactionPolicy(threshold=2.0).threshold == 2.0   # "disabled"


def test_serve_stats_schema_and_legacy_keys():
    # schema_version bumped 1 -> 2 in PR 5 (request-plane queue/latency
    # fields) and 2 -> 3 in PR 6 (obs_* registry fields; latency
    # percentiles are now 0.0 instead of None on an empty window;
    # DESIGN.md §8 changelog note) — the v1 fields and the legacy knn_*
    # keys are unchanged; 3 -> 4 in PR 7 (QuerySpec.use_tuned,
    # DESIGN.md §9.6); 4 -> 5 in PR 8 (audit_* / slo_alerts /
    # serving_fallback / retune_requested, DESIGN.md §10); 5 -> 6 in
    # PR 9 (fleet_namespaces_resident/evicted, fleet_reloads,
    # ns_queue_depth, DESIGN.md §11)
    st = ServeStats(races=3, cache_hits=5)
    d = st.as_dict()
    assert d["schema_version"] == 6 and d["races"] == 3
    assert d["audit_sampled"] == 0 and d["audit_err_upper"] == 1.0
    assert d["serving_fallback"] is False
    assert d["fleet_namespaces_resident"] == 0 and d["fleet_reloads"] == 0
    assert d["ns_queue_depth"] is None
    assert d["plane_submitted"] == 0 and d["plane_latency_p99_ms"] == 0.0
    assert st["knn_races"] == 3 and st["knn_cache_hits"] == 5
    assert st["races"] == 3                        # new names work too
    assert "knn_shard_coord_ops" in st and "bogus" not in st
    with pytest.raises(KeyError):
        st["bogus"]


# ---------------------------------------------------------------------------
# handle lifecycle (single shard — runs anywhere)
# ---------------------------------------------------------------------------


def test_handle_build_query_mutate_save_load(tmp_path):
    corpus, queries = _data()
    ex = oracle.exact_knn(corpus, queries, 3, "l2")
    idx = Index.build(corpus, _cfg(), jax.random.PRNGKey(0),
                      payload=np.arange(200, dtype=np.int32))
    assert (idx.n_live, idx.n_shards, idx.k) == (200, 1, 3)
    res = idx.query(queries, jax.random.PRNGKey(1))
    assert isinstance(res, KNNResult)
    for i in range(4):
        assert set(res.indices[i].tolist()) == \
            set(np.asarray(ex.indices[i]).tolist())
    assert (np.diff(res.values, axis=1) >= -1e-6).all()

    # k override via kwargs == via spec; either way uncached
    r_kw = idx.query(queries, jax.random.PRNGKey(2), k=2)
    r_sp = idx.query(queries, jax.random.PRNGKey(2), spec=QuerySpec(k=2))
    assert r_kw.indices.shape == (4, 2)
    np.testing.assert_array_equal(r_kw.indices, r_sp.indices)
    # δ + budget overrides rebind the racing cfg without touching the store
    r_tight = idx.query(queries, jax.random.PRNGKey(3), delta=0.001,
                        max_rounds=500, cache="bypass")
    assert set(r_tight.indices[0].tolist()) == \
        set(np.asarray(ex.indices[0]).tolist())
    assert idx.cfg.delta == 0.01                   # store cfg untouched

    # mutation: payload rides insert + compact remaps inside the handle
    epoch0 = idx.epoch
    gids = idx.insert(queries[:1], payload=np.asarray([999], np.int32))
    assert idx.epoch == epoch0 + 1
    r2 = idx.query(queries[:1], jax.random.PRNGKey(4))
    assert int(r2.indices[0, 0]) == int(gids[0])
    assert int(idx.payload[r2.indices[0, 0]]) == 999
    idx.delete(list(range(100, 200)))
    assert idx.maybe_compact() is not None         # policy default 0.5
    assert idx.stats.compactions == 1
    r3 = idx.query(queries[:1], jax.random.PRNGKey(5))
    assert int(idx.payload[r3.indices[0, 0]]) == 999

    # persistence: payload sidecar rides save/load
    path = os.path.join(tmp_path, "idx")
    idx.save(path)
    idx2 = Index.load(path)
    assert idx2.n_live == idx.n_live
    r4 = idx2.query(queries[:1], jax.random.PRNGKey(5))
    np.testing.assert_array_equal(r4.indices, r3.indices)
    assert int(idx2.payload[r4.indices[0, 0]]) == 999


def test_handle_cache_hits_refresh_and_epoch_fence():
    corpus, queries = _data()
    idx = Index.build(corpus, _cfg(), jax.random.PRNGKey(0),
                      cache=CachePolicy(capacity=8, near_threshold=0.0))
    r1 = idx.query(queries, jax.random.PRNGKey(1))
    assert r1.cache_hits == 0 and float(r1.coord_ops.sum()) > 0
    r2 = idx.query(queries, jax.random.PRNGKey(9))     # rng must not matter
    assert r2.cache_hits == 4 and float(r2.coord_ops.sum()) == 0.0
    np.testing.assert_array_equal(r1.indices, r2.indices)
    st = idx.stats
    assert (st.races, st.raced_queries, st.cache_hits) == (1, 4, 4)
    # refresh forces a re-race and overwrites the entries
    r3 = idx.query(queries, jax.random.PRNGKey(2), cache="refresh")
    assert r3.cache_hits == 0 and idx.stats.races == 2
    # bypass leaves the cache untouched
    idx.query(queries, jax.random.PRNGKey(3), cache="bypass")
    assert idx.stats.cache_entries == 4
    # epoch fence: any mutation invalidates
    idx.delete([int(r1.indices[0, 0])])
    assert idx.stats.cache_entries == 0
    # regression: an EMPTY QueryCache is falsy (__len__) — the cumulative
    # hit/miss counters must survive invalidation, not read as 0
    assert idx.stats.cache_hits == 4 and idx.stats.cache_misses == 4
    r5 = idx.query(queries, jax.random.PRNGKey(4))
    assert r5.cache_hits == 0
    assert int(r1.indices[0, 0]) not in set(r5.indices[0].tolist())


def test_attach_payload_validation():
    corpus, _ = _data()
    idx = Index.build(corpus, _cfg(), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="exceeds index capacity"):
        idx.attach_payload(np.zeros(idx.capacity + 1, np.int32))
    with pytest.raises(ValueError, match="does not cover"):
        idx.attach_payload(np.zeros(idx.n_live - 1, np.int32))
    idx.attach_payload(np.zeros(idx.n_live, np.int32))   # prefix covers live
    assert len(idx.payload) == idx.capacity


def test_build_gids_invalidated_on_delete_and_slot_reuse():
    """Regression: delete must mark the row's build_gid −1 so a later
    insert reusing the freed slot is not attributed to the original row."""
    corpus, _ = _data(n=64, d=64)
    idx = Index.build(corpus, _cfg(block=16), jax.random.PRNGKey(0))
    gid5 = int(idx.build_gids[5])
    idx.delete([gid5])
    assert idx.build_gids[5] == -1
    new_gid = idx.insert(corpus[5:6] * 2.0)       # reuses the freed slot
    assert int(new_gid[0]) == gid5
    assert idx.build_gids[5] == -1                # still not row 5's slot


def test_live_reshard_beyond_device_count_fails_cleanly():
    """Regression: reshard(S' > visible devices) must fail BEFORE the swap
    — the handle keeps serving at the old shard count."""
    corpus, queries = _data(n=64, d=64)
    idx = Index.build(corpus, _cfg(block=16), jax.random.PRNGKey(0))
    want = idx.query(queries, jax.random.PRNGKey(1), cache="bypass")
    with pytest.raises(RuntimeError, match="keeps serving"):
        idx.reshard(jax.device_count() + 1)
    assert idx.n_shards == 1 and idx.stats.reshards == 0
    assert idx._admin_active is None              # fence released
    got = idx.query(queries, jax.random.PRNGKey(1), cache="bypass")
    np.testing.assert_array_equal(got.indices, want.indices)


def test_admin_fence_blocks_mutations():
    corpus, _ = _data(n=64, d=64)
    idx = Index.build(corpus, _cfg(block=16), jax.random.PRNGKey(0))
    with idx._admin_op("test-op"):
        with pytest.raises(RuntimeError, match="quiesced"):
            idx.insert(corpus[:1])
        with pytest.raises(RuntimeError, match="quiesced"):
            idx.delete([0])
        with pytest.raises(RuntimeError, match="in flight"):
            idx.reshard(1)      # S'=1 is viable on any device count
    idx.delete([0])                                # fence lifted


def test_replica_fanout_single_device():
    """Read fan-out works at any device count (surplus replicas share the
    primary's placement): round-robined queries agree, mutation rebuilds."""
    corpus, queries = _data()
    idx = Index.build(corpus, _cfg(), jax.random.PRNGKey(0))
    idx.add_replicas(2)
    assert idx.stats.replicas == 2
    r1 = idx.query(queries, jax.random.PRNGKey(1), cache="bypass")
    r2 = idx.query(queries, jax.random.PRNGKey(1), cache="bypass")
    np.testing.assert_array_equal(r1.indices, r2.indices)
    gid = idx.insert(queries[:1])                  # invalidates replicas
    r3 = idx.query(queries[:1], jax.random.PRNGKey(2), cache="bypass")
    r4 = idx.query(queries[:1], jax.random.PRNGKey(2), cache="bypass")
    assert int(r3.indices[0, 0]) == int(gid[0])
    np.testing.assert_array_equal(r3.indices, r4.indices)


# ---------------------------------------------------------------------------
# deprecation shims over repro.index
# ---------------------------------------------------------------------------


def test_deprecation_shims_warn_once_and_forward():
    import repro.index as old

    corpus, queries = _data(n=80, d=64)
    cfg = _cfg(block=16)
    old._DEPRECATION_WARNED.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        store = old.build_index(corpus, cfg, jax.random.PRNGKey(0))
        store2 = old.build_index(corpus, cfg, jax.random.PRNGKey(0))
        res = old.index_knn(store, queries, jax.random.PRNGKey(1))
        old.index_knn(store2, queries, jax.random.PRNGKey(1))
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    # exactly once per symbol, not per call
    msgs = sorted(str(x.message).split(" ")[0] for x in dep)
    assert msgs == ["repro.index.build_index", "repro.index.index_knn"]
    # and the shim forwards to the very implementation the new API calls
    from repro.index import batched_race, builder
    assert old.index_knn.__wrapped__ is batched_race.index_knn
    assert old.build_index.__wrapped__ is builder.build_index
    # results identical to the new surface on the same store + rng
    handle = Index.open(store)
    new = handle.query(queries, jax.random.PRNGKey(1), cache="bypass")
    np.testing.assert_array_equal(np.asarray(res.indices), new.indices)
    np.testing.assert_array_equal(np.asarray(res.values), new.values)


def test_every_shimmed_symbol_is_wrapped():
    import repro.index as old
    for name, (mod, _) in old._SHIMS.items():
        fn = getattr(old, name)
        assert fn.__wrapped__ is getattr(mod, name), name
    # the store/state types pass through un-deprecated
    from repro.index import (FrontierState, IndexStore,  # noqa: F401
                             ShardedIndexStore, ShardedKNNResult)


# ---------------------------------------------------------------------------
# LIVE elastic re-sharding: parity vs the save→load-at-S′ path
# ---------------------------------------------------------------------------


def _build_for(kind: str, shards: int, seed: int = 3):
    if kind == "sparse":
        from repro.core.datasets import SparseDataset
        from repro.data.synthetic import clustered_sparse
        corpus = clustered_sparse(120, 512, seed=seed)
        ds = SparseDataset.build(corpus)
        queries = (ds.indices[:2], ds.values[:2], ds.nnz[:2])
        cfg = BMOConfig(k=3, delta=0.01, block=1, batch_arms=16,
                        pulls_per_round=8, init_pulls=16, metric="l1",
                        sparse=True)
    else:
        corpus, queries = make_knn_benchmark_data("dense", 120, 256, 2,
                                                  seed=seed)
        cfg = _cfg(block=64, rotate=(kind == "rotated"))
    idx = Index.build(corpus, cfg, jax.random.PRNGKey(0), shards=shards,
                      payload=np.arange(120, dtype=np.int32))
    return idx, queries


@_devices(8)
@pytest.mark.parametrize("kind", ["dense", "rotated", "sparse"])
@pytest.mark.parametrize("s_from,s_to", [(1, 4), (4, 2), (4, 8)])
def test_live_reshard_parity_vs_save_load(tmp_path, kind, s_from, s_to):
    """Property (PR-4 acceptance): ``Index.reshard(S')`` on a LIVE handle —
    with pending tombstones — returns bit-identical top-k ids/values to the
    save_sharded_index → load_sharded_index(shards=S') path, with the
    payload remapped and the query cache invalidated, and NO checkpoint
    written by the live path."""
    live, queries = _build_for(kind, s_from)
    live.delete(live.build_gids[[5, 17, 101]])     # pending tombstones
    if kind != "sparse":                           # warm the cache too
        live.query(queries, jax.random.PRNGKey(6))
        assert live.stats.cache_entries > 0

    path = os.path.join(tmp_path, "idx")
    live.save(path)
    ref = Index.load(path, shards=s_to)
    want = ref.query(queries, jax.random.PRNGKey(7), cache="bypass")

    n_files_before = sum(len(f) for _, _, f in os.walk(tmp_path))
    old_ids = live.reshard(s_to)
    assert sum(len(f) for _, _, f in os.walk(tmp_path)) == n_files_before
    got = live.query(queries, jax.random.PRNGKey(7), cache="bypass")

    np.testing.assert_array_equal(got.indices, want.indices)   # bit-exact
    np.testing.assert_array_equal(got.values, want.values)
    np.testing.assert_array_equal(live.payload, ref.payload)
    assert live.n_shards == s_to and live.stats.reshards == 1
    assert live.stats.cache_entries == 0           # fence cleared the LRU
    assert old_ids.shape == (live.capacity,)
    # payload still names the original rows through the remap
    rows = live.payload[got.indices]
    assert (live.build_gids[rows] == got.indices).all()


@_devices(4)
def test_live_reshard_then_serve_and_mutate():
    """After a live 4→2 re-shard the handle keeps serving AND mutating:
    inserts route by global id in the new addressing."""
    live, queries = _build_for("dense", 4)
    live.reshard(2)
    q0 = np.asarray(queries)[:1]
    gid = live.insert(q0 + 1e-3, payload=np.asarray([-1], np.int32))
    res = live.query(q0, jax.random.PRNGKey(2), cache="bypass")
    assert int(res.indices[0, 0]) == int(gid[0])
    assert int(live.payload[res.indices[0, 0]]) == -1


def test_live_reshard_parity_subprocess(tmp_path):
    """Dense 4→2 live-reshard parity on a forced 4-device host mesh — runs
    on every tier-1 invocation regardless of the parent's device count."""
    prog = f"""
        import os, numpy as np, jax
        from repro.api import Index
        from repro.configs.base import BMOConfig
        from repro.data.synthetic import make_knn_benchmark_data
        corpus, queries = make_knn_benchmark_data("dense", 128, 256, 2, seed=3)
        cfg = BMOConfig(k=3, delta=0.01, block=32, batch_arms=16, metric="l2")
        live = Index.build(corpus, cfg, jax.random.PRNGKey(0), shards=4,
                           payload=np.arange(128, dtype=np.int32))
        live.delete(live.build_gids[[3, 50]])
        path = r"{str(tmp_path)}/idx"
        live.save(path)
        ref = Index.load(path, shards=2)
        want = ref.query(queries, jax.random.PRNGKey(7), cache="bypass")
        live.reshard(2)
        got = live.query(queries, jax.random.PRNGKey(7), cache="bypass")
        np.testing.assert_array_equal(got.indices, want.indices)
        np.testing.assert_array_equal(got.values, want.values)
        np.testing.assert_array_equal(live.payload, ref.payload)
        assert live.n_shards == 2 and live.stats.reshards == 1
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c",
                          "import repro\n" + textwrap.dedent(prog)],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=560)
    assert out.returncode == 0 and "OK" in out.stdout, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"


@_devices(4)
def test_replica_fanout_on_disjoint_meshes():
    """Sharded replicas land on disjoint device slices (S=2, r=2 on 4
    devices) and round-robined queries agree with the primary's."""
    corpus, queries = _data(n=128, d=256)
    idx = Index.build(corpus, _cfg(block=64), jax.random.PRNGKey(0),
                      shards=2)
    want = idx.query(queries, jax.random.PRNGKey(1), cache="bypass")
    idx.add_replicas(2)
    r1 = idx.query(queries, jax.random.PRNGKey(1), cache="bypass")  # primary
    r2 = idx.query(queries, jax.random.PRNGKey(1), cache="bypass")  # replica
    np.testing.assert_array_equal(r1.indices, want.indices)
    np.testing.assert_array_equal(r2.indices, want.indices)
    reps = idx._replica_stores
    assert reps is not None and len(reps) == 2
    assert reps[1].device_offset == 2              # disjoint slice
