"""PR-6 observability coverage (DESIGN.md §8): metrics-registry and
ring-buffer unit semantics, Prometheus/JSON exporter formats, per-ticket
trace-span completeness across the race boxes (dense / rotated / sparse at
S=1, plus S=4 on a forced 4-device mesh as a subprocess), the shed span,
the no-epoch-mixing guarantee across the mutation fence (both modes), the
empty-window latency-percentile regression, structured trace-id logging,
the Chrome-trace writer, the committed sample trace render, and the
race and plane phase spans (event log and profiler annotations).

Every plane/race test uses a private ``ObsContext`` injected via the
``obs=`` kwarg so tests never race each other through the process-default
context.
"""
import collections
import contextlib
import json
import logging
import math
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.api import Index
from repro.configs.base import BMOConfig
from repro.data.synthetic import clustered_sparse, make_knn_benchmark_data
from repro.obs import (ObsContext, events_doc, json_snapshot,
                       prometheus_text)
from repro.obs.registry import EventLog, Histogram, MetricsRegistry
from repro.obs.trace import NULL_SPAN, Tracer, new_trace_id
from repro.serve.plane import PlaneConfig, RequestPlane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(prog: str, devices: int = 4, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c",
                          "import repro\n" + textwrap.dedent(prog)],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=timeout)
    assert out.returncode == 0 and "OK" in out.stdout, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"


def _dense_index(n=256, d=512, Q=4, seed=1, **kw):
    corpus, queries = make_knn_benchmark_data("dense", n, d, Q, seed=seed)
    cfg = BMOConfig(k=4, delta=0.01, block=64, batch_arms=16,
                    pulls_per_round=2, metric="l2", **kw)
    return Index.build(corpus, cfg, jax.random.PRNGKey(0)), queries


def _sparse_index():
    corpus = clustered_sparse(200, 2048, seed=4)
    cfg = BMOConfig(k=3, delta=0.01, block=1, batch_arms=16,
                    pulls_per_round=8, init_pulls=16, metric="l1",
                    sparse=True)
    idx = Index.build(corpus, cfg, jax.random.PRNGKey(0))
    from repro.core.datasets import SparseDataset
    ds = SparseDataset.build(corpus)
    return idx, (ds.indices[:4], ds.values[:4], ds.nnz[:4])


def _events(obs, name=None, trace=None):
    evs = obs.events.snapshot()
    if name is not None:
        evs = [e for e in evs if e["name"] == name]
    if trace is not None:
        evs = [e for e in evs if e.get("trace") == trace]
    return evs


# ---------------------------------------------------------------------------
# registry / ring / tracer units
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("repro_test_depth", "help")
    g.set(7)
    g.dec(3)
    g.inc(1)
    assert g.value == 5
    h = reg.histogram("repro_test_ms", "help")
    for v in (0.3, 3.0, 40.0):
        h.observe(v)
    h.observe(float("nan"))               # skipped, never poisons sum
    snap = h.snapshot()
    assert snap["count"] == 3 and math.isfinite(snap["sum"])
    assert sum(snap["counts"]) == 3       # per-bucket, non-cumulative
    assert len(snap["counts"]) == len(snap["buckets"]) + 1
    # registering again with the same (name, labels) returns the instance
    assert reg.counter("repro_test_total", "help") is c
    with pytest.raises(ValueError):       # same name, different type
        reg.gauge("repro_test_total", "help")


def test_registry_labels_are_distinct_series():
    reg = MetricsRegistry()
    a = reg.counter("repro_x_total", "h", shard="0")
    b = reg.counter("repro_x_total", "h", shard="1")
    a.inc(2)
    b.inc(5)
    assert a is not b and a.value == 2 and b.value == 5
    names = [(m.name, m.labels) for m in reg.collect()]
    assert (("repro_x_total", (("shard", "0"),)) in names
            or ("repro_x_total", {"shard": "0"}) in names
            or any(n == "repro_x_total" for n, _ in names))


def test_histogram_quantiles_and_empty():
    h = Histogram("h", "help", buckets=(1.0, 10.0, 100.0))
    assert h.quantile(0.99) == 0.0        # empty window -> 0.0, never NaN
    for _ in range(90):
        h.observe(0.5)
    for _ in range(10):
        h.observe(50.0)
    p50, p99 = h.quantile(0.5), h.quantile(0.99)
    assert 0.0 <= p50 <= 1.0
    assert 10.0 <= p99 <= 100.0
    assert not math.isnan(p50) and not math.isnan(p99)


def test_event_log_ring_drops_oldest():
    log = EventLog(capacity=4)
    for i in range(7):
        log.append({"name": f"e{i}", "ts": float(i)})
    snap = log.snapshot()
    assert [e["name"] for e in snap] == ["e3", "e4", "e5", "e6"]
    assert log.total == 7 and log.drops == 3 and len(log) == 4
    log.clear()
    assert len(log) == 0 and log.snapshot() == []


def test_tracer_span_and_disabled_null_span():
    log = EventLog(capacity=64)
    tr = Tracer(log, enabled=True)
    with tr.span("work", trace="t-1", k=4):
        pass
    tr.instant("mark", trace="t-1", reason="x")
    evs = log.snapshot()
    assert [e["ph"] for e in evs] == ["X", "i"]
    span_ev = evs[0]
    assert span_ev["name"] == "work" and span_ev["trace"] == "t-1"
    assert span_ev["dur"] >= 0.0 and span_ev["attrs"]["k"] == 4
    off = Tracer(log, enabled=False)
    assert off.start("nope", trace="t-2") is NULL_SPAN
    off.instant("nope", trace="t-2")
    assert len(log.snapshot()) == 2       # disabled tracer logged nothing
    a, b = new_trace_id("s"), new_trace_id("s")
    assert a != b and a.startswith("s-")


def test_obs_context_disabled_keeps_counters():
    obs = ObsContext("t", enabled=False)
    idx, queries = _dense_index()
    s = idx.race(queries, jax.random.PRNGKey(0), obs=obs)
    while s.step():
        pass
    assert len(obs.events) == 0           # no spans recorded
    # ...but the metrics registry stays authoritative
    epochs = [m for m in obs.registry.collect()
              if m.name == "repro_race_epochs_total"]
    assert epochs and sum(m.value for m in epochs) >= 1


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_prometheus_text_format():
    obs = ObsContext("t")
    obs.registry.counter("repro_a_total", "a counter", plane="p0").inc(3)
    h = obs.registry.histogram("repro_lat_ms", "latencies",
                               buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(500.0)
    text = prometheus_text(obs.registry)
    lines = text.splitlines()
    assert '# TYPE repro_a_total counter' in lines
    assert 'repro_a_total{plane="p0"} 3' in lines
    # histogram buckets are CUMULATIVE and end with +Inf == _count
    assert 'repro_lat_ms_bucket{le="1"} 1' in lines
    assert 'repro_lat_ms_bucket{le="10"} 2' in lines
    assert 'repro_lat_ms_bucket{le="+Inf"} 3' in lines
    assert 'repro_lat_ms_count 3' in lines
    assert any(ln.startswith("repro_lat_ms_sum ") for ln in lines)


def test_json_snapshot_and_events_doc_roundtrip(tmp_path):
    from repro.api.spec import SCHEMA_VERSION
    from repro.obs import dump_events, dump_metrics
    obs = ObsContext("t")
    obs.registry.counter("repro_a_total", "h").inc()
    obs.tracer.instant("mark", trace="t-1")
    snap = json_snapshot(obs)
    assert snap["schema_version"] == SCHEMA_VERSION
    doc = events_doc(obs)
    assert doc["clock"] == "perf_counter_s" and len(doc["events"]) == 1
    p_json = tmp_path / "m.json"
    p_prom = tmp_path / "m.prom"
    p_ev = tmp_path / "trace.json"
    dump_metrics(str(p_json), obs)
    dump_metrics(str(p_prom), obs)
    dump_events(str(p_ev), obs)
    assert json.loads(p_json.read_text())["schema_version"] == SCHEMA_VERSION
    assert "repro_a_total" in p_prom.read_text()
    assert json.loads(p_ev.read_text())["events"][0]["name"] == "mark"


# ---------------------------------------------------------------------------
# span completeness across the race boxes
# ---------------------------------------------------------------------------


def _assert_ticket_lifecycle(obs, ticket, *, expect_epochs=True):
    """Every admitted ticket yields submit -> queue -> admit -> N epoch
    instants -> exactly one terminal span, all under its trace id."""
    trace = ticket.trace_id
    assert trace, "admitted ticket carries a trace id"
    assert len(_events(obs, "plane.submit", trace)) == 1
    queue = _events(obs, "plane.queue", trace)
    assert queue and all(e["ph"] == "X" for e in queue)
    admits = _events(obs, "plane.admit", trace)
    assert len(admits) >= 1
    sid = admits[-1]["attrs"]["session"]
    term = _events(obs, "plane.terminal", trace)
    assert len(term) == 1
    assert term[0]["attrs"]["reason"] == ticket.result.reason
    assert term[0]["attrs"]["latency_ms"] >= 0.0
    epochs = _events(obs, "ticket.epoch", trace)
    if expect_epochs:
        assert epochs, "racing ticket records per-epoch instants"
        for e in epochs:
            assert e["attrs"]["worst_ci"] >= 0.0
            assert e["attrs"]["epoch"] >= 1
        # the joined session recorded its own race.epoch spans
        race = _events(obs, "race.epoch", sid)
        assert race and all(e["ph"] == "X" for e in race)
        for e in race:
            a = e["attrs"]
            assert a["coord_ops"] >= 0.0 and a["worst_ci"] >= 0.0
    return sid


@pytest.mark.parametrize("kind", ["dense", "rotated", "sparse"])
def test_trace_span_completeness(kind):
    if kind == "sparse":
        idx, queries = _sparse_index()
    else:
        idx, queries = _dense_index(rotate=(kind == "rotated"))
    obs = ObsContext("t")
    plane = RequestPlane(idx, obs=obs)
    t1 = plane.submit(queries, rng=jax.random.PRNGKey(1), cache="bypass")
    t2 = plane.submit(queries, rng=jax.random.PRNGKey(2), cache="bypass")
    plane.drain()
    assert t1.result.reason == "certified"
    sid1 = _assert_ticket_lifecycle(obs, t1)
    sid2 = _assert_ticket_lifecycle(obs, t2)
    assert t1.trace_id != t2.trace_id
    # coalesced into one group -> same session; either way sids join
    assert sid1 and sid2
    # per-epoch telemetry exposes the racing internals
    race = _events(obs, "race.epoch", sid1)
    if kind != "sparse":
        assert all("width" in e["attrs"] and "R" in e["attrs"]
                   for e in race)
    else:
        assert all("R" in e["attrs"] for e in race)


def test_trace_span_completeness_sharded_subprocess():
    """S=4 over a forced 4-device mesh: the race.epoch spans carry the
    per-shard straggler split (coord-ops and rounds per shard)."""
    _run("""
        import jax, numpy as np
        from repro.api import Index
        from repro.configs.base import BMOConfig
        from repro.data.synthetic import (clustered_sparse,
                                          make_knn_benchmark_data)
        from repro.obs import ObsContext
        from repro.serve.plane import RequestPlane

        def events(obs, name, trace=None):
            return [e for e in obs.events.snapshot() if e["name"] == name
                    and (trace is None or e.get("trace") == trace)]

        # dense S=4
        corpus, queries = make_knn_benchmark_data("dense", 256, 512, 4,
                                                  seed=1)
        cfg = BMOConfig(k=4, delta=0.01, block=64, batch_arms=16,
                        pulls_per_round=2, metric="l2")
        idx = Index.build(corpus, cfg, jax.random.PRNGKey(0), shards=4)
        obs = ObsContext("t")
        plane = RequestPlane(idx, obs=obs)
        t = plane.submit(queries, rng=jax.random.PRNGKey(1),
                         cache="bypass")
        plane.drain()
        assert t.result.reason == "certified"
        sid = events(obs, "plane.admit", t.trace_id)[-1]["attrs"]["session"]
        race = events(obs, "race.epoch", sid)
        assert race, "sharded session recorded epoch spans"
        for e in race:
            a = e["attrs"]
            assert a["shards"] == 4
            assert len(a["shard_coord_ops"]) == 4
            assert len(a["shard_rounds"]) == 4
            assert all(v >= 0.0 for v in a["shard_coord_ops"])
        assert events(obs, "ticket.epoch", t.trace_id)
        assert len(events(obs, "plane.terminal", t.trace_id)) == 1

        # sparse S=4
        from repro.core.datasets import SparseDataset
        corpus = clustered_sparse(200, 2048, seed=4)
        scfg = BMOConfig(k=3, delta=0.01, block=1, batch_arms=16,
                         pulls_per_round=8, init_pulls=16, metric="l1",
                         sparse=True)
        sidx = Index.build(corpus, scfg, jax.random.PRNGKey(0), shards=4)
        ds = SparseDataset.build(corpus)
        sq = (ds.indices[:4], ds.values[:4], ds.nnz[:4])
        obs2 = ObsContext("t2")
        plane2 = RequestPlane(sidx, obs=obs2)
        t2 = plane2.submit(sq, rng=jax.random.PRNGKey(1), cache="bypass")
        plane2.drain()
        assert t2.result.reason == "certified"
        sid2 = events(obs2, "plane.admit",
                      t2.trace_id)[-1]["attrs"]["session"]
        for e in events(obs2, "race.epoch", sid2):
            assert len(e["attrs"]["shard_coord_ops"]) == 4
        print("OK")
    """)


def test_shed_ticket_gets_shed_span():
    idx, queries = _dense_index()
    obs = ObsContext("t")
    plane = RequestPlane(idx, PlaneConfig(max_queue=1), obs=obs)
    kept = plane.submit(queries, rng=jax.random.PRNGKey(1), cache="bypass")
    shed = plane.submit(queries, rng=jax.random.PRNGKey(2), cache="bypass")
    assert shed.result is not None and shed.result.reason == "shed"
    evs = _events(obs, "plane.shed", shed.trace_id)
    assert len(evs) == 1 and evs[0]["attrs"]["reason"] == "queue_full"
    assert not _events(obs, "plane.terminal", shed.trace_id)
    plane.drain()
    _assert_ticket_lifecycle(obs, kept)


@pytest.mark.parametrize("mode", ["complete", "readmit"])
def test_trace_epochs_never_mix_store_epochs(mode):
    """The no-mixing guarantee, observable offline: every ticket.epoch
    instant is tagged with the store epoch it raced against, and a single
    ticket's tags never straddle the fence — 'complete' stays entirely on
    the old epoch, 'readmit' switches exactly at the readmit instant."""
    idx, queries = _dense_index()
    obs = ObsContext("t")
    plane = RequestPlane(idx, PlaneConfig(on_mutation=mode), obs=obs)
    epoch0 = idx.epoch
    t = plane.submit(queries, rng=jax.random.PRNGKey(1), cache="bypass")
    plane.step()                          # in flight against epoch0
    idx.insert(np.asarray(_dense_index(seed=7)[1], np.float32))
    plane.drain()
    assert t.result.reason == "certified"
    epochs = _events(obs, "ticket.epoch", t.trace_id)
    assert epochs
    tags = [e["attrs"]["store_epoch"] for e in epochs]
    term = _events(obs, "plane.terminal", t.trace_id)[0]
    if mode == "complete":
        assert set(tags) == {epoch0}
        assert term["attrs"]["store_epoch"] == epoch0
        assert not _events(obs, "plane.readmit", t.trace_id)
    else:
        readmits = _events(obs, "plane.readmit", t.trace_id)
        assert len(readmits) == 1
        cut = readmits[0]["ts"]
        for e in epochs:
            want = epoch0 if e["ts"] < cut else idx.epoch
            assert e["attrs"]["store_epoch"] == want, (e, cut)
        assert term["attrs"]["store_epoch"] == idx.epoch
    assert t.result.epoch == term["attrs"]["store_epoch"]


# ---------------------------------------------------------------------------
# satellite regressions: latency window + stats plumbing
# ---------------------------------------------------------------------------


def test_empty_window_percentiles_are_zero_not_nan():
    idx, _ = _dense_index()
    plane = RequestPlane(idx, obs=ObsContext("t"))
    st = plane.stats                      # zero terminals recorded
    for v in (st.plane_latency_p50_ms, st.plane_latency_p95_ms,
              st.plane_latency_p99_ms):
        assert v == 0.0 and not math.isnan(v)
    d = st.as_dict()
    assert d["plane_latency_p99_ms"] == 0.0


def test_latency_window_is_bounded_and_configurable():
    idx, queries = _dense_index()
    obs = ObsContext("t")
    plane = RequestPlane(idx, PlaneConfig(latency_window=2), obs=obs)
    for i in range(4):
        plane.query(queries, rng=jax.random.PRNGKey(i), cache="bypass")
    assert len(plane._latencies) == 2     # saturated at the window
    st = plane.stats
    assert st.plane_latency_p99_ms >= st.plane_latency_p50_ms >= 0.0
    assert not math.isnan(st.plane_latency_p99_ms)
    # the registry histogram saw ALL terminals, not just the window
    assert st.obs_latency_ms["count"] == 4
    with pytest.raises(ValueError, match="latency_window"):
        PlaneConfig(latency_window=0)


def test_stats_surface_obs_fields_and_counter_parity():
    idx, queries = _dense_index()
    obs = ObsContext("t")
    plane = RequestPlane(idx, obs=obs)
    plane.query(queries, rng=jax.random.PRNGKey(1), cache="bypass")
    st = plane.stats
    assert st.plane_submitted == st.plane_completed == 1
    assert st.obs_events == obs.events.total > 0
    assert st.obs_event_drops == 0
    assert st.obs_epoch_ms["count"] >= 1
    # the registry is the single source of truth: the exported text agrees
    text = prometheus_text(obs.registry)
    assert f'repro_plane_submitted_total{{plane="{plane.plane_id}"}} 1' \
        in text.splitlines()


# ---------------------------------------------------------------------------
# structured logging
# ---------------------------------------------------------------------------


def test_structured_logger_bind_and_suffix():
    # the repo logger installs its own handler with propagate=False, so
    # capture through a handler on the underlying logger, not caplog
    from repro.utils.logging import get_logger
    log = get_logger("repro.test_obs")
    records = []

    class _Cap(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    cap = _Cap(level=logging.INFO)
    log.logger.addHandler(cap)
    try:
        bound = log.bind(trace_id="p0.t1", plane="p0")
        assert bound is not log           # bind is pure
        bound.info("hello %d", 7)
        log.info("plain")
        # None-valued context is dropped, chained binds merge
        bound.bind(shard=None, epoch=2).info("x")
    finally:
        log.logger.removeHandler(cap)
    assert any("hello 7" in m and "trace_id=p0.t1" in m and "plane=p0" in m
               for m in records)
    assert any(m == "plain" for m in records)
    tail = records[-1]
    assert "epoch=2" in tail and "trace_id=p0.t1" in tail \
        and "shard" not in tail


def test_loglevel_env_reread_per_get_logger(monkeypatch):
    from repro.utils.logging import get_logger
    monkeypatch.setenv("REPRO_LOGLEVEL", "ERROR")
    lg = get_logger("repro.test_obs_lvl")
    assert lg.logger.level == logging.ERROR
    monkeypatch.setenv("REPRO_LOGLEVEL", "DEBUG")
    lg = get_logger("repro.test_obs_lvl")  # re-read, same logger object
    assert lg.logger.level == logging.DEBUG
    monkeypatch.setenv("REPRO_LOGLEVEL", "bogus")
    assert get_logger("repro.test_obs_lvl").logger.level == logging.INFO


# ---------------------------------------------------------------------------
# trace_view: chrome writer + committed sample render
# ---------------------------------------------------------------------------


def _trace_view():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_view
    return trace_view


def test_chrome_trace_writer_well_formed():
    idx, queries = _dense_index()
    obs = ObsContext("t")
    plane = RequestPlane(idx, obs=obs)
    plane.query(queries, rng=jax.random.PRNGKey(1), cache="bypass")
    doc = events_doc(obs)
    chrome = _trace_view().to_chrome(doc)
    evs = chrome["traceEvents"]
    assert evs and chrome["displayTimeUnit"] == "ms"
    names = collections.Counter(e["ph"] for e in evs)
    assert names["M"] >= 2                # one thread_name row per trace id
    assert names["X"] >= 1 and names["i"] >= 1
    for e in evs:
        if e["ph"] == "M":
            continue
        assert e["ts"] >= 0.0             # rebased to the earliest event
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        else:
            assert e["s"] == "t"
    json.dumps(chrome)                    # serializable as-is


def test_committed_sample_trace_renders():
    """Acceptance: a single plane-served query is reconstructable offline —
    the committed sample (sharded S=4 run) renders per-epoch pulls /
    frontier / CI and per-shard timing through tools/trace_view.py."""
    tv = _trace_view()
    path = os.path.join(ROOT, "examples", "sample_trace.json")
    doc = tv.load_trace(path)
    text = tv.render(doc)
    assert "plane.submit" in text and "plane.admit" in text
    assert "plane.terminal" in text
    assert "race.epoch" in text
    assert "worst_ci=" in text and "coord_ops=" in text
    assert "shard_coord_ops=" in text     # per-shard straggler split
    assert "unjoined sessions" not in text
    chrome = tv.to_chrome(doc)
    assert chrome["traceEvents"]
    with pytest.raises(ValueError, match="events"):
        tv.load_trace(os.path.join(ROOT, "tests", "api_surface.json"))


# ---------------------------------------------------------------------------
# race and plane phase spans
# ---------------------------------------------------------------------------


def _raced(obs, kind="dense"):
    idx, queries = _sparse_index() if kind == "sparse" else _dense_index()
    s = idx.race(queries, jax.random.PRNGKey(0), obs=obs)
    while s.step():
        pass
    return s


def test_race_epoch_splits_into_wait_and_host():
    obs = ObsContext("t")
    s = _raced(obs)
    race = _events(obs, "race.epoch", s.sid)
    assert race
    for e in race:
        a = e["attrs"]
        assert a["wait_ms"] > 0.0 and a["host_ms"] > 0.0
        assert a["wait_ms"] + a["host_ms"] == pytest.approx(a["dur_ms"])
        assert a["dur_ms"] == pytest.approx(e["dur"] * 1e3)
    # one fetch an epoch (snapshot and survivors packed), a race.sync span
    sync = _events(obs, "race.sync", s.sid)
    assert len(sync) == len(race) + 1              # + the init's snapshot
    assert len(_events(obs, "race.launch", s.sid)) == len(race)
    # the snapshot program runs on its own only at the session's start
    summary = _events(obs, "race.summary", s.sid)
    init = _events(obs, "race.init", s.sid)
    assert len(summary) == 1
    assert (init[0]["ts"] <= summary[0]["ts"] and summary[0]["ts"]
            + summary[0]["dur"] <= init[0]["ts"] + init[0]["dur"])


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_each_epoch_is_one_launch_and_one_fetch(kind):
    """Every epoch of a single-shard session dispatches its launch inside
    one race.launch span and fetches once, inside that epoch's span."""
    obs = ObsContext("t")
    s = _raced(obs, kind)
    race = _events(obs, "race.epoch", s.sid)
    assert race and s.epochs == len(race)
    for e in race:
        assert e["attrs"]["fetches"] == 1
        lo, hi = e["ts"], e["ts"] + e["dur"]
        inside = lambda name: [x for x in _events(obs, name, s.sid)
                               if lo <= x["ts"] and x["ts"] + x["dur"] <= hi]
        assert len(inside("race.sync")) == 1
        assert len(inside("race.launch")) == 1
        assert not inside("race.summary")


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_init_and_epoch_coord_ops_add_up_to_the_total(kind):
    """The race.init span carries the coordinates the wide init paid; with
    every epoch's delta they account for the session's whole total."""
    obs = ObsContext("t")
    s = _raced(obs, kind)
    init = _events(obs, "race.init", s.sid)
    assert len(init) == 1
    a = init[0]["attrs"]
    assert a["Q"] == s.Q and a["width"] > 0 and a["coord_ops"] > 0.0
    assert a["dur_ms"] == pytest.approx(init[0]["dur"] * 1e3)
    epochs = sum(e["attrs"]["coord_ops"]
                 for e in _events(obs, "race.epoch", s.sid))
    total = float(np.sum(s.snapshot.coord_ops))
    assert a["coord_ops"] + epochs == pytest.approx(total)
    series = {m.name: m.value for m in obs.registry.collect()
              if m.name == "repro_race_coord_ops_total"}
    assert series["repro_race_coord_ops_total"] == pytest.approx(epochs)


def test_blocking_driver_counts_epoch_coord_ops():
    """The blocking fused driver fetches its coordinate total with the
    survivor count, once an epoch, and counts the epochs' share."""
    from repro.index.batched_race import index_knn
    from repro.obs import set_obs
    idx, queries = _dense_index()
    cfg = idx.store.cfg
    obs = ObsContext("t")
    old = set_obs(obs)
    try:
        res = index_knn(idx.store, queries, jax.random.PRNGKey(0),
                        mode="fused")
    finally:
        set_obs(old)
    counted = sum(m.value for m in obs.registry.collect()
                  if m.name == "repro_race_coord_ops_total"
                  and dict(m.labels).get("kind") == "fused_blocking")
    T0 = max(1, max(cfg.init_pulls, 2) // cfg.pulls_per_round) \
        * cfg.pulls_per_round
    init = queries.shape[0] * idx.store.n_live * T0 * cfg.block
    assert counted > 0
    assert counted + init == pytest.approx(float(np.sum(res.coord_ops)))


def test_plane_step_self_time_within_its_duration():
    idx, queries = _dense_index()
    obs = ObsContext("t")
    plane = RequestPlane(idx, obs=obs)
    plane.query(queries, rng=jax.random.PRNGKey(1), cache="bypass")
    steps = _events(obs, "plane.step", plane.plane_id)
    assert steps
    for e in steps:
        a = e["attrs"]
        assert 0.0 <= a["self_ms"] <= a["dur_ms"]
        assert a["dur_ms"] == pytest.approx(e["dur"] * 1e3)
    # the step that launched the group paid its race.init
    init = _events(obs, "race.init")
    assert len(init) == 1
    assert any(e["attrs"]["dur_ms"] - e["attrs"]["self_ms"]
               >= init[0]["attrs"]["dur_ms"] for e in steps)
    assert _events(obs, "plane.admission", plane.plane_id)
    assert _events(obs, "plane.harvest", plane.plane_id)


def test_disabled_obs_context_records_no_events():
    idx, queries = _dense_index()
    obs = ObsContext("off", enabled=False)
    plane = RequestPlane(idx, obs=obs)
    t = plane.query(queries, rng=jax.random.PRNGKey(1), cache="bypass")
    assert t.reason == "certified"
    assert len(obs.events) == 0 and obs.events.total == 0
    assert isinstance(obs.tracer.annotate("x"), contextlib.nullcontext)
    assert obs.tracer.span("x") is NULL_SPAN


def test_profile_scopes_name_exactify_and_compaction():
    """Trace-time scopes name the snapshot's exactify and the frontier
    compaction in the compiled programs a device trace shows."""
    from repro.index.anytime import _fused_partial
    from repro.index.frontier import compact_frontier
    idx, queries = _dense_index()
    s = idx.race(queries, jax.random.PRNGKey(0), obs=ObsContext("t"))
    partial = _fused_partial.lower(
        s._x, s._qs, s._st, s._pool, cfg=s._cfg, d=s._d,
        log_term=s._log_term, prior_weight=s._prior_weight)
    assert "/repro.exactify/" in partial.compile().as_text()
    compact = compact_frontier.lower(s._st, W_new=s._st.width // 2)
    assert "/repro.compact_frontier/" in compact.compile().as_text()


def _host_annotations(trace_dir):
    from jax.profiler import ProfileData
    import glob
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events
                           if ev.name.startswith("repro."))
    return out


def test_profiler_capture_nests_race_and_plane_spans(tmp_path):
    """On a CPU profiler capture of a tiny plane race, every phase of the
    race loop and the plane is a host annotation inside its parent."""
    idx, queries = _dense_index()
    obs = ObsContext("t")
    plane = RequestPlane(idx, obs=obs)
    plane.query(queries, rng=jax.random.PRNGKey(1), cache="bypass")  # warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        plane.query(queries, rng=jax.random.PRNGKey(2), cache="bypass")
    finally:
        jax.profiler.stop_trace()
    spans = _host_annotations(str(tmp_path))
    names = {n for n, _, _ in spans}
    assert {"repro.race.sync", "repro.race.launch", "repro.race.summary",
            "repro.race.init", "repro.race.epoch.fused", "repro.plane.step",
            "repro.plane.admission", "repro.plane.harvest"} <= names
    parents = {"repro.race.launch": ("repro.race.epoch.fused",),
               "repro.race.sync": ("repro.race.summary",
                                   "repro.race.epoch.fused"),
               "repro.race.summary": ("repro.race.init",
                                      "repro.race.epoch.fused"),
               "repro.race.init": ("repro.plane.admission",),
               "repro.race.epoch.fused": ("repro.plane.step",),
               "repro.plane.admission": ("repro.plane.step",),
               "repro.plane.harvest": ("repro.plane.step",)}

    def inside(span, parent_names):
        _, s, e = span
        return any(n in parent_names and ps <= s and e <= pe
                   for n, ps, pe in spans)

    for span in spans:
        if span[0] in parents:
            assert inside(span, parents[span[0]]), span[0]


# ---------------------------------------------------------------------------
# jax compile telemetry (repro_xla_compiles_total)
# ---------------------------------------------------------------------------


def test_xla_compile_counter_counts_fresh_compiles():
    """The jax.monitoring hook lands backend compiles in whatever obs
    context is CURRENT at compile time — test-injected contexts see
    exactly the compiles their own traffic caused."""
    from repro.obs import compiles_total, set_obs

    ctx = ObsContext("compiles")
    old = set_obs(ctx)
    try:
        idx, queries = _dense_index(n=128, d=256, seed=7)
        idx.query(queries, jax.random.PRNGKey(0))
        fresh = compiles_total(ctx)
    finally:
        set_obs(old)
    assert fresh >= 1                     # build + first race compile
    h = ctx.registry.histogram("repro_xla_compile_ms",
                               "XLA backend compile wall time (ms)")
    assert h.count == fresh and h.sum > 0.0


def test_warm_race_precompile_leaves_zero_midtraffic_compiles():
    """Regression gate for the warm-start compile chain (DESIGN.md §9):
    the pow2 survivor buckets and pow2-quantized adaptive R bound the
    reachable (W, R) specializations to a log²-sized set, so a handful of
    full-certification warm races must exhaust it — and same-shape
    traffic after that must trigger ZERO further XLA backend compiles.
    An unbounded specialization chain (e.g. un-quantized adaptive R)
    never goes quiet and fails the convergence budget."""
    from repro.obs import compiles_total, set_obs

    idx, queries = _dense_index(n=256, d=256, seed=3)

    def one_race(i):
        rng = np.random.default_rng(i)
        qs = (np.asarray(queries)
              + rng.normal(size=np.asarray(queries).shape)
              .astype(np.float32))
        ctx = ObsContext(f"race{i}")
        old = set_obs(ctx)
        try:
            idx.query(qs, jax.random.PRNGKey(i), cache="bypass")
        finally:
            set_obs(old)
        return compiles_total(ctx)

    # warm until the chain is exhausted (two consecutive quiet races)
    quiet, budget = 0, 12
    for i in range(budget):
        quiet = quiet + 1 if one_race(i) == 0 else 0
        if quiet >= 2:
            break
    assert quiet >= 2, (
        f"compile chain did not converge within {budget} warm races — "
        "specializations are no longer bounded")
    # ...and stays exhausted: mid-traffic races compile NOTHING
    mid = sum(one_race(100 + j) for j in range(3))
    assert mid == 0, (
        f"{mid} XLA compile(s) fired mid-traffic after a warm race — "
        "the precompile chain no longer covers serving shapes")


def test_prometheus_exposition_scraper_conformance():
    """Satellite (PR 8): parse the exposition text the way a scraper does
    and enforce the 0.0.4 grammar — all series of one name contiguous even
    when registration interleaves names, exactly one # TYPE per group
    emitted before any of its samples, HELP/label escaping, cumulative
    monotone ``le`` buckets ending at +Inf == _count, and a _sum sample."""
    obs = ObsContext("t")
    reg = obs.registry
    # interleave registrations across names and label sets on purpose
    reg.counter("repro_x_total", "x events", tenant="a").inc(1)
    h = reg.histogram("repro_ms", "hist with \\ backslash\nnewline",
                      buckets=(1.0, 5.0), tenant="a")
    reg.counter("repro_x_total", "x events", tenant='we"ird\none').inc(2)
    reg.gauge("repro_g", "a gauge").set(1.5)
    h2 = reg.histogram("repro_ms", "", buckets=(1.0, 5.0), tenant="b")
    for v in (0.5, 2.0, 50.0):
        h.observe(v)
    h2.observe(0.1)
    text = prometheus_text(reg)
    assert text.endswith("\n")

    seen_groups, cur = [], None
    types, samples = {}, collections.defaultdict(list)
    for line in text.splitlines():
        assert line == line.strip() and line
        if line.startswith("# HELP "):
            _, name, help_text = line.split(" ", 2)
            assert "\n" not in help_text        # escaped, single line
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            seen_groups.append(name)
            cur = name
            continue
        sample, value = line.rsplit(" ", 1)
        base = sample.split("{")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if base.endswith(suffix) and \
                    base[: -len(suffix)] in types:
                base = base[: -len(suffix)]
                break
        assert base == cur, f"sample {line!r} outside its TYPE group"
        assert base in types, f"sample before TYPE: {line!r}"
        samples[sample.split(" ")[0]].append(float(value))
        samples[base].append(float(value))
    # contiguous: each name opened exactly one group (the context itself
    # eagerly registers its ring-drop counter, hence the leading entry)
    assert seen_groups == ["repro_obs_event_drops_total", "repro_x_total",
                           "repro_ms", "repro_g"]
    assert types == {"repro_obs_event_drops_total": "counter",
                     "repro_x_total": "counter", "repro_ms": "histogram",
                     "repro_g": "gauge"}
    # escaped label value survives as one line
    assert 'tenant="we\\"ird\\none"' in text
    assert "repro_ms hist with \\\\ backslash\\nnewline" in text
    # per-series buckets: cumulative, monotone, +Inf == _count
    for tenant, (c1, c5, cinf, total) in (("a", (1, 2, 3, 3)),
                                          ("b", (1, 1, 1, 1))):
        pre = f'repro_ms_bucket{{tenant="{tenant}",'
        bucket_lines = [l for l in text.splitlines() if l.startswith(pre)]
        vals = [int(l.rsplit(" ", 1)[1]) for l in bucket_lines]
        assert vals == sorted(vals) == [c1, c5, cinf]
        assert f'repro_ms_count{{tenant="{tenant}"}} {total}' in text
        assert any(l.startswith(f'repro_ms_sum{{tenant="{tenant}"}} ')
                   for l in text.splitlines())


def test_event_ring_overflow_exports_drop_counter_and_warns_once():
    """Satellite (PR 8): ring overflow is a first-class signal — the drop
    count exports as ``repro_obs_event_drops_total`` and the first
    overflow warns through the structured logger exactly once."""
    from repro.utils.logging import get_logger
    records = []

    class _Cap(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    cap = _Cap(level=logging.WARNING)
    lg = get_logger("repro.obs")
    lg.logger.addHandler(cap)
    try:
        obs = ObsContext("ovf", event_capacity=4, enabled=True)
        for i in range(3):
            obs.tracer.instant(f"e{i}", trace="t")
        drops = [m for m in obs.registry.collect()
                 if m.name == "repro_obs_event_drops_total"]
        assert len(drops) == 1 and drops[0].value == 0
        assert dict(drops[0].labels)["ring"] == "ovf"
        assert not records                       # no overflow yet, no noise
        for i in range(6):
            obs.tracer.instant(f"f{i}", trace="t")
        assert obs.events.drops == 5
        assert drops[0].value == 5               # counter tracks the ring
        warned = [m for m in records if "ring=ovf" in m]
        assert len(warned) == 1                  # warn-once, not per-event
        assert "4" in warned[0]                  # names the capacity
    finally:
        lg.logger.removeHandler(cap)
    # the Prometheus view carries it too
    assert 'repro_obs_event_drops_total{ring="ovf"} 5' in \
        prometheus_text(obs.registry)
