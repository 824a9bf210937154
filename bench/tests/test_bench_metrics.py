"""Each metric reader, on a hand-made run record."""
import numpy as np
import pytest

from bench import spec
from bench.harness import Run
from bench.traffic import Request


def _req(i, intended, finished, *, rows=1, admitted=None, status="done",
         coord=100.0):
    r = Request(i, np.arange(rows), intended, submitted=intended + 0.01,
                admitted=admitted, finished=finished, status=status)
    r.certified = np.ones(rows, bool)
    r.coord_ops = np.full(rows, coord)
    return r


def _run(reqs, **kw):
    base = dict(workload="w", config={"itemsize": 4}, mix={}, seconds=10.0,
                t0=0.0, t1=10.0, requests=reqs, setup_s=42.0,
                peak_bytes=3 * 2**30, events=[], hist={})
    base.update(kw)
    return Run(**base)


def read(name, run):
    return spec.reader(spec.reader_path(name))(run)


def test_qps_counts_rows_certified_by_the_close():
    reqs = [_req(0, 0.0, 1.0, rows=2), _req(1, 5.0, 9.0),
            _req(2, 9.0, 11.0), _req(3, 9.5, None, status="pending")]
    assert read("qps", _run(reqs)) == pytest.approx(3 / 10.0)


def test_latency_from_intended_arrival_over_answered_requests():
    reqs = [_req(i, float(i), float(i) + 0.1 * (i + 1)) for i in range(20)]
    reqs.append(_req(20, 1.0, None, status="shed"))
    run = _run(reqs)
    lat = [100.0 * (i + 1) for i in range(20)]
    assert read("latency_p50_ms", run) == pytest.approx(np.percentile(lat, 50))
    assert read("latency_p87_ms", run) == pytest.approx(np.percentile(lat, 87))


def test_closed_median_latency_reads_the_requests_answered_by_the_close():
    """``latency_p50_ms.closed`` is the median of the closed window's
    requests, each sent when its client's last one finished: it can move
    apart from the mean that Little's law ties to ``qps``."""
    lat = [0.5, 0.6, 0.7, 4.0, 9.0]
    reqs = [_req(i, float(i), float(i) + v) for i, v in enumerate(lat)]
    run = _run(reqs)
    assert read("latency_p50_ms.closed", run) == pytest.approx(700.0)
    assert np.mean(lat) * 1e3 > 2 * read("latency_p50_ms.closed", run)
    assert read("latency_p50_ms.closed", _run([])) is None


def test_setup_and_peak():
    run = _run([])
    assert read("setup_s", run) == 42.0
    assert read("peak_hbm_gib", run) == pytest.approx(3.0)
    assert read("peak_hbm_gib", _run([], peak_bytes=0)) is None


def test_plane_queue_and_group_rows():
    reqs = [_req(0, 0.0, 1.0, admitted=0.11), _req(1, 0.0, 1.0, admitted=0.31)]
    events = [{"name": "plane.admit", "attrs": {"session": "s-1", "rows": 1}},
              {"name": "plane.admit", "attrs": {"session": "s-1", "rows": 1}},
              {"name": "plane.admit", "attrs": {"session": "s-2", "rows": 3}},
              {"name": "plane.submit", "attrs": {}}]
    run = _run(reqs, events=events)
    assert read("plane_queue_ms.open", run) == pytest.approx(200.0)
    assert read("group_rows.closed", run) == pytest.approx(2.5)
    assert read("plane_queue_ms.open", _run([_req(0, 0, 1)])) is None
    assert read("group_rows.open", _run([])) is None


def test_race_epoch_and_coord_ops():
    run = _run([_req(0, 0, 1, coord=10.0), _req(1, 0, 1, coord=30.0)],
               hist={"repro_race_epoch_ms": (90.0, 3)})
    assert read("race_epoch_ms.open", run) == pytest.approx(30.0)
    assert read("coord_ops_per_query.closed", run) == pytest.approx(20.0)
    assert read("race_epoch_ms.open", _run([])) is None


def test_trace_metrics_need_a_trace():
    run = _run([_req(0, 0, 1, coord=819e9 / 4 * 0.002)])
    for name in ("fused_epoch_pull_roofline.open", "device_idle"):
        assert read(name, run) is None
    run.trace = {"window_s": 4.0, "busy_s": 3.0,
                 "op_s": {"fused_epoch_pull": 0.5, "fusion.3": 1.0}}
    run.peaks = spec.peaks("TPU v5 lite")
    # 0.002 s of HBM time needed over 0.5 s of kernel time
    assert read("fused_epoch_pull_roofline.open", run) == pytest.approx(0.4)
    assert read("device_idle.open", run) == pytest.approx(25.0)
    run.trace["op_s"] = {"fusion.3": 1.0}
    assert read("fused_epoch_pull_roofline.open", run) is None


def test_closed_roofline_counts_the_work_of_spans_ended_by_the_close():
    """A closed loop's roofline counts the ``coord_ops`` of the race spans
    that ended in the window, not the answered requests' results: the
    requests in flight at the close had device time in the trace too."""
    per_s = 819e9 / 4                  # coordinates one second of HBM reads
    events = [
        {"name": "race.init", "attrs": {"coord_ops": per_s * 0.001}},
        {"name": "race.epoch", "attrs": {"coord_ops": per_s * 0.0005}},
        {"name": "race.epoch", "attrs": {"coord_ops": per_s * 0.0005}},
        {"name": "race.sync", "attrs": {}},
        {"name": "plane.admit", "attrs": {"session": "s-1", "rows": 2}}]
    # one request answered by the close, whose results hold a tenth of
    # the work
    run = _run([_req(0, 0, 1, coord=per_s * 0.0002)], events=events)
    run.trace = {"window_s": 4.0, "busy_s": 3.0,
                 "op_s": {"fused_epoch_pull": 0.5}}
    run.peaks = spec.peaks("TPU v5 lite")
    # 0.002 s of HBM time over 0.5 s of kernel time, as the spans say
    assert read("fused_epoch_pull_roofline.closed", run) == pytest.approx(
        0.4)
    assert read("fused_epoch_pull_roofline.open", run) == pytest.approx(
        0.04)
    assert read("fused_epoch_pull_roofline.closed",
                _run(run.requests, events=events[3:], trace=run.trace,
                     peaks=run.peaks)) is None
    assert read("fused_epoch_pull_roofline.closed",
                _run(run.requests, events=events)) is None
