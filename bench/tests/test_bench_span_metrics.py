"""The readers of the race loop's and the request plane's span metrics,
on hand-made event logs, and on logs without those spans (a program that
records none gives no number, never an error)."""
import pytest

from bench import spec
from bench.harness import Run


def _run(events):
    return Run(workload="w", config={"itemsize": 4}, mix={}, seconds=10.0,
               t0=0.0, t1=10.0, requests=[], setup_s=1.0, peak_bytes=1,
               events=events, hist={})


def read(name, run):
    return spec.reader(spec.reader_path(name))(run)


def _epoch(wait_ms, host_ms, coord_ops):
    return {"ph": "X", "name": "race.epoch", "trace": "s-1",
            "dur": (wait_ms + host_ms) / 1e3,
            "attrs": {"wait_ms": wait_ms, "host_ms": host_ms,
                      "dur_ms": wait_ms + host_ms, "coord_ops": coord_ops}}


def _init(dur_ms, coord_ops):
    return {"ph": "X", "name": "race.init", "trace": "s-1",
            "dur": dur_ms / 1e3,
            "attrs": {"Q": 2, "width": 8, "coord_ops": coord_ops,
                      "dur_ms": dur_ms}}


def _step(self_ms, dur_ms):
    return {"ph": "X", "name": "plane.step", "trace": "p0",
            "dur": dur_ms / 1e3, "attrs": {"self_ms": self_ms,
                                           "dur_ms": dur_ms}}


EVENTS = [
    _init(20.0, 300.0), _init(40.0, 500.0),
    _epoch(4.0, 2.0, 100.0), _epoch(6.0, 4.0, 300.0), _epoch(2.0, 0.0, 0.0),
    _step(1.5, 30.0), _step(0.5, 8.0),
    {"ph": "i", "name": "plane.admit", "trace": "p0.t1",
     "attrs": {"session": "s-1", "rows": 1}},
    {"ph": "X", "name": "race.sync", "trace": "s-1", "dur": 0.004,
     "attrs": {}},
]


@pytest.mark.parametrize("name,want", [
    ("epoch_wait_ms.open", 4.0),
    ("epoch_host_ms.open", 2.0),
    ("race_init_ms.open", 30.0),
    ("init_coord_share.open", 100.0 * 800.0 / 1200.0),
    ("plane_self_ms.open", 1.0),
])
def test_span_metric_reads_its_spans(name, want):
    assert read(name, _run(EVENTS)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "epoch_wait_ms.open", "epoch_host_ms.open", "race_init_ms.open",
    "init_coord_share.open", "plane_self_ms.open"])
def test_span_metric_without_its_spans_is_none(name):
    assert read(name, _run([])) is None
    # a program whose race.epoch spans carry no wait/host split and which
    # records no race.init or plane.step spans
    bare = [{"ph": "X", "name": "race.epoch", "trace": "s-1", "dur": 0.01,
             "attrs": {"coord_ops": 10.0, "dur_ms": 10.0}}]
    assert read(name, _run(bare)) is None


def test_epoch_wait_and_host_add_up_to_the_epoch():
    run = _run(EVENTS)
    epochs = [e["attrs"]["dur_ms"] for e in EVENTS
              if e["name"] == "race.epoch"]
    assert (read("epoch_wait_ms.open", run)
            + read("epoch_host_ms.open", run)) == pytest.approx(
        sum(epochs) / len(epochs))


def _sharded_epoch(shard_coord_ops, fetches=2):
    return {"ph": "X", "name": "race.epoch", "trace": "s-2", "dur": 0.008,
            "attrs": {"wait_ms": 6.0, "host_ms": 2.0, "dur_ms": 8.0,
                      "fetches": fetches, "shards": len(shard_coord_ops),
                      "coord_ops": float(sum(shard_coord_ops)),
                      "shard_coord_ops": shard_coord_ops}}


@pytest.mark.parametrize("epochs,want", [
    ([[5.0, 5.0, 5.0, 5.0]], 1.0),
    ([[8.0, 0.0, 0.0, 0.0]], 4.0),
    # Σ max / Σ mean: (4 + 8) / (2.5 + 2) over two epochs
    ([[4.0, 3.0, 2.0, 1.0], [8.0, 0.0, 0.0, 0.0]], 12.0 / 4.5),
    # an epoch with no work weighs nothing
    ([[0.0, 0.0, 0.0, 0.0], [6.0, 2.0, 2.0, 2.0]], 6.0 / 3.0),
])
def test_shard_straggle_is_the_slowest_shard_over_the_mean(epochs, want):
    run = _run(EVENTS + [_sharded_epoch(e) for e in epochs])
    assert read("shard_straggle.shard4", run) == pytest.approx(want)


def test_epoch_fetches_is_the_mean_fetches_an_epoch():
    one_chip = [dict(e, attrs=dict(e["attrs"], fetches=1))
                for e in EVENTS if e["name"] == "race.epoch"]
    assert read("epoch_fetches.open", _run(one_chip)) == 1.0
    sharded = [_sharded_epoch([1.0, 2.0], fetches=2) for _ in range(3)]
    assert read("epoch_fetches.shard4", _run(sharded)) == 2.0
    assert read("epoch_fetches.shard4",
                _run(one_chip + sharded)) == pytest.approx(9 / 6)


@pytest.mark.parametrize("name", ["shard_straggle.shard4",
                                  "epoch_fetches.shard4"])
def test_shard_readers_without_their_attributes_are_none(name):
    # EVENTS' epochs are one chip's: no split by shard, no fetch count
    assert read(name, _run(EVENTS)) is None
    assert read(name, _run([])) is None
