"""BENCHMARK.json is well formed, and every cell resolves to its files."""
import json
import os
import shutil

import pytest

from bench import spec

BENCH = spec.load()
CELLS = [c["name"] for c in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all("/" not in w or w.startswith("bench/")
               for w in BENCH["command"])


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert spec.NAME.match(entry["name"]), entry["name"]
    if "unit" in entry:
        assert spec.UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
    for key in ("config", "traffic"):
        if key in entry:
            assert spec.NAME.match(entry[key])
    for key in entry.get("reduced", []):
        assert spec.NAME.match(key)
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


def test_unique_names():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_to_its_files(workload):
    cell = spec.cell(BENCH, workload)
    config = spec.config(BENCH, cell["config"])
    mix = spec.traffic(cell["traffic"])
    assert {"n", "d", "d_pad", "bmo", "generator", "limits"} <= set(config)
    assert mix["entry"] == "plane"
    for trace in (False, True):
        chosen = spec.metrics_for(BENCH, workload, trace)
        assert chosen, f"{workload} reports no metric (trace={trace})"
        for m in chosen:
            assert callable(spec.reader(spec.reader_path(m["name"])))
    e2e = {m["name"] for m in spec.metrics_for(BENCH, workload, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in spec.metrics_for(BENCH, workload, True):
        assert m["moves"] in e2e


@pytest.mark.parametrize("workload", CELLS)
def test_cell_has_a_chip_for_each_shard(workload):
    cell = spec.cell(BENCH, workload)
    config = spec.config(BENCH, cell["config"])
    spec.check_chips(cell, config)
    assert spec.shards(config) <= cell["chips"]


def test_a_cell_with_fewer_chips_than_shards_is_refused():
    cell = {"name": "w", "config": "c", "chips": 1}
    spec.check_chips(cell, {})
    spec.check_chips(dict(cell, chips=4), {"shards": 4})
    with pytest.raises(ValueError, match="fewer than the 4 shards"):
        spec.check_chips(cell, {"shards": 4})


def test_every_metric_moves_an_end_to_end_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in CELLS


def test_bounds_are_in_range():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_a_cell_is_added_with_files_and_one_entry(tmp_path):
    """A new cell on a configuration the benchmark has needs its mix file
    and one ``workloads`` entry; every metric it reports has a reader."""
    shutil.copytree(os.path.join(spec.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "bench" / "traffic" / "tinyimagenet-burst.json").write_text(
        json.dumps({"loop": "open", "entry": "plane",
                    "rows_per_request": 1,
                    "arrival": {"process": "poisson", "rate_qps": 2.0,
                                "on_s": 5, "off_s": 15}}))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "tinyimagenet-burst",
                               "config": "tinyimagenet-12288",
                               "traffic": "tinyimagenet-burst", "chips": 1,
                               "why": "on/off Poisson bursts"})
    # the open loop's latency, and a per-layer metric read by a reader
    # the benchmark has (``device_idle.py``)
    for m in bench["end_to_end"]:
        if m["name"].startswith("latency_"):
            m["workloads"].append("tinyimagenet-burst")
    bench["per_layer"].append({
        "name": "device_idle.burst", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "latency_p50_ms", "workloads": ["tinyimagenet-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    root = str(tmp_path)
    loaded = spec.load(root)
    cell = spec.cell(loaded, "tinyimagenet-burst")
    assert spec.config(loaded, cell["config"], root)["n"] == 100000
    mix = spec.traffic(cell["traffic"], root)
    assert mix["arrival"]["on_s"] == 5
    for trace in (False, True):
        chosen = spec.metrics_for(loaded, "tinyimagenet-burst", trace)
        assert chosen
        for m in chosen:
            assert os.path.exists(spec.reader_path(m["name"], root))
    assert {m["name"] for m in spec.metrics_for(
        loaded, "tinyimagenet-burst", False)} == {
        "latency_p50_ms", "peak_hbm_gib", "setup_s"}
