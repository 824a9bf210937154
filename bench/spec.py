"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; each metric is read by
``metrics/<name>.py``, or, where that file is absent, by the reader of the
name's stem before its first dot (``race_epoch_ms.open`` is read by
``metrics/race_epoch_ms.py``). Adding a cell is adding files and entries.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == workload:
            return c
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                   f"(have {[c['name'] for c in bench['workloads']]})")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def shards(config: dict) -> int:
    """Shards of the configuration's index, one a chip (1 when absent)."""
    return int(config.get("shards", 1))


def check_chips(cell: dict, config: dict) -> None:
    """A cell needs a chip for each shard of its configuration's index:
    the build places shard s on chip s."""
    if int(cell["chips"]) < shards(config):
        raise ValueError(f"cell {cell['name']!r} asks for {cell['chips']} "
                         f"chips, fewer than the {shards(config)} shards of "
                         f"its configuration {cell['config']!r}")


def traffic(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "traffic", f"{name}.json"))


def reported(metric: dict, workload: str, end_to_end: list) -> bool:
    """Whether ``workload`` reports ``metric``: the cells it lists, or,
    without a list, every cell that reports the metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    return any(m["name"] == moves and reported(m, workload, end_to_end)
               for m in end_to_end)


def metrics_for(bench: dict, workload: str, trace: bool) -> list:
    """The metrics a run of ``workload`` prints: the cell's end-to-end
    metrics, or with ``trace`` its per-layer ones."""
    e2e = bench["end_to_end"]
    pool = bench["per_layer"] if trace else e2e
    return [m for m in pool if reported(m, workload, e2e)]


def reader_path(name: str, root: str = ROOT) -> str:
    mdir = os.path.join(root, "bench", "metrics")
    for stem in (name, name.split(".")[0]):
        path = os.path.join(mdir, f"{stem}.py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no reader for metric {name!r} in {mdir}")


@functools.lru_cache(maxsize=None)
def reader(path: str):
    """The ``read(run)`` function of one metric file."""
    mod_name = "bench_metric_" + re.sub(r"\W", "_", os.path.basename(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """The published peaks of one device kind; an unknown kind is an
    error, never a default."""
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
