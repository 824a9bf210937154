"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e: three windows of a jitted fused_epoch_pull launch
and a matmul, each followed by a 3 ms host-only pause."""
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


def test_union_gaps_and_attribution():
    busy = tr._union([(25, 28), (0, 5), (80, 90), (3, 4)])
    assert busy == [[0, 5], [25, 28], [80, 90]]
    gaps = tr._gaps(busy, 0, 100)
    assert gaps == [(5, 25), (28, 80), (90, 100)]
    spans = [("bench.plane_step", 10, 50), ("repro.race.epoch.fused", 20, 30),
             ("bench.submit", 60, 70)]
    idle = tr._attribute(gaps, spans)
    assert idle == {"host.other": 35, "bench.plane_step": 30,
                    "repro.race.epoch.fused": 7, "bench.submit": 10}
    assert sum(idle.values()) == sum(e - s for s, e in gaps)


def test_op_names():
    assert tr.op_name("%fused_epoch_pull.5 = f32[2] fusion(x)") == \
        "fused_epoch_pull"
    assert tr.op_name("%broadcast.61.clone = f32[2] broadcast(y)") == \
        "broadcast"
    assert tr.op_name("%while.1 = (s32[]) while((s32[]) %t)") == "while"


def _raw(path):
    """Window, device-op intervals and kernel time, straight from the
    file: a second reading of the trace, apart from the reduction's."""
    from jax.profiler import ProfileData
    window, ops, kernel = None, [], 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.window":
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    lo, hi = window
    inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops
              if e > lo and s < hi]
    covered, end = 0, lo
    for s, e, _ in sorted(inside):
        if e > end:
            covered += e - max(s, end)
            end = e
    kernel = sum(e - s for s, e, n in inside
                 if n.startswith("%fused_epoch_pull"))
    return (hi - lo) / 1e9, covered / 1e9, kernel / 1e9


def test_reduction_of_a_chip_trace():
    r = tr.reduce_trace(DATA)
    window_s, busy_s, kernel_s = _raw(DATA)
    assert r["window_s"] == pytest.approx(window_s)
    assert r["busy_s"] == pytest.approx(busy_s)
    assert tr.kernel_s(r, "fused_epoch_pull") == pytest.approx(kernel_s)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"][0][0] in ("fused_epoch_pull", "convolution",
                                     "fusion")
    # the recorded numbers: busy share 0.467, idle share 0.533
    assert r["window_s"] == pytest.approx(0.030520279)
    assert r["busy_s"] / r["window_s"] == pytest.approx(0.46717, abs=1e-4)
    assert tr.kernel_s(r, "fused_epoch_pull") == pytest.approx(0.01364744)
    idle = dict(r["idle_gaps"])
    # three 3 ms host-only pauses: the device is idle through nearly all
    # of them (the first microseconds of each finish the launch before)
    assert 0.008 < idle["bench.plane_step"] <= 0.009
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


class _Ev:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Trace:
    def __init__(self, planes):
        self.planes = planes


def _chip(n, ops):
    return _Plane(f"/device:TPU:{n}", [_Line("XLA Ops", [
        _Ev(f"%{name}.{i} = f32[2] fusion(x)", s, e)
        for i, (name, s, e) in enumerate(ops)])])


def test_reduction_over_four_chips(monkeypatch):
    """Four chips, their planes out of order: busy time is the mean chip's,
    operation time the sum over the chips, and idle gaps are chip 0's.
    The readers then give the chips' mean idle share and the mean chip's
    roofline share."""
    from jax import profiler

    from bench import spec
    from bench.harness import Run
    busy = {0: [("fused_epoch_pull", 0, 40)],
            1: [("fused_epoch_pull", 10, 30), ("fusion", 60, 70)],
            2: [("fused_epoch_pull", 0, 100)],
            3: []}
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.window", 0, 100), _Ev("repro.race.sync", 40, 80)])])
    planes = [_chip(n, busy[n]) for n in (2, 0, 3, 1)] + [host]
    monkeypatch.setattr(profiler.ProfileData, "from_file",
                        lambda path: _Trace(planes))
    r = tr.reduce_trace(DATA)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((40 + 30 + 100 + 0) / 4 * 1e-9)
    assert r["op_s"]["fused_epoch_pull"] == pytest.approx(160e-9)
    assert r["op_s"]["fusion"] == pytest.approx(10e-9)
    # chip 0 is idle from 40 to 100: 40 under race.sync, 20 under nothing
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"repro.race.sync": 40e-9, "host.other": 20e-9})
    run = Run(workload="w", config={"itemsize": 4}, mix={}, seconds=1.0,
              t0=0.0, t1=1.0, requests=[], setup_s=1.0, peak_bytes=1,
              events=[{"name": "race.epoch",
                       "attrs": {"coord_ops": 819e9 * 80e-9 / 4}}],
              hist={}, trace=r, peaks={"hbm_bytes_per_s": 819e9})
    idle = spec.reader(spec.reader_path("device_idle"))(run)
    assert idle == pytest.approx(100 * (1 - 42.5 / 100))
    roof = spec.reader(spec.reader_path("fused_epoch_pull_roofline.closed"))
    # 80 ns of one chip's bandwidth over 160 ns summed over four chips
    assert roof(run) == pytest.approx(50.0)
