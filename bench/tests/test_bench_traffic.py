"""Open- and closed-loop arithmetic of the traffic generator."""
import time

import numpy as np
import pytest

from bench import traffic as tr
from bench.metrics import latency_p50_ms as p50  # noqa: F401 (import check)

OPEN = {"loop": "open", "entry": "plane", "rows_per_request": 1,
        "arrival": {"process": "poisson", "rate_qps": 40.0}, "drain_s": 5}


def test_arrivals_fixed_count_same_gaps_any_seed():
    """One order seed gives one set of times; another gives the same gaps
    in another order."""
    a = tr.arrivals(OPEN, 5.0, order=0)
    np.testing.assert_array_equal(a, tr.arrivals(OPEN, 5.0, order=0))
    b = tr.arrivals(OPEN, 5.0, order=2**33 + 1)
    assert len(a) == len(b) == 200
    assert np.all(np.diff(a) > 0) and a[-1] < 5.0
    np.testing.assert_allclose(np.sort(np.diff(np.r_[0, a])),
                               np.sort(np.diff(np.r_[0, b])), rtol=1e-9)
    assert not np.array_equal(a, b)


def test_bursts_keep_the_count_inside_on_periods():
    mix = dict(OPEN, arrival=dict(OPEN["arrival"], on_s=1.0, off_s=3.0))
    t = tr.arrivals(mix, 8.0, order=3)
    assert len(t) == 320
    assert np.all(t % 4.0 < 1.0)


def test_every_seed_sends_the_same_pool_in_its_own_order():
    a = tr.query_ids(OPEN, 5.0, order=1)
    np.testing.assert_array_equal(a, tr.query_ids(OPEN, 5.0, order=1))
    b = tr.query_ids(OPEN, 5.0, order=2**33 + 1)
    assert tr.pool_rows(OPEN, 5.0) == len(a) == 200
    np.testing.assert_array_equal(np.sort(a), np.arange(200))
    np.testing.assert_array_equal(np.sort(b), np.arange(200))
    assert not np.array_equal(a, b)
    closed = {"loop": "closed", "entry": "plane", "clients": 4,
              "queries": {"kind": "pool", "size": 64}}
    assert len(tr.query_ids(closed, 51.0, order=3)) == 64
    with pytest.raises(ValueError):
        tr.pool_rows(dict(closed, queries={"kind": "pool"}), 51.0)
    with pytest.raises(ValueError):
        tr.query_ids(dict(closed, queries={"kind": "zipf", "size": 8}),
                     51.0, order=3)


class SlowServer:
    """A blocking entry that takes ``service`` seconds per request."""

    active = 0

    def __init__(self, service):
        self.service = service

    def submit(self, r):
        r.submitted = time.monotonic()
        time.sleep(self.service)
        r.finished = time.monotonic()
        r.certified = np.ones(r.rows, bool)
        r.status = "done"

    def step(self):
        pass

    def poll(self, r):
        return r.status == "pending"


def test_open_loop_charges_lateness_from_the_intended_arrival():
    """A server slower than the arrivals: requests are submitted late, and
    their latency counts the wait from when each was due."""
    mix = dict(OPEN, arrival={"process": "poisson", "rate_qps": 20.0})
    win = tr.run_open(SlowServer(0.1), mix, 1.0, 5,
                      lambda s, n: np.arange(s, s + n))
    reqs = win["requests"]
    assert len(reqs) == 20
    late = np.array([r.submitted - r.intended for r in reqs])
    lat = np.array([r.finished - r.intended for r in reqs])
    service = np.array([r.finished - r.submitted for r in reqs])
    assert late[-1] > 0.5             # the backlog grew to the close
    np.testing.assert_allclose(lat, late + service, atol=1e-6)
    assert np.all(lat >= service - 1e-6)
    assert win["t1"] == pytest.approx(win["t0"] + 1.0)


def test_closed_loop_stops_sending_at_the_close():
    mix = {"loop": "closed", "entry": "plane", "clients": 1,
           "rows_per_request": 2}
    win = tr.run_closed(SlowServer(0.05), mix, 0.5,
                        lambda s, n: np.arange(s, s + n))
    reqs = win["requests"]
    assert 8 <= len(reqs) <= 11
    assert all(r.submitted < win["t0"] + 0.5 for r in reqs)
    # a blocking server answers each request before the loop looks at the
    # clock again: nothing is in flight at the close
    assert win["t1"] >= max(win["t0"] + 0.5, max(r.finished for r in reqs))
    assert win["inflight"] == []


class StepServer:
    """A scheduled entry: each request is answered by the ``steps``-th
    ``step()`` after it was submitted (``steps``, or ``later`` for every
    request after the first ``first``), each step taking at least
    ``step_s``."""

    def __init__(self, steps, step_s=0.01, first=0, later=None):
        self.steps, self.step_s = steps, step_s
        self.first, self.later = first, later
        self.left = {}

    @property
    def active(self):
        return len(self.left)

    def submit(self, r):
        r.submitted = time.monotonic()
        slow = self.later is not None and r.idx >= self.first
        self.left[r.idx] = (self.later if slow else self.steps, r)

    def step(self):
        time.sleep(self.step_s)
        for idx, (n, r) in list(self.left.items()):
            if n > 1:
                self.left[idx] = (n - 1, r)
                continue
            del self.left[idx]
            r.finished = time.monotonic()
            r.certified = np.ones(r.rows, bool)
            r.status = "done"

    def poll(self, r):
        return r.status == "pending"


CLOSED = {"loop": "closed", "entry": "plane", "clients": 3,
          "rows_per_request": 1}


def _two_rounds():
    """Three clients' first requests are answered in 3 steps; their
    second ones need 100 steps, 1 s or more: past a 0.3 s close."""
    return StepServer(steps=3, first=3, later=100)


def test_closed_loop_returns_at_the_close_with_requests_in_flight():
    """The window ends at the close: the loop returns there with every
    client's last request still in flight, and ``drain`` answers those
    afterwards."""
    entry = _two_rounds()
    win = tr.run_closed(entry, CLOSED, 0.3,
                        lambda s, n: np.arange(s, s + n))
    t0, t1, reqs = win["t0"], win["t1"], win["requests"]
    assert t0 + 0.3 <= t1 < t0 + 0.3 + 0.5
    assert len(reqs) == 6
    assert len(win["inflight"]) == 3
    assert all(r.status == "pending" for r in win["inflight"])
    settled = [r for r in reqs if r.status != "pending"]
    assert len(settled) + 3 == len(reqs) and settled
    assert all(r.finished <= t1 for r in settled)
    assert tr.drain(entry, win["inflight"], 5.0) == []
    assert all(r.status == "done" and r.finished > t1
               for r in win["inflight"])


@pytest.mark.parametrize("pause", [0.0, 0.6])
def test_drain_deadline_starts_with_the_drain(pause):
    """A pause between the close and the drain longer than ``drain_s``
    (a traced run's ``stop_trace``) leaves the drain its whole time: every
    request in flight is still answered."""
    entry = StepServer(steps=20)
    win = tr.run_closed(entry, CLOSED, 0.05,
                        lambda s, n: np.arange(s, s + n))
    time.sleep(pause)
    t = time.monotonic()
    assert tr.drain(entry, win["inflight"], 0.5) == []
    assert time.monotonic() - t >= 0.1       # 20 steps were still needed
    assert all(r.status == "done" for r in win["requests"])


def test_drain_gives_up_at_its_deadline():
    entry = StepServer(steps=10 ** 6)
    win = tr.run_closed(entry, CLOSED, 0.05,
                        lambda s, n: np.arange(s, s + n))
    t = time.monotonic()
    left = tr.drain(entry, win["inflight"], 0.2)
    assert 0.2 <= time.monotonic() - t < 1.0
    assert len(left) == 3 and all(r.status == "pending" for r in left)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_qps_is_rows_certified_by_the_close_over_the_window(loop):
    """One definition for both loops: rows certified by the close over
    the window's seconds; a closed loop's drain adds nothing to it."""
    from bench.harness import Run
    from bench.metrics import qps
    qid = lambda s, n: np.arange(s, s + n)   # noqa: E731
    if loop == "open":
        mix = dict(OPEN, arrival={"process": "poisson", "rate_qps": 20.0})
        win = tr.run_open(SlowServer(0.1), mix, 1.0, 5, qid)
        reqs = win["requests"]
    else:
        entry = _two_rounds()
        win = tr.run_closed(entry, CLOSED, 0.3, qid)
        tr.drain(entry, win["inflight"], 5.0)
        reqs = [r for r in win["requests"]
                if all(r is not f for f in win["inflight"])]
    run = Run(workload="w", config={}, mix={"loop": loop}, seconds=0.5,
              t0=win["t0"], t1=win["t1"], requests=reqs, setup_s=1.0,
              peak_bytes=1, events=[], hist={})
    by_close = sum(r.rows for r in reqs
                   if r.finished is not None and r.finished <= win["t1"])
    assert 0 < by_close < len(win["requests"])
    assert qps.read(run) == pytest.approx(by_close / (win["t1"] - win["t0"]))


@pytest.mark.parametrize("mix,max_group,sizes", [
    (dict(OPEN, plane={"max_group_queries": 8}), 8, [1, 2, 3, 7]),
    (OPEN, 64, [1, 2, 3, 7, 15, 31, 63]),
    ({"loop": "closed", "entry": "plane", "clients": 8}, 64, [1, 2, 3, 7]),
    ({"loop": "closed", "entry": "plane", "clients": 10}, 64,
     [1, 2, 3, 7, 15]),
    ({"loop": "closed", "entry": "plane", "clients": 2,
      "rows_per_request": 2}, 64, [2, 3]),
])
def test_warm_sizes_reach_every_group_the_plane_can_form(mix, max_group,
                                                         sizes):
    """Every power-of-two race size up to the plane's largest group (or
    the closed loop's outstanding rows) is warmed, so that a burst forms
    no group that compiles inside the window."""
    from bench.harness import warm_sizes
    from repro.core.datasets import next_pow2
    got = warm_sizes(mix, max_group)
    assert got == sizes
    most = max_group if mix["loop"] == "open" else min(
        max_group, mix["clients"] * mix.get("rows_per_request", 1))
    assert {next_pow2(s) for s in got} == {
        next_pow2(r) for r in range(mix.get("rows_per_request", 1),
                                    most + 1)}
