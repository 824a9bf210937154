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
    assert win["t1"] == max(r.finished for r in reqs)


@pytest.mark.parametrize("mix,max_group,sizes", [
    (dict(OPEN, plane={"max_group_queries": 8}), 8, [1, 2, 3, 7]),
    (OPEN, 64, [1, 2, 3, 7, 15, 31, 63]),
    ({"loop": "closed", "entry": "plane", "clients": 8}, 64, [1, 2, 3, 7]),
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
