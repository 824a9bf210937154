"""The harness over a sharded index: the rounds a sharded session can
reach, and (``sharded_probe.py``, on four virtual CPU devices) a whole run
through ``Index.build(shards=4)``, ``RequestPlane`` and
``ShardedFusedSession``: correct, nothing compiled in the window, the
shard readers' numbers, and not correct with the control or the program
broken underneath the timed path."""
import json
import os
import subprocess
import sys

import pytest

from bench import spec
from bench.entries import reachable_rounds

# the dense cells' session: R0 4, R_cap 64 (128 blocks, 2 pulls a round),
# W0 131072 slots a shard, floor 32
DENSE = dict(R0=4, R_cap=64, W0=131072, floor=32)


def _widths(W0, floor):
    w = W0 // 2
    while w >= floor:
        yield w
        w //= 2


def test_below_w0_a_sharded_session_reaches_the_one_chip_chain():
    d = DENSE
    for w in _widths(d["W0"], d["floor"]):
        one = reachable_rounds(d["R0"], d["R_cap"], d["W0"], w,
                               floor=d["floor"])
        four = reachable_rounds(d["R0"], d["R_cap"], d["W0"], w,
                                floor=d["floor"], shards=4)
        assert four == one, w
        assert all(r == d["R_cap"] or r // d["R0"] & (r // d["R0"] - 1) == 0
                   for r in one)


def test_at_w0_four_shards_reach_up_to_four_times_r0():
    """A sharded frontier stays at W0 while one shard keeps over half its
    arms, and the others may keep few: the summed need is then over W0/2
    and under 4·W0, so R reaches R0, 2·R0 and 4·R0. One chip reaches R0."""
    d = DENSE
    args = (d["R0"], d["R_cap"], d["W0"], d["W0"])
    assert reachable_rounds(*args, floor=d["floor"]) == [4]
    assert reachable_rounds(*args, floor=d["floor"], shards=4) == [4, 8, 16]


def test_reachable_rounds_are_every_value_the_rule_gives():
    """Against the session's rule evaluated at every need, on a small
    frontier."""
    from repro.index.frontier import pow2_floor
    R0, R_cap, W0, floor = 2, 64, 256, 16
    for S in (1, 3, 4):
        for w in [W0, *_widths(W0, floor)]:
            lo = W0 // 2 + 1 if w == W0 else 1
            want = sorted({min(R0 * pow2_floor(S * W0 // t), R_cap)
                           for t in range(lo, S * w + 1)})
            assert reachable_rounds(R0, R_cap, W0, w, floor=floor,
                                    shards=S) == want, (S, w)


@pytest.fixture(scope="module")
def probe():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(spec.ROOT, "src"), spec.ROOT]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.sharded_probe"],
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_open_loop_is_correct(probe):
    out = probe["sound"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 4


def test_sharded_window_compiles_nothing(probe):
    assert probe["sound"]["window_compiles"] == 0


def test_sharded_races_after_set_up_compile_nothing(probe):
    assert probe["warm_compiles"] == 0


def test_sharded_build_draws_the_corpus_to_the_host_once(probe):
    assert probe["host_corpus_calls"] == 1


def test_shard_readers_read_the_sharded_epochs(probe):
    m = probe["sound"]["metrics"]
    assert m["shard_straggle.shard4"]["value"] >= 1.0
    assert m["epoch_fetches.shard4"]["value"] == 2.0


def test_sharded_bf16_control_is_not_correct(probe):
    assert not probe["control"]["correct"], probe["control"]["checks"]


@pytest.mark.parametrize("fault", ["unchanged_step", "half_batch",
                                   "merge_left_out", "altered_answer"])
def test_sharded_broken_program_is_not_correct(probe, fault):
    assert not probe[fault]["correct"], probe[fault]["checks"]
