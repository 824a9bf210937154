"""Find the knee of an open-loop cell: the highest offered rate whose
queue does not grow.

    python3 bench/sweep.py --workload tinyimagenet-poisson --seed 3 \\
        --rates 2,3,4,6 --seconds 20

Builds the cell's index once, warms it, then offers each rate for
``--seconds`` through the cell's own mix and prints, per rate, what
finished inside the window, the latency quantiles from the intended
arrival, and the backlog (arrived minus finished) at each quarter of the
window. A rate whose backlog keeps climbing to the close is past the knee.
Runs on the chip only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated q/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import spec
    from bench import traffic as tr
    from bench.harness import setup
    from repro.utils.compile_cache import use_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 2
    use_compile_cache()
    # as run.py: keep every program, so that the cell's runs find them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = spec.load(ROOT)
    cell = spec.cell(bench, args.workload)
    config = spec.config(bench, cell["config"], ROOT)
    mix = spec.traffic(cell["traffic"], ROOT)
    if mix["loop"] != "open":
        print("sweep: the knee is a property of an open loop", file=sys.stderr)
        return 2
    rates = [float(r) for r in args.rates.split(",")]
    most = dict(mix, arrival=dict(mix["arrival"], rate_qps=max(rates)))
    t = time.monotonic()
    s = setup(config, most, args.seed, args.seconds,
              log=lambda msg: print(f"sweep: {msg}", file=sys.stderr,
                                    flush=True))
    print(f"sweep: set-up {time.monotonic() - t:.3f} s", file=sys.stderr,
          flush=True)
    for i, rate in enumerate(rates):
        # every rate draws from the same query pool (the LRU is bypassed)
        m = dict(mix, arrival=dict(mix["arrival"], rate_qps=rate))
        win = tr.run_open(s.entry, m, args.seconds, args.seed + i, s.qid_of)
        reqs, t0 = win["requests"], win["t0"]
        done = [r for r in reqs if r.status == "done"]
        lat = np.array([(r.finished - r.intended) * 1e3 for r in done])
        quarters = {}
        for q in (0.25, 0.5, 0.75, 1.0):
            t = t0 + q * args.seconds
            arrived = sum(r.intended <= t for r in reqs)
            finished = sum(r.finished is not None and r.finished <= t
                           for r in reqs)
            quarters[f"{q:g}"] = arrived - finished
        in_window = sum(r.finished <= t0 + args.seconds for r in done)
        print(json.dumps({
            "rate_qps": rate, "offered": len(reqs), "done": len(done),
            "shed": sum(r.status == "shed" for r in reqs),
            "finished_in_window_qps": in_window / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
            "p95_ms": float(np.percentile(lat, 95)) if lat.size else None,
            "backlog_at_quarters": quarters,
            "drained_s": win["end"] - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
