"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload tinyimagenet-poisson --seed 7 \\
        --seconds 51 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device`` and, last, ``checks``:
each number compared with the reference beside its limit. The same
numbers are the last lines of standard error. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.

``--control`` puts the bfloat16 reference scan in the program's place: the
run should come out not correct (see ``reference.py``).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# run as a script, this directory would lead sys.path and its modules
# (trace.py) would shadow the standard library's
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _err(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve with the bfloat16 reference scan instead "
                         "of the program (the check's control)")
    args = ap.parse_args(argv)

    from bench import spec
    bench = spec.load(ROOT)
    cell = spec.cell(bench, args.workload)
    config = spec.config(bench, cell["config"], ROOT)
    try:
        spec.check_chips(cell, config)
    except ValueError as e:
        _err(str(e))
        return 2
    mix = spec.traffic(cell["traffic"], ROOT)
    metric_specs = spec.metrics_for(bench, cell["name"], bool(args.trace))
    try:
        from repro.utils.compile_cache import use_compile_cache
    except ImportError as e:
        _err(f"the program under test is missing ({e})")
        return 2

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _err(f"no TPU found (JAX platform is {devs[0].platform!r}); the "
             "benchmark runs on the chip only")
        return 2
    if len(devs) < int(cell["chips"]):
        _err(f"{cell['name']} needs {cell['chips']} chips, found {len(devs)}")
        return 2
    cache = use_compile_cache()
    # keep every program, not only those that took a second to compile:
    # the race's many small epoch steps are most of what a run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _err(f"cell {cell['name']}: config {cell['config']}, traffic "
         f"{cell['traffic']}, seed {args.seed}, seconds {args.seconds}, "
         f"trace {args.trace}; {len(devs)} x {devs[0].device_kind}; "
         f"compile cache {cache}")

    from bench.harness import run_cell
    out = run_cell(cell["name"], config, mix, metric_specs, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   t_start=T_START, control=args.control, log=_err)
    for name, m in out["metrics"].items():
        _err(f"metric {name} {m['value']!r} {m['unit']}")
    _err(f"correct {out['correct']} attempted {out['attempted']} "
         f"failed {out['failed']}")
    for name, c in out["checks"].items():
        _err(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
