"""Benchmark harness — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).

  PYTHONPATH=src python -m benchmarks.run            # all figures
  PYTHONPATH=src python -m benchmarks.run fig2 fig5  # subset
"""
from __future__ import annotations

import sys
import time
import traceback

BENCHES = ["fig2", "fig3a", "fig4a", "fig4b", "fig5", "fig6", "fig7",
           "fig8", "fig9", "roofline"]


def main() -> None:
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    want = sys.argv[1:] or BENCHES
    print("name,us_per_call,derived")
    for name in want:
        mod_name = {
            "fig2": "benchmarks.fig2_gain_vs_d",
            "fig3a": "benchmarks.fig3a_gain_vs_n",
            "fig4a": "benchmarks.fig4a_adaptivity",
            "fig4b": "benchmarks.fig4b_sparse",
            "fig5": "benchmarks.fig5_kmeans",
            "fig6": "benchmarks.fig6_wallclock",
            "fig7": "benchmarks.fig7_rotation",
            "fig8": "benchmarks.fig8_batched_serve",
            "fig9": "benchmarks.fig9_serve_plane",
            "roofline": "benchmarks.roofline_table",
        }[name]
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
            print(f"{name}_total,{(time.time() - t0) * 1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name}_total,{(time.time() - t0) * 1e6:.0f},ERROR:{type(e).__name__}")


if __name__ == "__main__":
    main()
