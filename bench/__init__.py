"""The chip benchmark: one cell of ``BENCHMARK.json`` per run (``run.py``).

Everything a cell needs is found by name: its configuration in
``configs/<name>.json``, its traffic mix in ``traffic/<name>.json`` and each
per-layer metric's reader in ``metrics/<name>.py``.
"""
