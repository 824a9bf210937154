"""Pallas kernels of the race's hot loop (block pulls, the exact scan) and
their jit'd dispatchers (ops.py) and jnp oracles (ref.py)."""

#: kernel implementations a caller may ask for (``QuerySpec.impl`` and every
#: ``repro.kernels.ops`` dispatcher take exactly these)
IMPLS = ("auto", "kernel", "interpret", "ref")
