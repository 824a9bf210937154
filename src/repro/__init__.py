"""repro — Bandit-Based Monte Carlo Optimization for Nearest Neighbors,
built as a multi-pod JAX training/serving framework. See README.md."""

__version__ = "0.1.0"

# Nothing here imports jax: repro.analysis and tools/repro_lint.py are pure
# stdlib by design, and the CI lint job runs them without jax installed.
