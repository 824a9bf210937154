"""chip_smoke.py rehearsed on the CPU: its phase function at the SMOKE
configuration with the Pallas kernels in interpret mode, its oracle, and
its refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.bmo_nn import SMOKE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phase_interpret_agrees_with_both_oracles(chip_smoke):
    rep = chip_smoke.run(SMOKE, impl="interpret", log=lambda *a: None)
    assert rep["failures"] == []
    assert rep["plane_shed"] == 0
    assert rep["plane_answered"] == rep["plane_certified"] == \
        chip_smoke.N_QUERIES
    for path in ("plane", "query"):
        assert rep[f"{path}_audit_mismatches"] == 0
        assert rep[f"{path}_oracle_mismatches"] == 0
    assert rep["capacity"] == SMOKE.n_points
    assert rep["store_bytes"] == SMOKE.n_points * rep["d_pad"] * 4


def test_oracle_flags_wrong_and_duplicate_rows(chip_smoke):
    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(50, 16)).astype(np.float32)
    queries = rng.normal(size=(3, 16)).astype(np.float32)
    dist = chip_smoke.exact_scan(corpus, queries)
    want = ((queries[:, None, :].astype(np.float64) - corpus[None]) ** 2
            ).sum(-1)
    np.testing.assert_allclose(dist, want, rtol=1e-9, atol=1e-9)
    top = np.argsort(dist, axis=1)[:, :3]
    assert chip_smoke.oracle_mismatches(dist, top, 3) == 0
    wrong = top.copy()
    wrong[0, 0] = np.argsort(dist[0])[-1]       # the farthest row
    wrong[1, 1] = wrong[1, 0]                   # a duplicate
    wrong[2, 2] = -1                            # a missing answer
    assert chip_smoke.oracle_mismatches(dist, wrong, 3) == 3


def test_cli_without_chip_exits_nonzero_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
