"""repro.analysis — the invariant lint engine (DESIGN.md §12).

Each rule gets fixture-snippet positive/negative cases; the engine gets
suppression + ratchet-baseline semantics (new fails, baselined passes,
stale warns, fingerprints survive line shifts); the CLI gets JSON-schema
and exit-code checks; and the δ ledger gets the regression that pins the
set of sanctioned split sites in the real tree — adding a δ split
without registering it in an accounting helper breaks this test before
it breaks the proof.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (LintEngine, apply_baseline, baseline_from,
                            default_rules, load_baseline, save_baseline)
from repro.analysis.rules_delta import DeltaLedgerRule
from repro.analysis.rules_fence import EpochFenceRule
from repro.analysis.rules_hostsync import HostSyncRule
from repro.analysis.rules_metrics import MetricsConformanceRule
from repro.analysis.rules_pallas import PallasBudgetRule
from repro.analysis.rules_recompile import RecompileHazardRule

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_snippet(tmp_path, rules, source, rel="src/repro/serve/plane.py",
                baseline=None, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return LintEngine(rules).run([(str(p), rel)], baseline or {})


def rule_names(report):
    return [f.rule for f in report.findings]


# -- delta-ledger ------------------------------------------------------------

class TestDeltaLedger:
    def test_raw_delta_arithmetic_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [DeltaLedgerRule()], """
            def f(cfg, S):
                return cfg.delta / S
            """, rel="src/repro/index/foo.py")
        assert rule_names(rep) == ["delta-ledger"]
        assert "accounting" in rep.findings[0].message or \
            "ledger" in rep.findings[0].message

    def test_helper_call_clean_and_ledgered(self, tmp_path):
        rep = run_snippet(tmp_path, [DeltaLedgerRule()], """
            def f(cfg, n, mp):
                return delta_prime(cfg.delta, n, mp)
            """, rel="src/repro/index/foo.py")
        assert rep.findings == []
        assert rep.ledger == [{"helper": "delta_prime",
                               "path": "src/repro/index/foo.py",
                               "line": 3, "function": "f"}]

    def test_ledger_home_may_do_raw_arithmetic(self, tmp_path):
        rep = run_snippet(tmp_path, [DeltaLedgerRule()], """
            def delta_prime(delta, n, mp):
                return delta / (n * mp)

            def shard_delta(cfg, S):
                return cfg.delta / S
            """, rel="src/repro/core/confidence.py")
        assert rep.findings == []

    def test_literal_delta_at_ci_call_site_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [DeltaLedgerRule()], """
            def f(n, mp):
                a = delta_prime(0.05, n, mp)
                b = shard_delta(delta=0.1, shards=4)
                return a + b
            """, rel="src/repro/index/foo.py")
        assert rule_names(rep) == ["delta-ledger"] * 2
        assert "0.05" in rep.findings[0].message

    def test_inlined_log_confidence_term_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [DeltaLedgerRule()], """
            import numpy as np
            def f():
                return np.log(2.0 / 0.05)
            """, rel="src/repro/index/foo.py")
        assert rule_names(rep) == ["delta-ledger"]

    def test_welford_local_delta_not_flagged(self, tmp_path):
        # a bare local named `delta` (Welford updates) is not a budget
        rep = run_snippet(tmp_path, [DeltaLedgerRule()], """
            def welford(mean, b_mean, count):
                delta = b_mean - mean
                return mean + delta * count
            """, rel="src/repro/kernels/foo.py")
        assert rep.findings == []


# -- epoch-fence -------------------------------------------------------------

class TestEpochFence:
    def test_unfenced_store_swap_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [EpochFenceRule()], """
            class Index:
                def retune(self, new):
                    self._store = new
            """, rel="src/repro/api/handle.py")
        assert rule_names(rep) == ["epoch-fence"]
        assert "'retune'" in rep.findings[0].message

    def test_init_and_swap_are_fenced(self, tmp_path):
        rep = run_snippet(tmp_path, [EpochFenceRule()], """
            class Index:
                def __init__(self, store):
                    self._store = store
                    self._epoch = 0

                def _swap(self, new):
                    self._store = new
                    self._epoch += 1
            """, rel="src/repro/api/handle.py")
        assert rep.findings == []

    def test_swap_without_epoch_bump_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [EpochFenceRule()], """
            class Index:
                def _swap_quiet(self, new):
                    self._store = new
            """, rel="src/repro/api/handle.py")
        assert rule_names(rep) == ["epoch-fence"]
        assert "never bumps _epoch" in rep.findings[0].message

    def test_allow_comment_suppresses(self, tmp_path):
        rep = run_snippet(tmp_path, [EpochFenceRule()], """
            class Index:
                def _load(self, new):
                    self._store = new  # repro-lint: allow[epoch-fence]
            """, rel="src/repro/api/handle.py")
        assert rep.findings == [] and rep.suppressed == 1


# -- host-sync ---------------------------------------------------------------

class TestHostSync:
    def test_sync_in_hot_function_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [HostSyncRule()], """
            import numpy as np
            class Plane:
                def _harvest(self, snap):
                    return np.asarray(snap.done)
            """)
        assert rule_names(rep) == ["host-sync"]

    def test_annotation_and_helper_pass(self, tmp_path):
        rep = run_snippet(tmp_path, [HostSyncRule()], """
            import numpy as np
            class Plane:
                def _harvest(self, snap, dev):
                    a = np.asarray(snap.done)  # host-sync: numpy snapshot
                    b = host_fetch(dev)
                    c = float(np.sum(host_fetch(dev)))
                    return a, b, c
            """)
        assert rep.findings == []

    def test_annotation_on_line_above_statement(self, tmp_path):
        rep = run_snippet(tmp_path, [HostSyncRule()], """
            import numpy as np
            class Plane:
                def _harvest(self, snap):
                    # host-sync: post-boundary numpy
                    worst = float(np.where(snap.ok, snap.ci,
                                           0.0).max())
                    return worst
            """)
        assert rep.findings == []

    def test_cold_functions_unconstrained(self, tmp_path):
        rep = run_snippet(tmp_path, [HostSyncRule()], """
            import numpy as np
            def build(x):
                return np.asarray(x).item()
            """)
        assert rep.findings == []

    def test_non_hot_file_unconstrained(self, tmp_path):
        rep = run_snippet(tmp_path, [HostSyncRule()], """
            import numpy as np
            class Plane:
                def _harvest(self, snap):
                    return np.asarray(snap.done)
            """, rel="src/repro/api/handle.py")
        assert rep.findings == []


# -- recompile-hazard --------------------------------------------------------

class TestRecompileHazard:
    def test_per_call_jit_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [RecompileHazardRule()], """
            import jax
            def serve(f, x):
                return jax.jit(f)(x)
            """, rel="src/repro/api/handle.py")
        assert rule_names(rep) == ["recompile-hazard"]

    def test_module_level_init_and_cached_factory_pass(self, tmp_path):
        rep = run_snippet(tmp_path, [RecompileHazardRule()], """
            import functools
            import jax

            g = jax.jit(lambda x: x)

            class Box:
                def __init__(self, f):
                    self.f = jax.jit(f)

            @functools.lru_cache(maxsize=None)
            def make(f):
                return jax.jit(f)
            """, rel="src/repro/api/handle.py")
        assert rep.findings == []

    def test_unhashable_static_default_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [RecompileHazardRule()], """
            import jax
            def f(x, opts=[1, 2]):
                return x
            g = jax.jit(f, static_argnames=("opts",))
            """, rel="src/repro/api/handle.py")
        assert rule_names(rep) == ["recompile-hazard"]
        assert "unhashable" in rep.findings[0].message

    def test_partial_jit_decorator_static_default_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [RecompileHazardRule()], """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("opts",))
            def f(x, opts={}):
                return x
            """, rel="src/repro/api/handle.py")
        assert rule_names(rep) == ["recompile-hazard"]

    def test_len_shape_in_pow2_file_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [RecompileHazardRule()], """
            import jax.numpy as jnp
            def pack(rows):
                return jnp.zeros((len(rows), 4))
            """, rel="src/repro/index/frontier.py")
        assert rule_names(rep) == ["recompile-hazard"]
        assert "pow2" in rep.findings[0].message

    def test_pow2_laundered_len_passes(self, tmp_path):
        rep = run_snippet(tmp_path, [RecompileHazardRule()], """
            import jax.numpy as jnp
            def pack(rows):
                return jnp.zeros((next_pow2(len(rows)), 4))
            """, rel="src/repro/index/frontier.py")
        assert rep.findings == []

    def test_len_shape_outside_pow2_files_unconstrained(self, tmp_path):
        rep = run_snippet(tmp_path, [RecompileHazardRule()], """
            import jax.numpy as jnp
            def pack(rows):
                return jnp.zeros((len(rows), 4))
            """, rel="src/repro/launch/train.py")
        assert rep.findings == []


# -- metrics-conformance -----------------------------------------------------

class TestMetricsConformance:
    def test_name_and_suffix_rules(self, tmp_path):
        rep = run_snippet(tmp_path, [MetricsConformanceRule()], """
            def wire(reg):
                reg.counter("plane_submitted_total", "no prefix")
                reg.counter("repro_plane_submitted", "counter, no _total")
                reg.gauge("repro_queue_total", "gauge with _total")
                reg.histogram("repro_Plane_ms", "uppercase")
            """, rel="src/repro/obs/foo.py")
        msgs = " ".join(f.message for f in rep.findings)
        assert len(rep.findings) == 4
        assert "_total" in msgs and "repro_" in msgs

    def test_label_vocabulary(self, tmp_path):
        rep = run_snippet(tmp_path, [MetricsConformanceRule()], """
            def wire(reg, lbl):
                reg.counter("repro_x_total", "ok", kind="a", plane="p0")
                reg.counter("repro_y_total", "bad", namepsace="oops")
                reg.histogram("repro_z_ms", "ok", buckets=(1, 2), **lbl)
            """, rel="src/repro/obs/foo.py")
        assert rule_names(rep) == ["metrics-conformance"]
        assert "namepsace" in rep.findings[0].message

    def test_dynamic_name_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [MetricsConformanceRule()], """
            def wire(reg, which):
                reg.counter(f"repro_{which}_total", "dynamic")
            """, rel="src/repro/obs/foo.py")
        assert rule_names(rep) == ["metrics-conformance"]
        assert "dynamic" in rep.findings[0].message

    def test_cross_file_kind_conflict(self, tmp_path):
        rule = MetricsConformanceRule()
        a = tmp_path / "a.py"
        a.write_text("def f(reg):\n    reg.gauge('repro_thing')\n")
        b = tmp_path / "b.py"
        b.write_text("def g(reg):\n"
                     "    reg.histogram('repro_thing')\n")
        rep = LintEngine([rule]).run(
            [(str(a), "src/repro/a.py"), (str(b), "src/repro/b.py")], {})
        conflicts = [f for f in rep.findings if "conflicting" in f.message]
        assert len(conflicts) == 1
        assert "src/repro/a.py" in conflicts[0].message
        assert "src/repro/b.py" in conflicts[0].message

    def test_non_registry_receivers_ignored(self, tmp_path):
        rep = run_snippet(tmp_path, [MetricsConformanceRule()], """
            def f(db):
                db.counter("whatever")      # not a metrics registry
            """, rel="src/repro/obs/foo.py")
        assert rep.findings == []


# -- pallas-budget -----------------------------------------------------------

_KERNEL_HEADER = textwrap.dedent("""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
""")


def kernel_snippet(body):
    # header and body carry different source indents; dedent each alone
    return _KERNEL_HEADER + textwrap.dedent(body)


class TestPallasBudget:
    def test_over_budget_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [PallasBudgetRule()], kernel_snippet("""
            def launch(kern, x):
                return pl.pallas_call(
                    kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((2048, 2048),
                                           lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                )(x)
            """), rel="src/repro/kernels/foo.py")
        assert any("exceeds" in f.message for f in rep.findings)

    def test_within_budget_passes(self, tmp_path):
        rep = run_snippet(tmp_path, [PallasBudgetRule()], kernel_snippet("""
            def launch(kern, x, n_buf, block):
                return pl.pallas_call(
                    kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                              pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                    scratch_shapes=[pltpu.VMEM((n_buf, 1, block),
                                               jnp.float32)],
                )(x)
            """), rel="src/repro/kernels/foo.py")
        assert rep.findings == []

    def test_unpriceable_symbolic_dim_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [PallasBudgetRule()], kernel_snippet("""
            def launch(kern, x, mystery):
                return pl.pallas_call(
                    kern,
                    in_specs=[pl.BlockSpec((8, mystery),
                                           lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                )(x)
            """), rel="src/repro/kernels/foo.py")
        assert any("unpriceable" in f.message for f in rep.findings)

    def test_lane_misalignment_flagged(self, tmp_path):
        rep = run_snippet(tmp_path, [PallasBudgetRule()], kernel_snippet("""
            def launch(kern, x):
                return pl.pallas_call(
                    kern,
                    in_specs=[pl.BlockSpec((8, 200), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                )(x)
            """), rel="src/repro/kernels/foo.py")
        assert any("lane" in f.message for f in rep.findings)

    def test_strided_ds_needs_divisibility_guard(self, tmp_path):
        body = kernel_snippet("""
            def kern(x_ref, o_ref, *, block):
                blk = 3
                o_ref[...] = x_ref[pl.ds(blk * block, block)]

            def launch(x, block):
                {guard}
                return pl.pallas_call(
                    kern,
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                )(x)
            """)
        rep = run_snippet(
            tmp_path, [PallasBudgetRule()],
            body.format(guard="pass"), rel="src/repro/kernels/foo.py")
        assert any("divisibility" in f.message for f in rep.findings)
        rep = run_snippet(
            tmp_path, [PallasBudgetRule()],
            body.format(guard="assert x.shape[1] % block == 0"),
            rel="src/repro/kernels/foo.py")
        assert rep.findings == []

    def test_real_kernels_fit_budget(self):
        """The ISSUE's target kernels must lint clean (their symbolic dims
        are priced by DIM_BOUNDS and their strides carry guards)."""
        files = [os.path.join(REPO, "src", "repro", "kernels",
                              "block_pull.py")]
        rep = LintEngine([PallasBudgetRule()]).run(
            [(p, os.path.relpath(p, REPO)) for p in files], {})
        assert rep.findings == []


# -- engine: suppression + ratchet semantics ---------------------------------

class TestEngine:
    def test_standalone_allow_comment_suppresses_next_line(self, tmp_path):
        rep = run_snippet(tmp_path, [EpochFenceRule()], """
            class Index:
                def _load(self, new):
                    # repro-lint: allow[epoch-fence]
                    self._store = new
            """, rel="src/repro/api/handle.py")
        assert rep.findings == [] and rep.suppressed == 1

    def test_wildcard_allow(self, tmp_path):
        rep = run_snippet(tmp_path, [EpochFenceRule()], """
            class Index:
                def _load(self, new):
                    self._store = new  # repro-lint: allow[*]
            """, rel="src/repro/api/handle.py")
        assert rep.suppressed == 1

    def test_ratchet_new_vs_baselined_vs_stale(self, tmp_path):
        src = """
            class Index:
                def a(self, new):
                    self._store = new
                def b(self, new):
                    self._store = new
            """
        rep0 = run_snippet(tmp_path, [EpochFenceRule()], src,
                           rel="src/repro/api/handle.py")
        assert len(rep0.new) == 2 and rep0.ok is False
        base = baseline_from(rep0.findings)
        base["epoch-fence|src/repro/api/handle.py|gone"] = 1  # stale entry
        rep1 = run_snippet(tmp_path, [EpochFenceRule()], src,
                           rel="src/repro/api/handle.py", baseline=base)
        assert rep1.ok and rep1.new == [] and len(rep1.baselined) == 2
        assert rep1.stale == ["epoch-fence|src/repro/api/handle.py|gone"]
        # a THIRD identical violation exceeds the frozen budget -> new
        rep2 = run_snippet(tmp_path, [EpochFenceRule()], src + """
                def c(self, new):
                    self._store = new
            """, rel="src/repro/api/handle.py", baseline=base)
        assert len(rep2.new) == 1 and rep2.ok is False

    def test_fingerprints_survive_line_shifts(self, tmp_path):
        src = """
            class Index:
                def a(self, new):
                    self._store = new
            """
        rep0 = run_snippet(tmp_path, [EpochFenceRule()], src,
                           rel="src/repro/api/handle.py")
        base = baseline_from(rep0.findings)
        shifted = "\n\n\n# pushed down\n" + textwrap.dedent(src)
        p = tmp_path / "shifted.py"
        p.write_text(shifted)
        rep1 = LintEngine([EpochFenceRule()]).run(
            [(str(p), "src/repro/api/handle.py")], base)
        assert rep1.ok and len(rep1.baselined) == 1

    def test_unparseable_file_is_an_error_not_a_crash(self, tmp_path):
        p = tmp_path / "broken.py"
        p.write_text("def f(:\n")
        rep = LintEngine(default_rules()).run(
            [(str(p), "src/repro/broken.py")], {})
        assert rep.errors and not rep.ok

    def test_duplicate_rule_names_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            LintEngine([EpochFenceRule(), EpochFenceRule()])

    def test_baseline_round_trip_and_version_gate(self, tmp_path):
        path = str(tmp_path / "base.json")
        save_baseline(path, {"b|p|s": 2, "a|p|s": 1})
        assert load_baseline(path) == {"a|p|s": 1, "b|p|s": 2}
        doc = json.load(open(path))
        doc["version"] = 99
        json.dump(doc, open(path, "w"))
        with pytest.raises(ValueError, match="version"):
            load_baseline(path)

    def test_apply_baseline_counts(self):
        from repro.analysis.engine import Finding
        f = lambda: Finding("r", "p", 1, 0, "m", "snip")
        new, old, stale = apply_baseline([f(), f(), f()], {"r|p|snip": 2})
        assert (len(new), len(old), stale) == (1, 2, [])


# -- CLI ---------------------------------------------------------------------

class TestCLI:
    def run_cli(self, *args, cwd=REPO):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "repro_lint.py"),
             *args], capture_output=True, text=True, cwd=cwd)

    def test_repo_is_clean_against_committed_baseline(self):
        r = self.run_cli()
        assert r.returncode == 0, r.stdout + r.stderr

    def test_json_report_schema(self, tmp_path):
        out = str(tmp_path / "report.json")
        r = self.run_cli("--json", out)
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.load(open(out))
        assert doc["version"] == 1
        assert set(doc["counts"]) == {"total", "new", "baselined",
                                      "suppressed", "stale"}
        assert doc["ok"] is True and doc["counts"]["new"] == 0
        for f in doc["findings"]:
            assert set(f) == {"rule", "path", "line", "col", "message",
                              "snippet", "status"}
            assert f["status"] in ("new", "baselined")
        assert isinstance(doc["ledger"], list) and doc["ledger"]
        assert doc["errors"] == []

    def test_new_finding_fails_without_baseline(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("class I:\n"
                       "    def f(self, new):\n"
                       "        self._store = new\n")
        r = self.run_cli("--no-baseline", str(bad))
        assert r.returncode == 1
        assert "epoch-fence" in r.stdout

    def test_baseline_update_then_clean(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("class I:\n"
                       "    def f(self, new):\n"
                       "        self._store = new\n")
        base = str(tmp_path / "base.json")
        r = self.run_cli("--baseline", base, "--baseline-update", str(bad))
        assert r.returncode == 0, r.stdout + r.stderr
        r = self.run_cli("--baseline", base, str(bad))
        assert r.returncode == 0
        assert "[baselined]" in r.stdout

    def test_syntax_error_exits_2(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def f(:\n")
        r = self.run_cli("--no-baseline", str(bad))
        assert r.returncode == 2
        assert "error" in r.stderr.lower()


# -- the δ-split ledger regression (satellite: every split enumerable) -------

class TestDeltaSplitLedger:
    def test_ledger_enumerates_every_split_site(self):
        """The machine-generated δ-split table over the REAL tree: one
        entry per sanctioned accounting-helper call site. A new δ split
        must show up here (i.e. go through delta_prime/shard_delta) —
        and a removed one must be deleted — before the proof composes."""
        src = os.path.join(REPO, "src", "repro")
        files = []
        for dirpath, dirnames, filenames in os.walk(src):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    p = os.path.join(dirpath, fn)
                    files.append((p, os.path.relpath(p, REPO)))
        rep = LintEngine([DeltaLedgerRule()]).run(files, {})
        sites = {(row["helper"], row["path"], row["function"])
                 for row in rep.ledger}
        assert sites == {
            ("delta_prime", "src/repro/core/ucb.py", "race_topk"),
            ("delta_prime", "src/repro/index/anytime.py", "__init__"),
            ("delta_prime", "src/repro/index/batched_race.py",
             "make_rounds_race"),
            ("delta_prime", "src/repro/index/batched_race.py",
             "fused_race_topk"),
            ("delta_prime", "src/repro/index/sharded.py",
             "_sharded_fused_race"),
            ("shard_delta", "src/repro/index/sharded.py", "_shard_delta"),
            ("shard_delta", "src/repro/core/distributed.py",
             "distributed_knn"),
        }
        # and the tree is free of raw δ arithmetic outside the ledger home
        assert [f for f in rep.findings] == []
