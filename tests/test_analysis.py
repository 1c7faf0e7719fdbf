"""Unit tests for the static-analysis package (repro.analysis).

One positive + one negative (or suppressed) case per simlint rule, the
coherence rules, suppression/baseline plumbing, the jaxpr kernel audit,
and the acceptance pin: the shipped ``src/repro`` tree lints clean with
zero baseline entries.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import RULES, Baseline, collect_files, run_analysis
from repro.analysis.coherence import lint_coherence
from repro.analysis.findings import (Finding, inline_suppressions,
                                     is_inline_suppressed)
from repro.analysis.simlint import lint_source

SRC_ROOT = Path(__file__).resolve().parents[1] / "src"


def rules_of(source: str, path: str = "repro/core/x.py") -> list[str]:
    src = textwrap.dedent(source)
    return [f.rule for f in lint_source(src, path) + lint_coherence(src, path)]


# -- SL001: set iteration ---------------------------------------------------

def test_sl001_flags_set_iteration():
    assert "SL001" in rules_of("""
        def f(s: set[int]):
            for x in s:
                print(x)
    """)


def test_sl001_flags_self_attr_set():
    assert "SL001" in rules_of("""
        class C:
            def __init__(self):
                self.pending: set[int] = set()
            def drain(self):
                return [x for x in self.pending]
    """)


def test_sl001_flags_set_returning_method():
    assert "SL001" in rules_of("""
        def f(catalog, lfn):
            return [h + 1 for h in catalog.holders(lfn)]
    """)


def test_sl001_sorted_wrap_is_clean():
    assert rules_of("""
        def f(s: set[int]):
            for x in sorted(s):
                print(x)
    """) == []


def test_sl001_order_free_consumers_are_clean():
    assert rules_of("""
        def f(s: set[int]):
            return any(x > 0 for x in s), len(s), set(s), bool(s)
    """) == []


def test_sl001_min_max_over_set_flagged():
    # conservative: min/max key-function ties resolve in encounter order
    assert "SL001" in rules_of("""
        def f(s: set[int], cost):
            return min(s, key=cost)
    """)


def test_sl001_dict_iteration_is_clean():
    assert rules_of("""
        def f(d: dict[int, str]):
            for k in d:
                print(k)
    """) == []


# -- SL002: global / unseeded PRNG ------------------------------------------

def test_sl002_flags_global_random():
    assert "SL002" in rules_of("""
        import random
        def f():
            return random.random()
    """)


def test_sl002_flags_np_random_module(tmp_path):
    assert "SL002" in rules_of("""
        import numpy as np
        def f():
            return np.random.rand(3)
    """)


def test_sl002_seeded_instances_are_clean():
    assert rules_of("""
        import random as _random
        import numpy as np
        def f(seed):
            rng = _random.Random(seed)
            g = np.random.default_rng(seed)
            return rng.random(), g.random()
    """) == []


# -- SL003: float reduction over unordered containers -----------------------

def test_sl003_flags_sum_over_set():
    assert "SL003" in rules_of("""
        def f(s: set[float]):
            return sum(s)
    """)


def test_sl003_sum_over_sorted_is_clean():
    assert rules_of("""
        def f(s: set[float]):
            return sum(sorted(s))
    """) == []


# -- SL004: id()/hash() in sort keys ----------------------------------------

def test_sl004_flags_id_in_sort_key():
    assert "SL004" in rules_of("""
        def f(items):
            return sorted(items, key=lambda j: id(j))
    """)


def test_sl004_domain_key_is_clean():
    assert rules_of("""
        def f(items):
            return sorted(items, key=lambda j: j.job_id)
    """) == []


# -- SL005: wall-clock reads in sim-state code ------------------------------

def test_sl005_flags_wall_clock_in_core():
    assert "SL005" in rules_of("""
        import time
        def f():
            return time.time()
    """, path="repro/core/x.py")


def test_sl005_scope_excludes_fault_instrumentation():
    # perf_counter in repro/fault is host-side instrumentation, not sim state
    assert "SL005" not in rules_of("""
        import time
        def f():
            return time.perf_counter()
    """, path="repro/fault/failures.py")


def test_sl005_obs_package_is_the_sanctioned_exemption():
    # the telemetry probe measures host phase time by design; SL014
    # guards the other direction (it cannot write engine state)
    src = """
        import time
        def f():
            return time.perf_counter()
    """
    assert "SL005" not in rules_of(src, path="repro/obs/probe.py")
    assert "SL005" in rules_of(src, path="repro/core/simulator.py")


# -- SL010: heappush tie key ------------------------------------------------

def test_sl010_flags_missing_seq_key():
    assert "SL010" in rules_of("""
        import heapq
        def f(q, t):
            heapq.heappush(q, (t, "payload"))
    """)


def test_sl010_seq_key_is_clean():
    assert rules_of("""
        import heapq
        def f(q, t, kind):
            self_seq = 0
            heapq.heappush(q, (t, self_seq, kind, None))
    """) == []


# -- SL011: catalog bypass --------------------------------------------------

def test_sl011_flags_holders_access_outside_catalog():
    assert "SL011" in rules_of("""
        def f(cat):
            cat._holders["lfn"].add(3)
    """)


def test_sl011_flags_notify_less_mutation_inside_catalog():
    assert "SL011" in rules_of("""
        class ReplicaCatalog:
            def _notify(self, *a): ...
            def silent_add(self, lfn, site):
                self._holders[lfn].add(site)
    """, path="repro/core/catalog.py")


def test_sl011_notifying_mutation_is_clean():
    assert rules_of("""
        class ReplicaCatalog:
            def _notify(self, *a): ...
            def add_replica(self, lfn, site):
                self._holders[lfn].add(site)
                self._notify("on_add_replica", lfn, site)
    """, path="repro/core/catalog.py") == []


# -- SL012: sync coherence --------------------------------------------------

_SYNC_CLASS = """
    class Mirror:
        def __init__(self, catalog):
            self.catalog = catalog
            self._n = 0
        def sync(self):
            self._table = dict(self.catalog.files)
            self._n = len(self._table)
        def {sig}:
            {body}
"""


def test_sl012_flags_unsynced_read():
    src = _SYNC_CLASS.format(sig="lookup(self, k)",
                             body="return self._table[k]")
    assert "SL012" in rules_of(src)


def test_sl012_synced_read_is_clean():
    src = _SYNC_CLASS.format(
        sig="lookup(self, k)",
        body="self.sync()\n            return self._table[k]")
    assert rules_of(src) == []


def test_sl012_private_and_listener_hooks_exempt():
    for sig in ("_peek(self, k)", "on_add_replica(self, k)"):
        src = _SYNC_CLASS.format(sig=sig, body="return self._table[k]")
        assert "SL012" not in rules_of(src), sig


def test_sl012_transitive_sync_counts():
    # calling a helper that itself syncs satisfies the rule
    src = textwrap.dedent("""
        class Mirror:
            def sync(self):
                self._table = {}
            def _fresh(self):
                self.sync()
                return self._table
            def lookup(self, k):
                return self._fresh()[k]
    """)
    assert "SL012" not in rules_of(src)


# -- SL014: obs callbacks are observation-only ------------------------------

def test_sl014_flags_mutating_call_on_parameter():
    assert "SL014" in rules_of("""
        class Sampler:
            def sample(self, sim):
                sim.records.append(1)
    """, path="repro/obs/series.py")


def test_sl014_flags_write_through_parameter():
    src = """
        def f(sim):
            sim.now = 0.0
            sim.network.link_act[0] += 1.0
            del sim._cpu_queue[0]
    """
    rules = rules_of(src, path="repro/obs/series.py")
    assert rules.count("SL014") == 3


def test_sl014_reads_and_self_mutation_are_clean():
    assert rules_of("""
        class Sampler:
            def sample(self, sim):
                n = len(sim.records) + sim.network.n_active
                self.ring.append((sim.now, float(n)))
    """, path="repro/obs/series.py") == []


def test_sl014_scoped_to_obs_package():
    # the same mutation outside repro/obs/ is not SL014's business
    assert "SL014" not in rules_of("""
        def f(sim):
            sim.records.append(1)
    """, path="repro/core/simulator.py")


# -- suppressions + baseline ------------------------------------------------

def test_inline_same_line_suppression():
    assert rules_of("""
        def f(s: set[int]):
            for x in s:  # simlint: disable=SL001
                print(x)
    """) != []  # lint_source itself still reports ...
    supp = inline_suppressions(textwrap.dedent("""
        def f(s: set[int]):
            for x in s:  # simlint: disable=SL001
                print(x)
    """))
    f = Finding("SL001", "p.py", 3, "m", "for x in s:")
    assert is_inline_suppressed(f, supp)
    assert not is_inline_suppressed(
        Finding("SL003", "p.py", 3, "m", ""), supp)


def test_inline_next_line_and_blanket_suppression():
    supp = inline_suppressions(
        "# simlint: disable-next-line=SL010\nx = 1\n# simlint: disable\ny = 2\n")
    assert is_inline_suppressed(Finding("SL010", "p", 2, "m", ""), supp)
    assert is_inline_suppressed(Finding("SL999", "p", 3, "m", ""), supp)


def test_baseline_roundtrip_and_line_stability(tmp_path):
    f1 = Finding("SL001", "repro/core/x.py", 10, "m", "for x in s:")
    f2 = Finding("SL001", "repro/core/x.py", 99, "m", "for  x  in s:")
    path = tmp_path / "baseline.json"
    Baseline().write(path, [f1])
    loaded = Baseline.load(path)
    assert f1 in loaded
    # fingerprints hash the normalized snippet, not the line number
    assert f2 in loaded
    assert Finding("SL003", "repro/core/x.py", 10, "m", "for x in s:") \
        not in loaded
    assert json.loads(path.read_text())["version"] == 1


# -- acceptance pins --------------------------------------------------------

def test_shipped_tree_lints_clean():
    """The acceptance criterion: src/repro carries zero unsuppressed
    findings and zero baseline entries."""
    new, baselined, _ = run_analysis()
    assert new == [], "\n".join(f.render() for f in new)
    assert baselined == []


def test_collect_files_covers_tree():
    files = collect_files()
    assert len(files) > 50
    assert all(f.suffix == ".py" for f in files)


def test_rule_catalog_matches_emitted_rules():
    emitted = {"SL001", "SL002", "SL003", "SL004", "SL005", "SL010",
               "SL011", "SL012", "SL013", "SL014"}
    assert emitted <= set(RULES)


def test_cli_clean_run_exits_zero():
    env = dict(os.environ, PYTHONPATH=str(SRC_ROOT))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--no-jaxpr",
         "--fail-on-findings"],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- kernel registry (satellite: uniform packages) --------------------------

KERNEL_NAMES = {"event_engine", "st_cost", "value_score", "selective_scan",
                "flash_attention", "strategy_plan"}


def test_registry_discovers_all_kernels():
    from repro.kernels import registered_kernels
    regs = registered_kernels()
    assert set(regs) == KERNEL_NAMES
    for name, spec in regs.items():
        assert spec.name == name
        assert spec.module == f"repro.kernels.{name}"
        assert spec.domain in ("sim", "model")
        assert spec.budget_bytes > 0


def test_kernel_spec_import_is_jax_free():
    """The registry must be importable on hosts without jax (the DES
    engine's numpy paths import kernel packages for their SPECs)."""
    env = dict(os.environ, PYTHONPATH=str(SRC_ROOT))
    code = ("import sys; import repro.kernels as k; k.registered_kernels(); "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr


# -- jaxpr audit ------------------------------------------------------------

jax = pytest.importorskip("jax")


def test_jaxpr_audit_single_kernel_ok():
    from repro.analysis.jaxpr_audit import audit_kernel
    from repro.kernels import get_kernel_spec
    entry = audit_kernel(get_kernel_spec("st_cost"))
    assert entry["ok"], entry["checks"]
    assert entry["max_rank"] <= 2
    assert entry["checks"]["oracle_f64"]
    assert entry["checks"]["x64_interpret_identity"]


def test_jaxpr_audit_event_engine_pinned():
    """The batched event-engine flush is registered and gated by the
    auditor on the program the chip runs, ``_flush_call``: rank ceiling 2
    (no dense (slots, links, ·) materialization), a tight byte budget at
    the audit shapes and no host callbacks. That program returns least
    share ranks and has no float oracle of its own; the whole route's
    bit-identity to its float64 oracle (the intra-route half of the
    two-tier golden contract, whose inter-engine half lives in
    tests/golden_tolerance.json) is held by
    tests/test_kernels.py::test_event_engine_interpret_matches_oracle and
    ::test_event_engine_kernel_route_keeps_float64_ties."""
    from repro.analysis.jaxpr_audit import audit_kernel
    from repro.kernels import get_kernel_spec
    spec = get_kernel_spec("event_engine")
    assert spec.domain == "sim"
    assert spec.max_rank == 2
    assert spec.kernel_attr == "_flush_call"
    assert not spec.multi_output
    entry = audit_kernel(spec)
    assert entry["ok"], entry["checks"]
    assert entry["callbacks"] == []
    assert entry["peak_eqn_bytes"] <= spec.budget_bytes


@pytest.mark.slow
def test_jaxpr_audit_all_kernels_ok(tmp_path):
    from repro.analysis.jaxpr_audit import run_jaxpr_audit
    report, failures = run_jaxpr_audit(tmp_path / "kernels.json")
    assert failures == []
    assert set(report["kernels"]) == KERNEL_NAMES
    assert (tmp_path / "kernels.json").exists()


def _fake_spec(fn, *, max_rank=2, budget=10**9, shapes=((8, 8), (8, 8))):
    import types

    import numpy as np

    def make_inputs():
        rng = np.random.default_rng(0)
        return tuple(rng.random(s).astype(np.float32) for s in shapes), {}

    return types.SimpleNamespace(
        name="fake", domain="model", max_rank=max_rank, budget_bytes=budget,
        load_kernel=lambda: fn, make_inputs=make_inputs,
        make_small_inputs=None)


def test_jaxpr_audit_catches_rank_and_budget_violations():
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import audit_kernel

    def dense_blowup(a, b, interpret=True):
        return (a[:, :, None] * b[None, :, :]).sum(-1)

    entry = audit_kernel(_fake_spec(dense_blowup, max_rank=2, budget=512))
    assert not entry["checks"]["rank_ok"]
    assert not entry["checks"]["budget_ok"]
    assert entry["max_rank"] == 3
    assert not entry["ok"]


def test_jaxpr_audit_catches_host_callbacks():
    import numpy as np

    from repro.analysis.jaxpr_audit import audit_kernel

    def with_callback(a, b, interpret=True):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(a.shape, a.dtype), a)

    entry = audit_kernel(_fake_spec(with_callback))
    assert not entry["checks"]["no_callbacks"]
    assert entry["callbacks"]
