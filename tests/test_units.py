"""Tests for the dataflow framework + unit/dimension checker (SL020-25).

Covers: FlowAnalysis propagation semantics (env scoping, class
attribute pre-pass, fixpoint mode), one positive + one negative (and a
suppression) case per unit rule, the SL001 port-parity pin (the
determinism linter now rides on the framework and must keep flagging /
keep the corpus clean), the unit-broken fixture module, kernel unit
signatures, and the acceptance pin: the shipped dimension-carrying
modules unit-check clean with zero suppressions.
"""

import ast
import textwrap
from pathlib import Path

from repro.analysis import RULES
from repro.analysis.dataflow import FlowAnalysis
from repro.analysis.jaxpr_audit import check_unit_signature
from repro.analysis.simlint import lint_source
from repro.analysis.units import (DIMENSIONS, UNIT_SCOPE, lint_units,
                                  run_units, unit_scoped)

REPO_ROOT = Path(__file__).resolve().parents[1]
FIXTURE = REPO_ROOT / "tests" / "fixtures" / "unit_broken.py"


def unit_rules(source: str, path: str = "repro/core/x.py") -> list[str]:
    return [f.rule for f in lint_units(textwrap.dedent(source), path)]


# -- dataflow framework -----------------------------------------------------

class _TagFlow(FlowAnalysis):
    """Toy client: annotation `int` means label 'tag'; calls to tag()
    produce 'tag'; flags every Name read that evaluates to 'tag'."""

    def ann_label(self, ann):
        return "tag" if isinstance(ann, ast.Name) and ann.id == "int" \
            else None

    def expr_label(self, node):
        if isinstance(node, ast.Name):
            return self.env.get(node.id)
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and node.value.id == "self":
            return self.attr_env.get(node.attr)
        if isinstance(node, ast.Call) and self.func_name(node.func) == "tag":
            return "tag"
        return None

    def visit_Expr(self, node):
        if self.expr_label(node.value) == "tag":
            self.flag("TAG", node, "tagged value used")
        self.generic_visit(node)


def tag_lines(source: str, *, fixpoint: bool = False) -> list[int]:
    src = textwrap.dedent(source)
    flow = _TagFlow("x.py", src)
    flow.fixpoint = fixpoint
    return [f.line for f in flow.run(ast.parse(src))]


def test_dataflow_assign_propagation_and_rebinding():
    assert tag_lines("""
        x = tag()
        x
        x = 0
        x
    """) == [3]                       # rebinding to unknown drops the label


def test_dataflow_function_scope_and_annotation_seeding():
    assert tag_lines("""
        def f(a: int, b):
            a
            b
        def g(b):
            b
    """) == [3]                       # only the annotated param labels


def test_dataflow_closure_sees_enclosing_bindings():
    assert tag_lines("""
        def outer():
            y = tag()
            def inner():
                y
    """) == [5]


def test_dataflow_class_attr_prepass():
    assert tag_lines("""
        class C:
            def use(self):
                self.z
            def set(self):
                self.z = tag()
    """) == [4]                       # pre-pass sees later assignment


def test_dataflow_fixpoint_reaches_loop_carried_labels():
    src = """
        def f():
            x = 0
            for _ in range(3):
                x
                x = tag()
    """
    assert tag_lines(src) == []                  # single pass misses it
    assert tag_lines(src, fixpoint=True) == [5]  # fixpoint finds it


def test_dataflow_fixpoint_reports_each_finding_once():
    lines = tag_lines("""
        def f():
            x = tag()
            x
    """, fixpoint=True)
    assert lines == [4]               # warm-up passes stay muted


# -- SL001 port parity ------------------------------------------------------

def test_sl001_still_fires_after_framework_port():
    findings = lint_source(textwrap.dedent("""
        class C:
            def __init__(self):
                self.pending: set[int] = set()
            def drain(self):
                return [x for x in self.pending]
    """), "repro/core/x.py")
    assert [f.rule for f in findings] == ["SL001"]


def test_sl001_order_free_consumers_still_clean_after_port():
    assert lint_source(textwrap.dedent("""
        def f(s: set[int]):
            return sorted(s), any(x > 0 for x in s), len(s)
    """), "repro/core/x.py") == []


def test_simlint_corpus_parity_on_shipped_tree():
    """The ported linter keeps the shipped corpus clean file-for-file
    (the pre-port corpus had zero findings; so must the port)."""
    from repro.analysis import run_analysis
    new, baselined, inline = run_analysis()
    assert new == [] and baselined == []


# -- unit rules: positive / negative / suppression ---------------------------

def test_sl020_cross_dimension_add():
    assert "SL020" in unit_rules("""
        def f(size, now):
            return size + now
    """)


def test_sl020_unknown_operand_is_forgiving():
    assert unit_rules("""
        def f(now):
            return now + 5.0
    """) == []


def test_sl020_augassign_mismatch():
    assert "SL020" in unit_rules("""
        class C:
            def f(self, now):
                self.total_wan_bytes += now
    """)


def test_sl021_cross_dimension_compare():
    assert "SL021" in unit_rules("""
        def f(size, bandwidth):
            return size > bandwidth
    """)


def test_sl021_ratio_compare_is_clean():
    assert unit_rules("""
        def f(size, bandwidth, now, deadline):
            return size / bandwidth > deadline - now
    """) == []                        # bytes/bw -> seconds vs seconds


def test_sl022_bytes_divided_by_mbps():
    assert "SL022" in unit_rules("""
        def f(n_bytes, link_mbps):
            return n_bytes / link_mbps
    """)


def test_sl022_kwarg_binding_without_conversion():
    assert "SL022" in unit_rules("""
        def f(make, spec):
            return make(wan_bandwidth=spec.lan_mbps)
    """)


def test_sl022_converted_mbps_is_clean():
    assert unit_rules("""
        from repro.core.quantities import MBPS_TO_BYTES_PER_S
        def f(make, spec):
            return make(wan_bandwidth=spec.lan_mbps * MBPS_TO_BYTES_PER_S)
    """) == []


def test_sl023_sim_wall_mixing():
    rules = unit_rules("""
        def f(now, elapsed_us):
            return now - elapsed_us
    """)
    assert "SL023" in rules and "SL020" not in rules


def test_sl024_raw_conversion_literal():
    assert "SL024" in unit_rules("""
        def f(n_bytes):
            return n_bytes / 1e9
    """)


def test_sl024_exempt_in_quantities_and_named_constant_clean():
    src = """
        from repro.core.quantities import GB
        def f(n_bytes):
            return n_bytes / GB
    """
    assert unit_rules(src) == []
    assert unit_rules("""
        def f(n_bytes):
            return n_bytes / 1e9
    """, path="repro/core/quantities.py") == []


def test_sl025_declared_dimension_contradiction():
    assert "SL025" in unit_rules("""
        class C:
            def f(self, n_bytes):
                self.makespan = n_bytes
    """)


def test_sl025_registry_outranks_buggy_inference():
    """The buggy assignment itself must not relabel the declared attr."""
    rules = unit_rules("""
        class C:
            def f(self, n_bytes):
                self.makespan = n_bytes
                return self.makespan + n_bytes
    """)
    assert "SL025" in rules and "SL020" in rules


def test_unit_rules_inline_suppression():
    from repro.analysis.findings import (inline_suppressions,
                                         is_inline_suppressed)
    src = textwrap.dedent("""
        def f(size, now):
            return size + now  # simlint: disable=SL020
    """)
    findings = lint_units(src, "repro/core/x.py")
    supp = inline_suppressions(src)
    assert findings and all(is_inline_suppressed(f, supp) for f in findings)


def test_unit_algebra_labels_engine_idioms():
    """The canonical engine lines type-check: rem -= rate*dt,
    eta = now + rem/rate, share = bw/active."""
    assert unit_rules("""
        class Net:
            def advance(self, now, dt):
                self.rem -= self.rate * dt
                eta = now + self.rem / self.rate
                share = self.link_bw / self.n_active
                return eta, share
    """) == []


# -- fixture + shipped tree --------------------------------------------------

def test_unit_broken_fixture_yields_three_distinct_rules():
    findings = lint_units(FIXTURE.read_text(), "tests/fixtures/unit_broken.py")
    rules = {f.rule for f in findings}
    assert len(rules) >= 3, rules
    assert rules <= {"SL020", "SL021", "SL022", "SL023", "SL024", "SL025"}
    # every seeded bug class is caught
    assert {"SL020", "SL021", "SL022", "SL023", "SL024", "SL025"} <= rules


def test_shipped_tree_unit_checks_clean():
    """Acceptance pin: zero findings, zero suppressions on the scoped
    modules, and the report covers the whole scope."""
    findings, n_inline, report = run_units()
    assert findings == [], "\n".join(f.render() for f in findings)
    assert n_inline == 0
    assert len(report["files"]) == len(UNIT_SCOPE)
    assert report["n_findings"] == 0


def test_unit_scope_files_exist():
    for scope in UNIT_SCOPE:
        assert (REPO_ROOT / "src" / scope).is_file(), scope
        assert unit_scoped(str(REPO_ROOT / "src" / scope))


def test_units_catches_seeded_engine_mutations():
    """End-to-end sensitivity: breaking real engine lines is flagged."""
    net = (REPO_ROOT / "src/repro/core/network.py").read_text()
    broken = net.replace("np.maximum(self.rem - self.rate * dt",
                         "np.maximum(self.rem - self.rate + dt")
    assert broken != net
    assert any(f.rule == "SL020"
               for f in lint_units(broken, "repro/core/network.py"))

    scen = (REPO_ROOT / "src/repro/core/scenarios.py").read_text()
    broken = scen.replace("lan_bandwidth=spec.lan_mbps * mbps",
                          "lan_bandwidth=spec.lan_mbps")
    assert broken != scen
    assert any(f.rule == "SL022"
               for f in lint_units(broken, "repro/core/scenarios.py"))


def test_unit_rules_registered_in_catalog():
    assert {"SL020", "SL021", "SL022", "SL023", "SL024", "SL025"} \
        <= set(RULES)


def test_list_rules_groups_by_family():
    from repro.analysis import RULE_FAMILIES
    grouped = [r for _, rules in RULE_FAMILIES for r in rules]
    assert sorted(grouped) == sorted(set(grouped))      # no dupes
    assert set(grouped) == set(RULES)                   # nothing dropped


# -- kernel unit signatures (jax-free) --------------------------------------

def test_all_kernel_specs_declare_unit_signatures():
    from repro.kernels import registered_kernels
    specs = registered_kernels()
    assert len(specs) == 6
    for name, spec in specs.items():
        args, _ = spec.make_inputs()
        assert check_unit_signature(spec, len(args)), name
        assert set(spec.arg_units) <= DIMENSIONS, name
        assert set(spec.out_units) <= DIMENSIONS, name


def test_sim_kernel_signatures_pinned():
    """The physical signatures of the DES kernels are load-bearing
    documentation — pin them."""
    from repro.kernels import get_kernel_spec
    st = get_kernel_spec("st_cost")
    assert st.out_units == ("sim_seconds",)
    ev = get_kernel_spec("event_engine")
    assert ev.arg_units == ("count", "count")       # link ids, share ranks
    assert ev.out_units == ("count",)               # least rank


def test_check_unit_signature_rejects_incomplete():
    import dataclasses
    from repro.kernels import get_kernel_spec
    spec = get_kernel_spec("value_score")
    args, _ = spec.make_inputs()
    assert not check_unit_signature(
        dataclasses.replace(spec, arg_units=spec.arg_units[:-1]), len(args))
    assert not check_unit_signature(
        dataclasses.replace(spec, out_units=()), len(args))
    assert not check_unit_signature(
        dataclasses.replace(spec, out_units=("furlongs",)), len(args))
