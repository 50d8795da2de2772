"""thriftlint: the linter's own test suite.

Three layers:

* **fixtures** — each rule pass fires exactly on the seeded violations in
  ``tests/lint_fixtures/`` (expected locations derived from the inline
  ``FIRES: <rule>`` markers) and nowhere else;
* **real tree** — the committed ``src/repro`` baseline is zero findings,
  and the walker resolves the entry points the rules depend on;
* **runtime sentinels** — ``CompileSentinel`` counts real XLA
  compilations and the tracer-leak guard turns leaks into errors.
"""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (
    ALL_RULES,
    CompileSentinel,
    compile_cache_size,
    run_lint,
    tracer_leak_guard,
)
from repro.analysis.findings import (
    BAD_SUPPRESSION,
    apply_suppressions,
    parse_suppressions,
)
from repro.analysis.walker import Project

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"


def _expected_locations(rule: str) -> set[tuple[str, int]]:
    """(path, line) pairs carrying a ``FIRES: <rule>`` marker."""
    out = set()
    for path in (FIXTURES / "badrepro").rglob("*.py"):
        rel = path.relative_to(FIXTURES).as_posix()
        for lineno, line in enumerate(
            path.read_text().splitlines(), start=1
        ):
            if f"FIRES: {rule}" in line:
                out.add((rel, lineno))
    return out


class TestRulesFireOnFixtures:
    @pytest.mark.parametrize("rule", sorted(ALL_RULES))
    def test_rule_fires_exactly_on_seeded_violations(self, rule):
        report = run_lint(
            src_root=FIXTURES, package="badrepro", rules=(rule,)
        )
        expected = _expected_locations(rule)
        assert expected, f"fixture tree seeds no {rule} violations"
        actual = {(f.path, f.line) for f in report.findings}
        assert actual == expected
        assert all(f.rule == rule for f in report.findings)

    def test_all_rules_marker_census(self):
        """Every badrepro finding is a marked line and vice versa."""
        report = run_lint(src_root=FIXTURES, package="badrepro")
        expected = set()
        for rule in ALL_RULES:
            expected |= _expected_locations(rule)
        assert {(f.path, f.line) for f in report.findings} == expected


class TestRealTreeIsClean:
    @pytest.mark.parametrize("rule", sorted(ALL_RULES))
    def test_rule_silent_on_real_tree(self, rule):
        report = run_lint(src_root=REPO / "src", rules=(rule,))
        assert [f.format() for f in report.findings] == []

    def test_full_run_is_clean_and_suppressions_are_reasoned(self):
        report = run_lint(src_root=REPO / "src")
        assert report.ok, [f.format() for f in report.findings]
        # every committed suppression carries its justification
        assert all(s.has_reason for s in report.suppressions)


class TestSuppressionMachinery:
    def test_reasoned_reasonless_and_bare(self):
        report = run_lint(src_root=FIXTURES, package="suppdemo")
        by_rule = report.by_rule()
        # the reason-less comment is itself a finding...
        assert len(by_rule[BAD_SUPPRESSION]) == 1
        # ...and does NOT silence the violation on its line; the bare
        # violation also survives
        assert len(by_rule["f64-reduction"]) == 2
        # the reasoned suppression silenced exactly one finding
        assert len(report.suppressed) == 1
        assert report.suppressed[0].rule == "f64-reduction"

    def test_docstring_spelling_is_not_a_suppression(self):
        text = '"""docs say # thriftlint: ignore[jit-purity] reason"""\nx = 1\n'
        assert parse_suppressions("m.py", text) == []

    def test_bad_suppression_cannot_be_suppressed(self):
        text = "x = 1  # thriftlint: ignore[bad-suppression]\n"
        sup = parse_suppressions("m.py", text)
        surviving, suppressed = apply_suppressions([], sup)
        assert [f.rule for f in surviving] == [BAD_SUPPRESSION]
        assert not suppressed


class TestWalker:
    @pytest.fixture(scope="class")
    def project(self):
        return Project(REPO / "src")

    def test_finds_the_declared_entry_points(self, project):
        entries = {e.fn.qualname for e in project.jit_entries if e.fn}
        assert {"_wave_scan_packed", "_sur_greedy_scan_core",
                "xi_from_responses", "sample_pool_responses"} <= entries
        # the wave loop is entered through its packed-buffer wrapper
        assert "_wave_scan_core" in {f.qualname for f in project.reachable}

    def test_wrapper_assignment_idiom_resolves(self, project):
        # mc.py: `xi_from_responses_grouped = partial(jax.jit, ...)(core)`
        symbols = project.jitted_symbols()
        assert "xi_from_responses_grouped" in symbols
        assert symbols["xi_from_responses_grouped"].fn.qualname == (
            "_masked_xi_core"
        )
        assert "num_classes" in symbols[
            "xi_from_responses_grouped"
        ].static_argnames

    def test_nested_scan_bodies_are_reachable(self, project):
        names = {f.qualname for f in project.reachable}
        assert "_sur_greedy_scan_core.<locals>.body" in names
        assert "_sur_greedy_scan_core.<locals>.cond" in names

    def test_pallas_kernels_are_roots(self, project):
        assert len(project.pallas_sites) >= 5
        assert all(k in project.reachable for k in project.kernels)


class TestCLI:
    def test_zero_findings_zero_exit(self):
        out = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "lint.py"),
             "--format=json"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        report = json.loads(out.stdout)
        assert report["ok"] and report["findings"] == []

    def test_rule_filter_and_listing(self):
        out = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "lint.py"),
             "--list-rules"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0
        assert set(out.stdout.split()) == set(ALL_RULES)

    def test_nonzero_exit_on_findings(self):
        out = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "lint.py"),
             "--src", str(FIXTURES), "--package", "badrepro"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 1
        assert "jit-purity" in out.stdout


class TestCompileSentinel:
    def test_counts_real_compilations(self):
        @jax.jit
        def double(x):
            return x * 2

        sentinel = CompileSentinel({"d": double})
        double(jnp.ones(3))
        assert sentinel.compiles("d") == 1
        double(jnp.ones(3) * 5.0)        # same shape: cache hit
        assert sentinel.compiles("d") == 1
        double(jnp.ones(4))              # new shape: one more program
        assert sentinel.compiles("d") == 2
        with pytest.raises(AssertionError, match="recompilation"):
            sentinel.assert_no_new_compiles()
        sentinel.snapshot()
        sentinel.assert_no_new_compiles()
        sentinel.assert_within({"d": 0})
        double(jnp.ones(5))
        with pytest.raises(AssertionError, match="budget"):
            sentinel.assert_within({"d": 0})

    def test_rejects_plain_functions(self):
        with pytest.raises(TypeError, match="_cache_size"):
            compile_cache_size(lambda x: x)
        with pytest.raises(TypeError):
            CompileSentinel({"plain": lambda x: x})


class TestTracerGuard:
    def test_leak_raises(self):
        leaked = []

        def leaky(x):
            leaked.append(x)     # smuggle the tracer into host state
            return x * 2

        with pytest.raises(Exception, match="[Ll]eak"):
            with tracer_leak_guard():
                jax.jit(leaky)(jnp.ones(3))

    def test_clean_trace_passes(self):
        with tracer_leak_guard():
            assert float(jax.jit(lambda x: x * 2)(jnp.ones(()))) == 2.0
