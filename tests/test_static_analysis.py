"""Static analysis (ISSUE 6): the aiko_lint rule catalogue against its
broken-definition fixture corpus, in-tree cleanliness, the framework
self-check (``aiko_lint --self`` as a tier-1 gate), and the
``Pipeline.__init__`` pre-flight."""

import os
import time
from pathlib import Path

import pytest

from aiko_services_tpu.analysis import (
    ERROR, RULES, ModuleIndex, analyze_element_sources,
    analyze_framework, lint_definition, lint_paths, preflight)
from aiko_services_tpu.pipeline import (
    DefinitionError, Pipeline, parse_pipeline_definition)
from aiko_services_tpu.pipeline.definition import load_pipeline_definition

FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"
REPO = Path(__file__).resolve().parents[1]

#: fixture file -> the ONE rule it must trigger (and nothing else).
DEFINITION_FIXTURES = {
    "bad_graph.json": "bad-graph",
    "unknown_element.json": "unknown-element",
    "unbound_input.json": "unbound-input",
    "dead_output.json": "dead-output",
    "key_collision.json": "key-collision",
    "bad_mapping.json": "bad-mapping",
    "fallback_mismatch.json": "fallback-mismatch",
    "unused_element.json": "unused-element",
    "bad_placement.json": "bad-placement",
    "bad_replicas.json": "bad-placement",
    "replicas_on_unplaced.json": "replicas-on-unplaced",
    "placement_remote.json": "placement-remote",
    "bad_parameter.json": "bad-parameter",
    "bad_element_parameter.json": "bad-parameter",
    "bad_prefix_cache.json": "bad-parameter",
    "bad_llm_family.json": "bad-parameter",
    "bad_llm_decode_block.json": "bad-parameter",
    "bad_llm_hybrid_family.json": "bad-parameter",
    "bad_data_plane.json": "bad-parameter",
    "bad_qos.json": "bad-parameter",
    "bad_qos_tenant.json": "bad-parameter",
    "bad_journal.json": "bad-parameter",
    "bad_drain_timeout.json": "bad-parameter",
    "bad_slo.json": "bad-parameter",
    "bad_fleet.json": "bad-parameter",
    "bad_controller.json": "bad-parameter",
    "data_plane_on_local.json": "data-plane-on-local",
    "bad_source.py": "bad-source",
    "undeclared_host_input.json": "undeclared-host-input",
    "device_fn_host_call.json": "device-fn-host-call",
    "unread_parameter.json": "unread-parameter",
    "donation_alias.json": "donation-alias",
}

#: selfcheck fixture tree -> its rule (each tree carries a healthy
#: baseline -- matched hook pair, full span files -- plus ONE breakage).
SELFCHECK_FIXTURES = {
    "hook_parity": "hook-parity",
    "handler_liveness": "handler-liveness",
    "span_sync": "span-sync",
    "resume_identity": "resume-identity",
    "parameter_registry": "parameter-registry",
    "metric_registry": "metric-registry",
    "kernel_test": "kernel-test",
    "kernel_table": "kernel-table",
}


# -- fixture corpus: each rule fires exactly at its fixture -----------------

@pytest.mark.parametrize("filename,rule",
                         sorted(DEFINITION_FIXTURES.items()))
def test_definition_fixture_fires_exactly_its_rule(filename, rule):
    report = lint_paths([FIXTURES / filename])
    assert [f.rule for f in report.findings] == [rule], report.render()


@pytest.mark.parametrize("dirname,rule", sorted(SELFCHECK_FIXTURES.items()))
def test_selfcheck_fixture_fires_exactly_its_rule(dirname, rule):
    findings = analyze_framework(FIXTURES / "selfcheck" / dirname,
                                 registry={})
    assert [f.rule for f in findings] == [rule], \
        "\n".join(f.render() for f in findings)


def test_element_parameter_domains_scoped_to_module():
    """ELEMENT_PARAMETERS is keyed by (module, class): a user's
    unrelated class that happens to be named LLM never has the serving
    element's value domains imposed on it, while path-form references
    to the real module normalize and match."""
    from aiko_services_tpu.analysis.params import \
        validate_element_parameters

    assert validate_element_parameters(
        "LLM", {"speculative": "banana"}, "p: a",
        module="my_app.models") == []
    findings = validate_element_parameters(
        "LLM", {"speculative": "banana"}, "p: a",
        module="aiko_services_tpu/elements/llm.py")
    assert [f.rule for f in findings] == ["bad-parameter"]


def test_prefix_cache_knob_domains():
    """ISSUE 18 shared-prefix KV knobs validate at create time: each
    bad value fires exactly one bad-parameter finding, and the full
    good configuration (including ``speculative: auto``) is clean."""
    from aiko_services_tpu.analysis.params import \
        validate_element_parameters

    module = "aiko_services_tpu.elements.llm"
    assert validate_element_parameters(
        "LLM", {"prefix_cache": "on", "prefix_min_tokens": 64,
                "spec_autoprobe": "off", "speculative": "auto"},
        "p: a", module=module) == []
    for bad in ({"prefix_cache": "maybe"},
                {"prefix_min_tokens": 0},
                {"prefix_min_tokens": "lots"},
                {"spec_autoprobe": "sometimes"}):
        findings = validate_element_parameters(
            "LLM", bad, "p: a", module=module)
        assert [f.rule for f in findings] == ["bad-parameter"], bad


def test_every_rule_has_a_fixture():
    covered = set(DEFINITION_FIXTURES.values()) \
        | set(SELFCHECK_FIXTURES.values())
    assert covered == set(RULES)


def test_findings_carry_graph_path_context():
    report = lint_paths([FIXTURES / "unbound_input.json"])
    finding = report.findings[0]
    # pipeline name -> node path -> offending field
    assert "fx_unbound_input: a->b: b.input.nope" in finding.render()


# -- escape hatches ---------------------------------------------------------

def test_source_comment_disable_suppresses_rule():
    findings = analyze_element_sources([FIXTURES / "broken_elements.py"])
    by_rule = {}
    for finding in findings:
        by_rule.setdefault(finding.rule, []).append(finding)
    # the source-visible violations -- including the ones hidden
    # behind the module-local _as_uint8 wrapper and behind _via_import
    # (a local wrapper around elements/image.py's as_uint8); the
    # "# aiko-lint: disable=..." twin (SuppressedHostInput) is silent.
    assert sorted(by_rule) == ["device-fn-host-call",
                               "undeclared-host-input"]
    assert len(by_rule["device-fn-host-call"]) == 1
    assert len(by_rule["undeclared-host-input"]) == 3
    assert any("host-materializing helper" in f.message
               for f in by_rule["undeclared-host-input"])
    assert any("ImportWrappedHostInput" in f.message
               for f in by_rule["undeclared-host-input"])
    assert not any("SuppressedHostInput" in f.message for f in findings)


def test_missing_source_path_is_a_finding():
    report = lint_paths([FIXTURES / "no_such_file.py"])
    assert [f.rule for f in report.findings] == ["bad-source"]
    report = lint_paths([FIXTURES / "no_such_definition.json"])
    assert [f.rule for f in report.findings] == ["bad-source"]


def test_unknown_lint_key_rule_rejected():
    with pytest.raises(DefinitionError, match="dead_output"):
        parse_pipeline_definition({
            "version": 0, "name": "p_typo", "runtime": "jax",
            "graph": ["(a)"],
            "elements": [
                {"name": "a", "input": [], "output": [],
                 "lint": ["dead_output"],    # underscore typo
                 "deploy": {"local": {
                     "module": "tests/lint_fixtures/broken_elements.py",
                     "class_name": "CleanHead"}}}]})


def test_module_index_reparses_on_mtime_change(tmp_path):
    source = tmp_path / "elem.py"
    source.write_text(
        "import numpy as np\n"
        "from aiko_services_tpu.pipeline import PipelineElement\n"
        "class E(PipelineElement):\n"
        "    def process_frame(self, stream, image=None):\n"
        "        return True, {'n': np.asarray(image).size}\n")
    index = ModuleIndex()
    assert [f.rule for f in
            analyze_element_sources([source], index)] \
        == ["undeclared-host-input"]
    fixed = source.read_text().replace(
        "class E(PipelineElement):",
        "class E(PipelineElement):\n    host_inputs = ('image',)")
    source.write_text(fixed)
    os.utime(source, ns=(1, 1))             # force a distinct mtime
    assert not analyze_element_sources([source], index)


def test_fallback_signature_compares_by_name_not_order():
    # same names in a different declaration order binds identically at
    # runtime (**inputs / mappings are by name): no finding.
    module = "tests/lint_fixtures/broken_elements.py"
    definition = parse_pipeline_definition({
        "version": 0, "name": "p_fb_order", "runtime": "jax",
        "graph": ["(a r s)"],
        "elements": [
            {"name": "a", "input": [],
             "output": [{"name": "x"}, {"name": "y"}],
             "deploy": {"local": {"module": module,
                                  "class_name": "CleanHead"}}},
            {"name": "r", "input": [{"name": "x"}, {"name": "y"}],
             "output": [{"name": "out"}],
             "deploy": {"remote": {"name": "fx_worker"}},
             "fallback": "fb"},
            {"name": "fb", "input": [{"name": "y"}, {"name": "x"}],
             "output": [{"name": "out"}],
             "deploy": {"local": {"module": module,
                                  "class_name": "CleanHead"}}},
            {"name": "s", "input": [{"name": "out"}], "output": [],
             "deploy": {"local": {"module": module,
                                  "class_name": "CleanSink"}}}]})
    assert not lint_definition(definition).findings


def test_definition_lint_key_suppresses_rule():
    definition = load_pipeline_definition(
        str(FIXTURES / "unbound_input.json"))
    assert lint_definition(definition).findings
    definition.lint_disable = ("unbound-input",)    # JSON: "lint": [...]
    assert not lint_definition(definition).findings


def test_key_collision_fixture_exercises_element_lint_key():
    # b's "lint": ["dead-output"] suppresses the secondary finding (the
    # walk runs b after the join), leaving exactly the collision.
    definition = load_pipeline_definition(
        str(FIXTURES / "key_collision.json"))
    assert definition.element("b").lint_disable == ("dead-output",)
    rules = [f.rule for f in lint_definition(definition).findings]
    assert rules == ["key-collision"]


# -- in-tree cleanliness (the acceptance gate) ------------------------------

def test_examples_and_elements_lint_clean():
    paths = sorted((REPO / "examples").rglob("*.json"))
    assert paths, "no example definitions found"
    paths.append(REPO / "aiko_services_tpu" / "elements")
    report = lint_paths(paths)
    assert not report.findings, report.render()


def test_framework_self_check_clean():
    """``aiko_lint --self`` inside tier-1: hook parity, handler
    liveness, span sync, resume-post identity, parameter registry --
    all over the real package sources."""
    findings = analyze_framework()
    assert not findings, "\n".join(f.render() for f in findings)


def test_preflight_cost_is_create_time_cheap():
    """The e2e-style definition pre-flights in well under 100 ms once
    the module index is warm."""
    definition = load_pipeline_definition(
        str(REPO / "examples" / "speech" / "pipeline_speech.json"))
    lint_definition(definition)                     # warm the AST cache
    start = time.perf_counter()
    report = lint_definition(definition)
    elapsed_ms = (time.perf_counter() - start) * 1000.0
    assert not report.findings, report.render()
    assert elapsed_ms < 100.0, f"pre-flight took {elapsed_ms:.1f} ms"


# -- Pipeline.__init__ pre-flight -------------------------------------------

def _broken_definition():
    return parse_pipeline_definition({
        "version": 0, "name": "p_preflight", "runtime": "jax",
        "graph": ["(a (c (v: ghost.x)))"],
        "elements": [
            {"name": "a", "input": [], "output": [{"name": "x"}],
             "deploy": {"local": {
                 "module": "tests/lint_fixtures/broken_elements.py",
                 "class_name": "CleanHead"}}},
            {"name": "c", "input": [{"name": "v"}, {"name": "x"}],
             "output": [],
             "deploy": {"local": {
                 "module": "tests/lint_fixtures/broken_elements.py",
                 "class_name": "CleanSink"}}}]})


def test_pipeline_create_rejects_error_findings(runtime):
    with pytest.raises(DefinitionError) as excinfo:
        Pipeline(_broken_definition(), runtime=runtime)
    message = str(excinfo.value)
    assert "pre-flight failed" in message
    assert "bad-mapping" in message
    assert "p_preflight: a->c" in message           # graph-path context


def test_pipeline_create_strict_rejects_warnings(runtime):
    definition = load_pipeline_definition(
        str(FIXTURES / "unbound_input.json"))
    Pipeline(definition, runtime=runtime)           # warning passes "on"
    with pytest.raises(DefinitionError, match="unbound-input"):
        Pipeline(definition, name="p_strict", runtime=runtime,
                 preflight="strict")


def test_pipeline_create_preflight_off_bypasses(runtime):
    definition = _broken_definition()
    definition.parameters["preflight"] = "off"
    Pipeline(definition, runtime=runtime)           # frame N's problem


def test_preflight_gate_severities():
    broken = _broken_definition()
    with pytest.raises(DefinitionError):
        preflight(broken)                           # error severity
    assert preflight(broken, mode="off") is None
    broken.parameters["preflight"] = "off"
    with pytest.raises(DefinitionError):
        preflight(broken, mode="strict")            # --check beats "off"
    warn_only = load_pipeline_definition(
        str(FIXTURES / "unbound_input.json"))
    report = preflight(warn_only)                   # warnings survive "on"
    assert [f.rule for f in report.findings] == ["unbound-input"]
    assert all(f.severity != ERROR for f in report.findings)
