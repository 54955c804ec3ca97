"""``batcher.admission_fill``: the median of the program's
``llm_admission_fill`` histogram (one observation an admission chunk:
its real tokens over its program's rows, in %), through the
``histogram_quantile`` reader as it is."""

import pytest

from aiko_services_tpu.observability.metrics import MetricsRegistry
from benchmark import run

MANIFEST = run.load_json(run.ROOT, "BENCHMARK.json")


def _context(registry):
    ctx = object.__new__(run.Context)
    ctx._registry = registry
    ctx._values = {}
    return ctx


def test_the_metric_is_the_median_fill_of_the_windows_chunks():
    registry = MetricsRegistry()
    # a camera window: ~40-token prompts in 128-row programs, and one
    # prompt of 300 tokens (a 512-row program)
    for tokens, width in [(41, 128)] * 7 + [(38, 128)] * 5 + [(300, 512)]:
        registry.observe("llm_admission_fill", 100.0 * tokens / width)
    read = _context(registry).metric("batcher.admission_fill")
    assert read == pytest.approx(100.0 * 41 / 128, rel=0.09)


def test_a_program_that_observes_no_fill_reads_none():
    registry = MetricsRegistry()
    registry.observe("llm_admit_to_first_ms", 40.0)
    assert _context(registry).metric("batcher.admission_fill") is None


def test_the_entry_and_its_file_agree():
    entry = next(entry for entry in MANIFEST["per_layer"]
                 if entry["name"] == "batcher.admission_fill")
    spec = run.layer_metric("batcher.admission_fill")
    assert spec["kind"] == "histogram_quantile"
    assert spec["args"] == {"name": "llm_admission_fill", "q": 0.5}
    assert entry["workloads"] == spec["workloads"] \
        == ["camera-paced", "chat-batch"]
    assert all(entry[key] == spec[key] for key in
               ("unit", "better", "source", "layer", "moves"))
