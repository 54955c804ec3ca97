"""A configuration names its architecture, and the harness finds the
model family's file by that name (``benchmark/architectures/``): the
errors of a name that leads nowhere, and the proof that the seam is
enough -- a second family, every file of which lies under
``benchmark/tests/tiny_moe/``, walked by ``python -m benchmark.run
--rehearse`` from a manifest of its own to a contract line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import architectures, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FAMILY = os.path.join(HERE, "tiny_moe")


def test_a_configuration_without_the_key_is_an_error_that_says_so():
    with pytest.raises(SystemExit) as refusal:
        architectures.load({"name": "some-model"}, [run.HERE])
    message = str(refusal.value)
    assert '"architecture"' in message and "some-model" in message
    assert os.path.join(run.HERE, "architectures") in message


def test_a_name_with_no_file_is_an_error_that_names_the_directories():
    with pytest.raises(SystemExit) as refusal:
        architectures.load({"name": "some-model", "architecture": "mamba9"},
                           [FAMILY, run.HERE])
    message = str(refusal.value)
    assert "mamba9.py" in message
    assert os.path.join(FAMILY, "architectures") in message
    assert os.path.join(run.HERE, "architectures") in message


def test_every_configuration_of_the_manifest_names_a_family_with_all_pieces():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    for entry in manifest["configs"]:
        family = architectures.load(run.load_json(ROOT, entry["file"]),
                                    [run.HERE])
        assert all(callable(getattr(family, piece))
                   for piece in architectures.PIECES)


def test_a_family_that_no_existing_file_knows_is_walked_to_a_contract_line():
    # Everything of the family is this test's own: no file outside
    # ``benchmark/tests/tiny_moe`` names it.
    for directory, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if directory.startswith(HERE) or "__pycache__" in directory:
            continue
        for name in files:
            if name.endswith((".py", ".json")):
                with open(os.path.join(directory, name)) as stream:
                    assert "tiny_moe" not in stream.read(), name
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--manifest",
         os.path.join(FAMILY, "manifest.json"), "--rehearse",
         "--workload", "moe-chat", "--seed", "7", "--seconds", "3",
         "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["rehearsal"] is True
    checks = next(line["checks"] for line in lines if "checks" in line)
    assert checks["reference_agrees"] and checks["widths_match_file"]
    reference = next(line for line in lines
                     if line.get("phase") == "reference")
    assert reference["positions"] == 5 and reference["argmax_agree"] == 5
    # The reader's count is the family's own, not the dense one.
    config = run.load_json(FAMILY, "configs", "tiny-moe.json")
    family = architectures.load(config, [FAMILY, run.HERE])
    assert family.__file__.startswith(FAMILY)
    notes = next(line["reader_notes"]["decode_roofline"]
                 for line in lines if "reader_notes" in line)
    own = family.decode_step(config, notes["rows"], notes["context_tokens"])
    assert notes["bytes"] == pytest.approx(own["bytes"])
    assert notes["operations"] == pytest.approx(own["operations"])
    from benchmark.architectures import llama
    dense = llama.decode_step(
        {**config, "intermediate_size": config["moe_intermediate_size"]},
        notes["rows"], notes["context_tokens"], weight_bytes=2)
    assert notes["bytes"] > 2 * dense["bytes"]


def test_the_routed_count_streams_the_experts_its_rows_can_touch():
    config = run.load_json(FAMILY, "configs", "tiny-moe.json")
    family = architectures.load(config, [FAMILY, run.HERE])
    attention, router, expert = 2 * 64 * 64 + 2 * 64 * 32, 64 * 4, 3 * 64 * 128
    one = family.decode_step(config, rows=1, context_tokens=10)
    assert one["bytes"] == (2 * (attention + router + 2 * expert)
                            + 64 * 512) * 2 + 10 * 2 * 2 * 32 * 2
    assert one["operations"] == 2.0 * (
        2 * (attention + router + 2 * expert) + 64 * 512) \
        + 4.0 * 10 * 64 * 2
    many = family.decode_step(config, rows=4, context_tokens=10)
    assert many["bytes"] == (2 * (attention + router + 4 * expert)
                             + 64 * 512) * 2 + 4 * 10 * 2 * 2 * 32 * 2
