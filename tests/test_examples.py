"""The example scripts actually run: each aloha_honua demo (minimal
actor, discovery/do_command, do_request) executes as a subprocess and
produces its expected output -- examples are living documentation of the
actor / discovery / request-response patterns (reference
examples/aloha_honua/aloha_honua_{0..3}.py)."""

import pathlib
import subprocess
import sys

import pytest

EXAMPLES = pathlib.Path(__file__).resolve().parent.parent / "examples"


SANDBOX_ENV = {"PATH": "/usr/bin:/bin", "AIKO_LOG_LEVEL": "ERROR",
               "JAX_PLATFORMS": "cpu", "HOME": "/tmp",
               "JAX_ENABLE_COMPILATION_CACHE": "false"}


def run_example(relative, timeout=300):
    """Run an example as a subprocess on the CPU backend
    (``SANDBOX_ENV`` sets ``JAX_PLATFORMS=cpu``)."""
    result = subprocess.run([sys.executable, str(EXAMPLES / relative)],
                            capture_output=True, text=True,
                            timeout=timeout, env=dict(SANDBOX_ENV))
    assert result.returncode == 0, result.stderr[-2000:]
    return result.stdout


@pytest.mark.parametrize("script,expected", [
    ("aloha_honua/aloha_honua_0.py", "Aloha Pele!"),
    ("aloha_honua/aloha_honua_1.py", "Aloha Honua!"),
    ("aloha_honua/aloha_honua_2.py", "response:"),
    ("robot/run_ooda.py", "last_action=sit"),
])
def test_aloha_example(script, expected):
    stdout = run_example(script)
    assert expected in stdout, stdout


@pytest.mark.parametrize("script,expected", [
    ("pipeline/run_local.py", "result="),
    ("pipeline/run_paths.py", "path in_square: x=6 -> result=36"),
    ("pipeline/run_remote.py", "worker added 100"),
    ("detector/detect_image.py", "detections:"),
    ("llm/chat.py", "DONE"),
    ("speech/run_speech.py", "reply.wav"),
])
def test_model_example(script, expected):
    """Every model-path demo runs end to end (CPU backend): these are
    the reference's yolo/llm/speech example equivalents and break
    silently when element contracts drift -- detect_image.py's missing
    'path' input went unnoticed exactly this way."""
    stdout = run_example(script)
    assert expected in stdout, stdout
