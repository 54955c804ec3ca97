"""``chip_smoke.py`` off the chip (ISSUE 21): the script that proves the
system starts on the TPU must FAIL anywhere else -- unless the builder
asks for the rehearsal by name -- and importing the package must leave
the chip alone (a process holds it only once it builds a model)."""

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
SMOKE = str(ROOT / "chip_smoke.py")


def _run(*args, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)          # one CPU device: the plain shape
    return subprocess.run([sys.executable, SMOKE, *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_smoke_refuses_without_a_tpu():
    """Under ``JAX_PLATFORMS=cpu`` the default run exits non-zero
    before building anything, names the platform it found, and prints
    no result."""
    result = _run(timeout=120)
    assert result.returncode != 0
    assert "platform='cpu'" in result.stderr, result.stderr[-2000:]
    assert "--rehearse" in result.stderr
    assert result.stdout.strip() == "", result.stdout[-2000:]


def test_smoke_rehearsal_passes_at_tiny():
    """``--rehearse`` -- the only way the script runs off the chip --
    walks both phases at ``model: tiny`` and says it was a rehearsal."""
    result = _run("--rehearse")
    assert result.returncode == 0, \
        (result.stdout[-3000:], result.stderr[-3000:])
    lines = result.stdout.strip().splitlines()
    verdict = json.loads(lines[-1])
    assert verdict["ok"] is True and verdict["rehearsal"] is True
    assert verdict["device"]["platform"] == "cpu"
    phases = [line for line in lines if line.startswith("phase ")]
    assert [line.split()[1] for line in phases] == ["serve:", "kernels:"]
    serve = phases[0]
    assert "sent=16 ok=16" in serve and "implicit=0" in serve
    assert "broken=0" in serve and "recoveries=0" in serve
    # Off the chip every ``auto`` probe resolves the reference path.
    assert "decode_backend=reference" in serve
    assert "matmul_backend=reference" in serve
    # The block-causal forms of the two attention kernels (ISSUE 36).
    assert "flash_attention[block=4]=" in phases[1]
    assert "flash_verify_append[paged,block=4]=" in phases[1]


_IMPORT_PROBE = """
import importlib, pkgutil
import aiko_services_tpu
import aiko_services_tpu.cli
import aiko_services_tpu.gateway.server
import aiko_services_tpu.orchestration.controller
import aiko_services_tpu.elements as elements
for module in pkgutil.iter_modules(elements.__path__):
    importlib.import_module(f"aiko_services_tpu.elements.{module.name}")
from jax._src import xla_bridge
assert not xla_bridge._backends, sorted(xla_bridge._backends)
print("backend-free")
"""


def test_package_import_initialises_no_backend():
    """Importing the package, the CLI, the gateway, the controller and
    every element leaves ``xla_bridge._backends`` empty: on a chip
    machine an importing parent does not take the chip from the one
    process that serves from it."""
    result = subprocess.run([sys.executable, "-c", _IMPORT_PROBE],
                            cwd=ROOT, capture_output=True, text=True,
                            timeout=300)
    assert result.returncode == 0, result.stderr[-3000:]
    assert result.stdout.strip().endswith("backend-free")
