"""Test configuration.

JAX runs on the CPU backend with 8 virtual devices so every sharding /
mesh / collective path is exercised here; what only a TPU can show
(Mosaic compilation, donation, the kernel `auto` routes) is
``chip_smoke.py``'s job.  The env vars must be set before jax is first
imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Tier-1 compiles thousands of small CPU programs, here and in the
# processes it spawns; keep them all out of the in-checkout persistent
# compile cache (pipeline/fusion.py) -- jax reads this at import, and
# children inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest


@pytest.fixture
def runtime():
    """Fresh isolated process runtime on the in-memory loopback broker."""
    from aiko_services_tpu.transport import reset_broker
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.services.share import reset_services_cache

    reset_broker()
    reset_services_cache()
    rt = init_process(transport="loopback")
    rt.initialize()
    yield rt
    rt.engine.terminate()
    reset_process()
    reset_services_cache()
    reset_broker()


def run_until(rt, predicate, timeout=5.0):
    """Run the runtime's event loop until predicate() or timeout; returns
    predicate()'s final value."""
    rt.run(until=predicate, timeout=timeout)
    return predicate()


def all_eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs (``pjit``,
    ``scan``, ``while``, ``cond`` bodies) too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from all_eqns(inner)
