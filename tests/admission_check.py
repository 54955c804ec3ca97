"""Standalone batched-vs-single admission equality check (run by
test_models.py::test_batched_admission_matches_single in a SUBPROCESS --
see that test's docstring for why).  Exits 0 on success, 1 with a
diagnostic on mismatch.

Determinism (round 5): the exact-stream comparison requires the
[N*S, dim] batched prefill GEMM and the [S, dim] single-slot GEMM to
round IDENTICALLY.  With multi-threaded Eigen GEMMs the partitioning --
and therefore the summation order -- varies with machine load, which
flips near-tie argmaxes intermittently (~1-in-7 under a loaded host;
reproduced round 5 in fresh processes, so this, not cross-test buffer
state, was the flake's root cause).  Single-threaded GEMMs + highest
matmul precision make both shapes round identically run-to-run
(0 failures across repeated loaded-host trials).

ISSUE 31: the batched side is the device loop (the fused-block driver
it used to run is gone), and the model is float32, as in the other
equivalence tests: under six test workers the bfloat16 check still
failed one run in three or four (never alone), and the cache can only
agree over the decode positions where the streams do, so the streams
stay compared and bfloat16's near-ties go."""

import dataclasses
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_cpu_multi_thread_eigen=false").strip()
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np

from aiko_services_tpu.models import llama
from aiko_services_tpu.models.batching import ContinuousBatcher, Request


def main() -> int:
    config = dataclasses.replace(llama.LlamaConfig.tiny(),
                                 dtype="float32")
    params = llama.init_params(jax.random.PRNGKey(0), config)
    prompts = [[1, 2, 3], list(range(1, 41)), list(range(5, 22)), [7]]

    def run(block, inflight):
        streams = {}
        batcher = ContinuousBatcher(params, config, max_slots=4,
                                    max_seq=64, prefill_chunk=16,
                                    decode_block_tokens=block,
                                    inflight=inflight)
        for i, prompt in enumerate(prompts):
            batcher.submit(Request(
                f"r{i}", list(prompt), max_new_tokens=6,
                emit=lambda r, t, f: streams.setdefault(r, []).append(t)))
        steps = batcher.run_until_drained(max_steps=400)
        assert steps < 400, f"did not drain in {steps} steps"
        return batcher, streams

    single, single_streams = run(0, 1)
    batched, batched_streams = run(4, 3)
    if single_streams != batched_streams:
        print(f"token stream mismatch: single={single_streams} "
              f"batched={batched_streams}")
        return 1
    if any(len(s) != 6 for s in single_streams.values()):
        print(f"budget mismatch: {single_streams}")
        return 1
    # And the caches agree over the prompt plus every decode position
    # BOTH paths define: tokens t1..t5 write positions P..P+4; the
    # final token t6's KV at P+5 is written only by the device loop's
    # overshoot (the single path frees the slot at budget before
    # processing t6) -- a don't-care position beyond the freed slot's
    # live region, excluded here.
    single_k = np.asarray(llama.cache_array(single.cache), np.float32)
    batched_k = np.asarray(llama.cache_array(batched.cache), np.float32)
    for i, prompt in enumerate(prompts):
        extent = len(prompt) + 5
        a = batched_k[:, i, :extent]
        b = single_k[:, i, :extent]
        if not np.allclose(a, b, atol=1e-4, rtol=1e-4):
            print(f"slot {i} KV mismatch: max diff "
                  f"{np.abs(a - b).max()}")
            return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
