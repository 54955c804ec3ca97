"""The flagship config-4 COMPOSITION: detect -> caption -> LLM with
placement blocks AND async stages, end to end through the real engine on
the 8-device virtual mesh.

The pieces are proven separately (tests/test_tensor.py placement,
tests/test_async_stages.py async park/resume + cross-frame batching);
this is the one test that runs them TOGETHER, the TPU equivalent of the
reference's remote-deploy pipeline parallelism (reference
src/aiko_services/main/pipeline.py:246-258,858-891 -- stages in other
processes; here stages on disjoint chip submeshes with ICI frame hops).
"""

import json
import queue

import numpy as np

from conftest import run_until

from aiko_services_tpu.pipeline import create_pipeline

N_FRAMES = 8
MAX_NEW = 8


def _definition(tmp_path):
    definition = {
        "version": 0, "name": "config4", "runtime": "jax",
        "graph": ["(DET (CAP (LLM)))"],
        "elements": [
            {"name": "DET",
             "input": [{"name": "image"}],
             "output": [{"name": "image"}, {"name": "overlay"},
                        {"name": "detections"}],
             "parameters": {"width": 4, "max_batch": 8},
             "placement": {"mesh": {"dp": 4}},
             "deploy": {"local": {
                 "module": "aiko_services_tpu.elements.detect",
                 "class_name": "Detector"}}},
            {"name": "CAP",
             "input": [{"name": "detections"}],
             "output": [{"name": "text"}],
             "deploy": {"local": {
                 "module": "aiko_services_tpu.elements.llm",
                 "class_name": "DetectionCaption"}}},
            {"name": "LLM",
             "input": [{"name": "text"}],
             "output": [{"name": "text"}],
             "parameters": {"max_new_tokens": MAX_NEW, "max_seq": 64},
             "placement": {"mesh": {"tp": 4}},
             "deploy": {"local": {
                 "module": "aiko_services_tpu.elements.llm",
                 "class_name": "LLM"}}},
        ]}
    path = tmp_path / "config4.json"
    path.write_text(json.dumps(definition))
    return str(path)


def test_config4_placed_async_composition(tmp_path, runtime):
    """detect on a 4-chip dp submesh, LLM on the OTHER 4 chips as tp=4,
    async stages on both ends: every frame completes, detect
    micro-batches the parked burst into fewer device dispatches, and
    the LLM decodes requests from many in-flight frames together --
    frames overlapped at both model stages."""
    pipeline = create_pipeline(_definition(tmp_path), runtime=runtime)

    # -- placement: disjoint submeshes straight from the definition ----
    placement = pipeline.stage_placement
    assert placement is not None
    assert dict(placement.plan("DET").mesh.shape) == {"dp": 4}
    assert dict(placement.plan("LLM").mesh.shape) == {"tp": 4}
    det_devices = set(placement.plan("DET").mesh.devices.flat)
    llm_devices = set(placement.plan("LLM").mesh.devices.flat)
    assert not det_devices & llm_devices

    responses = queue.Queue()
    stream = pipeline.create_stream_local("s", queue_response=responses)
    rng = np.random.default_rng(0)
    for _ in range(N_FRAMES):
        pipeline.create_frame_local(stream, {
            "image": rng.integers(0, 255, (64, 64, 3), dtype=np.uint8)})
    assert run_until(runtime, lambda: responses.qsize() >= N_FRAMES,
                     timeout=300.0)

    texts = []
    while not responses.empty():
        _, _, swag, metrics, okay, diagnostic = responses.get()
        assert okay, diagnostic
        texts.append(swag["text"])
        assert "DET_time" in metrics and "LLM_time" in metrics
    assert len(texts) == N_FRAMES

    # -- placement transfer: the detect element resolved ITS stage's
    # submesh (not the local default) and its weights live there.
    import jax
    det = pipeline.graph.get_node("DET").element
    assert dict(det.plan.mesh.shape) == {"dp": 4}
    for leaf in jax.tree_util.tree_leaves(det._params):
        assert set(leaf.sharding.device_set) <= det_devices

    # -- the LLM looks at ITS placement too (ISSUE 21): every parameter
    # and KV-cache leaf lives inside the LLM stage's submesh -- none on
    # the process default device, which belongs to the detector.
    llm = pipeline.graph.get_node("LLM").element
    where = llm.model_devices()
    assert where["params"] and where["params"] <= llm_devices, where
    assert where["cache"] and where["cache"] <= llm_devices, where
    assert not (where["params"] | where["cache"]) & det_devices
    assert jax.devices()[0] in det_devices      # the seed's landing spot

    # -- async composition, detect side: the parked burst ran as
    # MICRO-BATCHED dispatches, not one dispatch per frame.
    dispatches = det.jit_cache.hits + det.jit_cache.misses
    assert dispatches < N_FRAMES, (
        f"{dispatches} detect dispatches for {N_FRAMES} frames: parked "
        "frames were not micro-batched")

    # -- async composition, LLM side: requests from many in-flight
    # frames decoded together (total decode steps far below the
    # serialized sum) -- frames overlapped across the placed stages.
    batcher = pipeline.graph.get_node("LLM").element._batcher
    serialized = N_FRAMES * MAX_NEW
    assert batcher.steps < serialized * 0.6, (
        f"{batcher.steps} decode steps for {N_FRAMES} frames x "
        f"{MAX_NEW} tokens: frames did not overlap at the LLM stage")
    pipeline.stop()
