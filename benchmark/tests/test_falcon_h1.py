"""The falcon_h1 architecture file: its counts to the byte at
Falcon-H1-34B-Instruct's published widths (the layer table and the
decode step's bytes as the configuration reckons them), the
configuration file against the catalog row it was copied from, the
manifest's new files, the two new roofline shares on a made-up cut, and
the reference check's two controls at a small size."""

import dataclasses
import json
import os
import types

import pytest

from benchmark import architectures, run
from benchmark.readers import op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = run.load_json(ROOT, "benchmark", "configs",
                       "text-falcon-h1-34b-instruct.json")
FAMILY = architectures.load(CONFIG, [run.HERE])

# The catalog row's ``config`` (model-configs guide,
# Falcon-H1-34B-Instruct).
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}


def test_the_file_holds_every_published_key_to_the_letter():
    differ = {key for key, value in PUBLISHED.items()
              if CONFIG.get(key, "absent") != value}
    assert differ == {"num_hidden_layers", "max_position_embeddings"} \
        == set(CONFIG["reduced"]) == set(CONFIG["reduced_why"])
    assert CONFIG["source_values"] == {
        key: PUBLISHED[key] for key in CONFIG["reduced"]}
    assert (CONFIG["num_hidden_layers"],
            CONFIG["max_position_embeddings"]) == (6, 1280)
    assert {"init", "mup_segments", "gated_norm", "serving_precision",
            "tokenizer", "ignore_eos", "garbage_collection"} \
        <= set(CONFIG["assumed"])
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(entry for entry in manifest["configs"]
                 if entry["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    served = CONFIG["definition"]["elements"][0]["parameters"]
    assert served["max_seq"] == CONFIG["max_position_embeddings"]
    assert served["kv_pages"] == served["max_slots"] * 10 + 1 == 801
    assert "quantize" not in served and served["attention"] == "flash"


def test_the_manifests_new_files_load():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = next(cell for cell in manifest["workloads"]
                if cell["name"] == "reply-batch")
    assert cell["config"] == CONFIG["name"] and cell["chips"] == 1
    workload = run.load_cell("reply-batch")["workload"]
    assert (workload["loop"], workload["sessions"]) == ("closed", 120)
    assert workload["payload"]["text"]["text_tokens"] == {
        "dist": "loguniform", "lo": 128, "hi": 1024, "pool": 256}
    assert workload["new_tokens"] == 256 and workload["window"] == 4
    for name in ("kernel.ssd_decode_roofline",
                 "kernel.ssd_prefill_roofline"):
        metric = run.layer_metric(name)
        entry = next(entry for entry in manifest["per_layer"]
                     if entry["name"] == name)
        assert metric["kind"] == "op_roofline"
        assert entry["workloads"] == metric["workloads"] == ["reply-batch"]
        assert all(entry[key] == metric[key] for key in
                   ("unit", "better", "source", "layer", "moves"))
        assert callable(getattr(FAMILY, metric["args"]["count"]))


def test_element_parameters_and_widths_are_clean():
    from aiko_services_tpu.models.falcon_h1 import FalconH1Config
    from aiko_services_tpu.models.families import (FAMILY_WIDTHS,
                                                   family_spec_error)
    handed = FAMILY.element_parameters(CONFIG)
    assert handed["family"] == "falcon_h1"
    assert FAMILY.WIDTH_FIELDS == FAMILY_WIDTHS["falcon_h1"]
    assert family_spec_error(handed) is None
    served = FalconH1Config.from_widths(handed["widths"], max_seq=1280)
    batcher = types.SimpleNamespace(config=served)
    assert FAMILY.width_differences(CONFIG, batcher) == []
    other = types.SimpleNamespace(config=dataclasses.replace(
        served, ssm_multipliers=(1.0,) * 5, n_layers=4, max_seq=2048))
    assert {key for key, _, _ in FAMILY.width_differences(
        CONFIG, other)} == {"ssm_multipliers", "num_hidden_layers",
                            "max_position_embeddings"}


# The configuration's arithmetic, by hand.
ATTENTION = 2 * 5120 * 2560 + 2 * 5120 * 512
W_IN = 5120 * 9248
W_OUT = 4096 * 5120
MLP = 3 * 5120 * 21504
VECTORS = 5 * 5120 + 3 * 32 + 4096 + 2 * 5120
HEAD = 5120 * 261120
STATE = 32 * 128 * 256 * 4          # one slot, one layer, float32
TAIL = 3 * 5120 * 4
TOKEN = 4 * (2 * 4096 + 2 * 512 + 32)
KV_TOKEN = 6 * 2 * 4 * 128 * 2


def test_the_layer_table():
    assert ATTENTION == 31_457_280 and W_IN == 47_349_760
    assert W_OUT == 20_971_520 and MLP == 330_301_440
    assert VECTORS == 29_792 + 10_240
    assert FAMILY.layer_weights(CONFIG) + FAMILY.layer_vectors(CONFIG) \
        == ATTENTION + W_IN + W_OUT + MLP + VECTORS == 430_120_032
    assert HEAD == 1_336_934_400
    assert round((6 * 430_120_032 + 2 * HEAD) * 2 / 1e9, 3) == 10.509
    assert FAMILY.state_bytes(CONFIG) == STATE == 4_194_304
    assert FAMILY.tail_bytes(CONFIG) == TAIL == 61_440
    assert round(80 * 6 * (STATE + TAIL) / 1e9, 3) == 2.043
    assert FAMILY.kv_bytes_per_token(CONFIG) == KV_TOKEN
    assert 801 * 128 * KV_TOKEN == 801 * 1_572_864
    assert round(801 * 128 * KV_TOKEN / 1e9, 3) == 1.260
    assert FAMILY.token_bytes(CONFIG) == TOKEN
    assert FAMILY.prefill_chunk(CONFIG) == 512


@pytest.mark.parametrize("rows,context", [(1, 0), (80, 560), (64, 900)])
def test_a_decode_step_to_the_byte(rows, context):
    work = FAMILY.decode_step(CONFIG, rows, context)
    streamed = 6 * 430_120_032 + HEAD
    state = rows * 6 * (2 * STATE + TOKEN)
    assert work["bytes"] == pytest.approx(
        2 * streamed + state + rows * 6 * 2 * TAIL
        + rows * context * KV_TOKEN, rel=1e-12)
    assert work["operations"] == pytest.approx(
        2.0 * streamed * rows + rows * 6 * 5 * 32 * 128 * 256
        + rows * context * 6 * 4 * 20 * 128, rel=1e-12)
    kernel = FAMILY.ssd_decode(CONFIG, rows, context)
    assert kernel["bytes"] == state
    assert kernel["operations"] == rows * 6 * 5 * 32 * 128 * 256


def test_a_full_step_streams_what_the_configuration_reckons():
    # 7.84 GB of weights, 4.09 GB of state both ways, ~0.55 GB of K/V:
    # state is a third of the step's bytes
    work = FAMILY.decode_step(CONFIG, 80, 560)
    weights = 2 * (6 * 430_120_032 + HEAD)
    state = 80 * 6 * 2 * (STATE + TAIL)
    assert round(weights / 1e9, 2) == 7.84 and round(state / 1e9, 2) == 4.09
    assert round(80 * 560 * KV_TOKEN / 1e9, 2) == 0.55
    assert 0.32 < state / work["bytes"] < 0.34
    token = FAMILY.ssd_chunk(CONFIG)
    assert token["bytes"] == 6 * (TOKEN + 2 * STATE / 512)
    assert token["operations"] == 6 * 5_373_952
    assert round(token["operations"] / 6 / 1e6, 1) == 5.4


def _context(ops, slice_counters):
    """A reader's context over a made-up cut: ``ops`` are (name,
    start_ns, duration_ns) of one device's op line."""
    return types.SimpleNamespace(
        architecture=FAMILY, config=CONFIG, notes={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        cut={"devices": {"/device:TPU:0": {"modules": [], "ops": ops}},
             "host": []},
        slice_counters=slice_counters,
        counters={"client.answered": 100, "batcher.prefill_tokens": 45_000},
        workload={"new_tokens": 256},
        metric=lambda name: {"batcher.rows_per_step": 78.0}[name])


def _args(name):
    return json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", f"{name}.json")))["args"]


def test_the_shares_from_a_cut():
    # 48 decode calls (8 steps x 6 layers) of 1.2 ms
    ops = [[f"%ssd_decode_step.{index % 6} = custom-call(...)",
            1000 + index * 1_500_000, 1_200_000] for index in range(48)]
    context = _context(ops, {"batcher.steps": 8})
    share = op_roofline.read(_args("kernel.ssd_decode_roofline"), context)
    least = 78 * 6 * (2 * STATE + TOKEN) / 819e9        # memory-bound
    assert share == pytest.approx(100 * least * 8 / (48 * 1.2e-3))
    assert 0 < share < 100
    assert context.notes["ssd_decode"]["bound"] == "memory"
    # 18 chunk-scan calls (3 chunks x 6 layers) of 0.4 ms, 1,100 tokens
    ops = [[f"%ssd_chunk_scan.{index % 6} = custom-call(...)",
            1000 + index * 500_000, 400_000] for index in range(18)]
    context = _context(ops, {"batcher.prefill_tokens": 1100})
    share = op_roofline.read(_args("kernel.ssd_prefill_roofline"), context)
    token = FAMILY.ssd_chunk(CONFIG)
    least = max(token["bytes"] / 819e9, token["operations"] / 197e12)
    assert share == pytest.approx(100 * least * 1100 / (18 * 0.4e-3))
    assert 0 < share < 100
    # the parent's programs have no such op: nothing, not an error
    for name in ("kernel.ssd_decode_roofline",
                 "kernel.ssd_prefill_roofline"):
        assert op_roofline.read(_args(name), _context(
            [["%fusion.1 = fusion(...)", 0, 1000]],
            {"batcher.steps": 8, "batcher.prefill_tokens": 1100})) is None


def test_the_reference_checks_controls_fail_at_a_small_size():
    """bfloat16 served at the preset's tiny widths: both stretches pass
    a limit that the reference in fp8 -- the nearest precision below
    the stated one -- and the reference whose mixer forgets at every
    admission chunk both fail."""
    import jax
    from aiko_services_tpu.models import falcon_h1
    from aiko_services_tpu.models.batching import ContinuousBatcher
    config = falcon_h1.FalconH1Config.tiny()
    params = falcon_h1.init_params(jax.random.PRNGKey(3), config)
    batcher = ContinuousBatcher(params, config, max_slots=6, max_seq=256,
                                prefill_chunk=64, kv_page_tokens=16,
                                decode_block_tokens=4)
    spec = {"prompt_tokens": 150, "decode_steps": 12,
            "loop_prompt_tokens": [16, 128], "loop_sequences": 5,
            "loop_first": 2, "loop_new_tokens": 6, "loop_join": 2}
    limit = 0.045
    served = FAMILY.check_reference(batcher, 7, spec)
    assert served["max_abs_diff"] < limit, served
    assert served["positions"] == 15 and served["loop_mismatches"] == 0
    assert served["loop_slots_reused"] == 2
    for control in FAMILY.CONTROLS:
        failed = FAMILY.check_reference(batcher, 7, spec, control)
        assert failed["max_abs_diff"] > 2 * limit, (control, failed)
    with pytest.raises(ValueError, match="control"):
        FAMILY.check_reference(batcher, 7, spec, "int4")
